"""Training launcher: mesh setup, sharded jit, fault-tolerant loop.

Runs for real on whatever devices exist (1 CPU here; set
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` before python to
exercise a small mesh).  The same entrypoint is the per-host main() on a
real cluster — jax.distributed.initialize is attempted when the standard
coordinator env vars are present.

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b --reduced \
      --steps 30 --batch 8 --seq 64
  PYTHONPATH=src python -m repro.launch.train --arch moonshot-v1-16b-a3b \
      --reduced --steps 10 --fail-at 5 --ckpt-every 2
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint.store import CheckpointStore
from repro.configs import registry as cr
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.distributed import sharding as sh
from repro.distributed import specs as sp
from repro.ft import driver as ftd
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import registry as mr
from repro.training import optimizer as opt
from repro.training import step as tstep


def maybe_init_distributed():
    if "JAX_COORDINATOR" in os.environ:
        jax.distributed.initialize(
            coordinator_address=os.environ["JAX_COORDINATOR"],
            num_processes=int(os.environ.get("JAX_NUM_PROCESSES", "1")),
            process_id=int(os.environ.get("JAX_PROCESS_ID", "0")))


def build_mesh(spec: str):
    """spec 'dxm' e.g. '2x2'; '1x1' -> single device mesh."""
    d, m = (int(x) for x in spec.split("x"))
    return make_host_mesh(d, m)


def run(args) -> dict:
    maybe_init_distributed()
    cfg = cr.reduced(args.arch) if args.reduced else cr.get_any(args.arch)
    if args.compute_dtype:
        cfg = dataclasses.replace(cfg, compute_dtype=args.compute_dtype)
    model = mr.build(cfg)
    mesh = build_mesh(args.mesh)
    adamw = opt.AdamWConfig(lr=args.lr, warmup_steps=max(args.steps // 20, 5),
                            total_steps=args.steps)
    data = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                                  global_batch=args.batch, seed=args.seed))

    with sh.mesh_context(mesh, act_mode=args.act_mode, remat=not args.no_remat):
        params = model.init(jax.random.key(args.seed))
        opt_state = opt.init_opt_state(params)
        p_specs = sp.params_specs(params)
        o_specs = sp.opt_specs(opt_state, p_specs)
        ns = lambda tree: jax.tree.map(lambda s: NamedSharding(mesh, s), tree,
                                       is_leaf=lambda s: isinstance(s, P))
        params = jax.device_put(params, ns(p_specs))
        opt_state = jax.device_put(opt_state, ns(o_specs))

        step_fn = tstep.build_train_step(
            model, adamw, num_microbatches=args.microbatches,
            block_skip=args.block_skip, fused_ce=not args.naive_ce)
        jit_step = jax.jit(step_fn, donate_argnums=(0, 1))

        store = CheckpointStore(args.ckpt_dir, keep=3,
                                async_write=not args.sync_ckpt)
        injector = ftd.FailureInjector(tuple(args.fail_at or ()))
        monitor = ftd.StragglerMonitor()

        def wrapped_step(state, batch):
            params, opt_state = state
            if model.needs_ctx():
                batch = dict(batch)
                batch["ctx"] = model.make_ctx(jax.random.key(0),
                                              batch["tokens"].shape[0])
            params, opt_state, metrics = jit_step(params, opt_state, batch)
            return (params, opt_state), metrics

        t0 = time.time()
        (params, opt_state), log = ftd.run_training(
            step_fn=wrapped_step, init_state=(params, opt_state), data=data,
            num_steps=args.steps, store=store, ckpt_every=args.ckpt_every,
            injector=injector, monitor=monitor)
        wall = time.time() - t0

    result = {"losses": log.losses, "steps": log.steps,
              "step_s": log.step_seconds,
              "restarts": log.restarts, "wall_s": wall,
              "straggler_events": log.straggler_events,
              "final_loss": log.losses[-1] if log.losses else float("nan"),
              "first_loss": log.losses[0] if log.losses else float("nan")}
    if args.verbose:
        print(f"[train] arch={cfg.name} steps={args.steps} "
              f"loss {result['first_loss']:.3f} -> {result['final_loss']:.3f} "
              f"restarts={log.restarts} wall={wall:.1f}s")
    return result


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--mesh", default="1x1")
    ap.add_argument("--act-mode", default="tp", choices=["tp", "sp"])
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--block-skip", action="store_true")
    ap.add_argument("--naive-ce", action="store_true")
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--compute-dtype", default="float32")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--sync-ckpt", action="store_true")
    ap.add_argument("--fail-at", type=int, nargs="*", default=None)
    ap.add_argument("--verbose", action="store_true", default=True)
    return ap.parse_args(argv)


def main(argv=None) -> dict:
    enable_compile_cache()
    return run(parse_args(argv))


if __name__ == "__main__":
    main()
