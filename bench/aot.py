"""Compile each cell's jitted programs at their real sizes for a described
TPU v5e, with no chip attached, and print what the compiler says they hold.

  JAX_PLATFORMS=cpu python bench/aot.py [cell ...]

Nothing runs: the numbers are the compiler's ``memory_analysis()`` of each
program (arguments, outputs, temporaries, in bytes), one program at a time.
"""
from __future__ import annotations

import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import common  # noqa: E402


def on(sharding, tree, dtype=None):
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        x.shape, dtype if (dtype is not None and jnp.issubdtype(
            x.dtype, jnp.floating)) else x.dtype, sharding=sharding), tree)


def programs(name: str, one_chip):
    """(label, jitted fn, abstract args) of each program the cell runs."""
    from repro.models import registry as mr
    from repro.training import optimizer as opt
    from repro.training import step as tstep
    _, conf, mix = common.cell(name)
    cfg = common.model_config(conf)
    model = mr.build(cfg)
    cdt = jnp.dtype(cfg.compute_dtype)
    i32 = lambda shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)
    kind = mix["kind"]
    import run as bench_run
    ref = bench_run.load("reference", conf["reference"])
    arch = conf["architecture"]
    if kind == "train":
        params = on(one_chip, model.abstract_params())
        state = on(one_chip, opt.abstract_opt_state(model.abstract_params()))
        B, S = mix["batch"], mix["seq"]
        adamw = bench_run.load("kinds", "train").adamw_config(mix["optimizer"])
        step = jax.jit(tstep.build_train_step(model, adamw),
                       donate_argnums=(0, 1))
        yield "train_step", step, (params, state,
                                   {"tokens": i32((B, S)), "labels": i32((B, S))})
        yield "reference_grad", jax.jit(jax.value_and_grad(
            lambda p, t, lb: ref.loss(p, t, lb, arch))), (
                params, i32((B, S)), i32((B, S)))
    elif kind == "forward":
        params = on(one_chip, model.abstract_params(), cdt)
        B, S = mix["batch"], mix["seq"]
        yield "forward", jax.jit(model.forward), (params, i32((B, S)))
        yield "reference_gaps", jax.jit(lambda p, t, c: ref.gaps_at(
            p, t, c, 0, arch)), (params, i32((B, S)), i32((B, S)))


def main(argv=None) -> int:
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from repro.distributed import sharding as sh
    names = (argv if argv else sys.argv[1:]) or [
        w["name"] for w in common.benchmark()["workloads"]]
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    mesh = jax.sharding.Mesh([[topo.devices[0]]], ("data", "model"))
    for name in names:
        for label, fn, args in programs(name, one_chip):
            with sh.mesh_context(mesh if label == "train_step" else None,
                                 act_mode="tp", remat=True):
                m = fn.lower(*args).compile().memory_analysis()
            gib = lambda b: b / 2**30
            print(f"{name} {label}: arguments {gib(m.argument_size_in_bytes):.2f} "
                  f"GiB, outputs {gib(m.output_size_in_bytes):.2f} GiB, "
                  f"aliased {gib(m.alias_size_in_bytes):.2f} GiB, temporaries "
                  f"{gib(m.temp_size_in_bytes):.2f} GiB", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
