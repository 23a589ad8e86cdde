"""A training step: the jitted step of ``training/step.build_train_step``,
built as ``launch/train.run`` builds it (one-chip mesh, remat on, params and
optimizer state donated), driven back to back on a pool of seeded batches.

Set-up drives that same compiled step through the first ``check_steps``
steps, reading the first gradient from the optimizer's state after step 1
and the parameters' change after the last, and hands the state on to the
window.  After the window the plain reference repeats those steps in float32
from the same weights and batches, and the two are compared.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

import flops
import traffic as tr
import weights


def leaf_norms(tree):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])


def leaf_gaps(prog, ref, keep):
    """Each kept leaf's |prog - ref| / max(ref, median ref)."""
    prog, ref = np.asarray(prog)[keep], np.asarray(ref)[keep]
    return np.abs(prog - ref) / np.maximum(ref, np.median(ref))


def adamw_config(optimizer: dict):
    """The program's AdamW for the mix's ``optimizer`` section.  The program
    decays every leaf stored with ``decay_min_ndim`` dimensions or more, a
    rule fixed in its code; the section states it for the reference."""
    from repro.training import optimizer as opt
    return opt.AdamWConfig(**{k: v for k, v in optimizer.items()
                              if k != "decay_min_ndim"})


class Cell:
    def __init__(self, cfg, conf, mix, seed, reference):
        from repro.models import registry as mr
        self.cfg, self.conf, self.mix, self.seed = cfg, conf, mix, seed
        self.arch = conf["architecture"]
        self.ref = reference
        self.model = mr.build(cfg)
        self.adamw = adamw_config(mix["optimizer"])
        self.batch, self.seq = mix["batch"], mix["seq"]

    def context(self):
        from repro.distributed import sharding as sh
        from repro.launch.train import build_mesh
        self.mesh = build_mesh("1x1")
        return sh.mesh_context(self.mesh, act_mode="tp", remat=True)

    # ----- set-up -----
    def setup(self):
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.distributed import specs as sp
        from repro.training import optimizer as opt
        from repro.training import step as tstep
        abstract = self.model.abstract_params()
        self.paths = weights.leaf_paths(abstract)
        self.build = weights.builder(abstract, "float32", self.cfg.d_model)
        params = jax.jit(self.build)(*weights.seed_args(self.seed))
        opt_state = jax.jit(opt.init_opt_state)(params)
        ns = lambda tree: jax.tree.map(lambda s: NamedSharding(self.mesh, s),
                                       tree, is_leaf=lambda s: isinstance(s, P))
        p_specs = sp.params_specs(params)
        params = jax.device_put(params, ns(p_specs))
        opt_state = jax.device_put(opt_state, ns(sp.opt_specs(opt_state, p_specs)))
        pool = tr.token_block(self.seed, "train", (self.mix["data_pool"],
                              self.batch, self.seq + 1), self.cfg.vocab_size)
        self.batches = [{"tokens": pool[i, :, :-1], "labels": pool[i, :, 1:]}
                        for i in range(pool.shape[0])]
        self.step = self.make_step(tstep.build_train_step(
            self.model, self.adamw, num_microbatches=1, block_skip=False,
            fused_ce=True))

        # the first steps, through the window's own call and feed
        n_check = self.mix["check_steps"]
        losses, times = [], []
        for i in range(n_check):
            t = time.perf_counter()
            params, opt_state, metrics = self.step(params, opt_state,
                                                   self.batches[i])
            jax.block_until_ready(metrics)
            times.append(time.perf_counter() - t)
            losses.append(metrics["loss"])
            if i == 0:
                # the optimizer's first moment after one step is
                # (1 - b1) times the gradient it was given
                self.grad_norms = jax.jit(leaf_norms)(opt_state.m) \
                    / (1.0 - self.adamw.b1)
        self.losses = jnp.stack(losses)
        self.change_norms = jax.jit(lambda p, lo, hi: leaf_norms(
            jax.tree.map(jnp.subtract, p, self.build(lo, hi))))(
                params, *weights.seed_args(self.seed))
        self.state = (params, opt_state)
        self.steps_done = n_check
        self.step_estimate_s = min(times[1:])

    def make_step(self, step_fn):
        """The window's step: jitted, params and optimizer state donated."""
        return jax.jit(step_fn, donate_argnums=(0, 1))

    def work(self) -> dict:
        f = flops.train_flops(self.arch, self.batch, self.seq)
        return {"flops_per_step": f, "dot_flops_per_step": f}

    # ----- window -----
    def run_steps(self, n: int) -> float:
        params, opt_state = self.state
        pool = len(self.batches)
        t = time.perf_counter()
        for i in range(self.steps_done, self.steps_done + n):
            params, opt_state, metrics = self.step(params, opt_state,
                                                   self.batches[i % pool])
        jax.block_until_ready((params, opt_state, metrics))
        elapsed = time.perf_counter() - t
        self.state = (params, opt_state)
        self.steps_done += n
        self.n_window = n
        return elapsed

    def predicted_step_s(self, svc) -> float:
        return svc.latency_train(self.cfg, self.batch, self.seq,
                                 dtype=self.cfg.compute_dtype).seconds

    def release(self):
        self.prog = {"losses": np.asarray(self.losses),
                     "grad": np.asarray(self.grad_norms),
                     "change": np.asarray(self.change_norms)}
        self.check_batches = [(b["tokens"], b["labels"])
                              for b in self.batches[:self.mix["check_steps"]]]
        del self.state, self.batches, self.step
        self.losses = self.grad_norms = self.change_norms = None

    # ----- check -----
    def reference_readings(self, prec: str = "f32") -> dict:
        """The reference's losses and leaf norms over the checked steps."""
        params = jax.jit(self.build)(*weights.seed_args(self.seed))
        losses, grad, final = self.ref.train(
            params, self.check_batches, self.arch, self.mix["optimizer"], prec)
        change = jax.jit(lambda p, lo, hi: leaf_norms(
            jax.tree.map(jnp.subtract, p, self.build(lo, hi))))(
                final, *weights.seed_args(self.seed))
        return {"losses": np.asarray(losses), "grad": np.asarray(grad),
                "change": np.asarray(change)}

    def compare(self, prog: dict, ref: dict) -> tuple[dict, list]:
        """The numbers compared, and notes on where each was worst.  The
        losses are noted, not compared: their gap is rounding noise that
        reaches the float8 control's (PERF.md, Findings)."""
        loss_gaps = np.abs(prog["losses"] - ref["losses"]) / np.abs(ref["losses"])
        # leaves whose reference gradient is nought to rounding (a key's
        # bias under softmax) move under Adam by round-off alone
        keep = ref["grad"] >= 1e-3 * np.median(ref["grad"])
        grad = leaf_gaps(prog["grad"], ref["grad"], keep)
        change = leaf_gaps(prog["change"], ref["change"], keep)
        kept = [p for p, k in zip(self.paths, keep) if k]
        # the worst leaf catches a state left unchanged; the mean over the
        # leaves, steadier than one small bias, separates the float8 control
        numbers = {"grad_gap": float(grad.max()),
                   "grad_gap_mean": float(grad.mean()),
                   "update_gap": float(change.max())}
        notes = [f"losses program={prog['losses'].tolist()} "
                 f"reference={ref['losses'].tolist()} "
                 f"gaps={loss_gaps.tolist()}",
                 f"grad_gap worst leaf {kept[int(grad.argmax())]}; update_gap "
                 f"worst leaf {kept[int(change.argmax())]}; leaves left out: "
                 f"{[p for p, k in zip(self.paths, keep) if not k]}"]
        return numbers, notes

    def check(self) -> tuple[dict, list]:
        self.ref_f32 = self.reference_readings("f32")
        return self.compare(self.prog, self.ref_f32)

    def control(self) -> dict:
        """The control's numbers: the reference in float8 in the program's
        place (after ``check``)."""
        return self.compare(self.reference_readings("fp8"), self.ref_f32)[0]
