"""A forward pass: ``models/transformer.forward`` jitted whole, over one
seeded batch, back to back, with weights in the type they are served in.

The check takes the window's last output: at every position, the token its
logits rank first.  The plain reference runs once over the same tokens, and
the widest gap by which such a token's reference logit lies below the
reference's best is compared with its limit.
"""
from __future__ import annotations

import contextlib
import time

import jax
import jax.numpy as jnp
import numpy as np

import flops
import traffic as tr
import weights


def first_tokens(vocab: int):
    """Jitted: the token each row of logits ranks first, over the real
    vocabulary (the padded tail is never served)."""
    return jax.jit(lambda logits: jnp.argmax(logits[..., :vocab], -1)
                   .astype(jnp.int32))


class Cell:
    def __init__(self, cfg, conf, mix, seed, reference):
        from repro.models import registry as mr
        self.cfg, self.conf, self.mix, self.seed = cfg, conf, mix, seed
        self.arch = conf["architecture"]
        self.ref = reference
        self.model = mr.build(cfg)
        self.batch, self.seq = mix["batch"], mix["seq"]

    def context(self):
        return contextlib.nullcontext()

    def setup(self):
        abstract = self.model.abstract_params()
        self.build = weights.builder(abstract, self.cfg.compute_dtype,
                                     self.cfg.d_model)
        self.params = jax.jit(self.build)(*weights.seed_args(self.seed))
        self.tokens = tr.token_block(self.seed, "forward",
                                     (self.batch, self.seq), self.cfg.vocab_size)
        self.fwd = self.make_forward()
        self.pick = first_tokens(self.cfg.vocab_size)
        times = []
        for _ in range(3):
            t = time.perf_counter()
            out = self.fwd(self.params, self.tokens)
            jax.block_until_ready(out)
            times.append(time.perf_counter() - t)
            del out
        self.pick(jnp.zeros((1, 1, self.model.padded_vocab),
                            jnp.dtype(self.cfg.compute_dtype)))
        self.step_estimate_s = min(times[1:])

    def make_forward(self):
        return jax.jit(self.model.forward)

    def work(self) -> dict:
        f = flops.forward_flops(self.arch, self.batch, self.seq)
        return {"flops_per_step": f, "dot_flops_per_step": f}

    def run_steps(self, n: int) -> float:
        # Each call returns the logits of every position, gigabytes of them;
        # a dispatch queue running ahead would hold one set per queued call,
        # so each call is waited for before the next is sent.  The host's
        # round trip is well under a millisecond against steps of tenths of
        # a second.
        out = None
        t = time.perf_counter()
        for _ in range(n):
            out = None
            out = self.fwd(self.params, self.tokens)
            jax.block_until_ready(out)
        elapsed = time.perf_counter() - t
        self.last = out[0]
        self.n_window = n
        return elapsed

    def predicted_step_s(self, svc) -> float:
        return svc.latency_query(self.cfg, self.batch, self.seq,
                                 dtype=self.cfg.compute_dtype).seconds

    def release(self):
        self.chosen = np.asarray(self.pick(self.last))
        self.tokens_host = np.asarray(self.tokens)
        del self.params, self.last, self.tokens, self.fwd

    def reference_gaps(self, chooser_prec=None) -> np.ndarray:
        params = jax.jit(self.build)(*weights.seed_args(self.seed))
        gaps = jax.jit(lambda p, t, c: self.ref.gaps_at(
            p, t, c, 0, self.arch, chooser_prec))(
                params, jnp.asarray(self.tokens_host), jnp.asarray(self.chosen))
        return np.asarray(gaps)

    def control(self) -> dict:
        """The control's number: the gaps of the tokens that the reference
        in float8 ranks first."""
        return {"logit_gap": float(self.reference_gaps("fp8").max())}

    def check(self) -> tuple[dict, list]:
        gaps = self.reference_gaps()
        i = np.unravel_index(np.argmax(gaps), gaps.shape)
        return ({"logit_gap": float(gaps.max())},
                [f"logit_gap over {gaps.size} positions, widest at row "
                 f"{i[0]} position {i[1]}; median {float(np.median(gaps))}"])
