"""jit'd public wrappers around the Pallas kernels: ragged-shape padding,
GQA head folding, config auto-selection, CPU interpret fallback.

Off the TPU the kernels execute with ``interpret=True`` (Pallas' Python
evaluator); on a TPU the same call sites compile to Mosaic.
``interpret_default`` is that rule, shared with ``core/calibrate.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as fk
from repro.kernels import matmul as mk


def interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def matmul(a, b, config: mk.MatmulConfig | None = None, *, out_dtype=None,
           interpret: bool | None = None):
    """a (M,K) @ b (K,N) with padding to the selected kernel's blocks."""
    M, K = a.shape
    _, N = b.shape
    config = config or mk.select_config(M, N, K, a.dtype)
    interpret = interpret_default() if interpret is None else interpret
    pm, pk, pn = (-M) % config.bm, (-K) % config.bk, (-N) % config.bn
    ap = jnp.pad(a, ((0, pm), (0, pk))) if (pm or pk) else a
    bp = jnp.pad(b, ((0, pk), (0, pn))) if (pk or pn) else b
    o = mk.matmul_kernel(ap, bp, config, out_dtype=out_dtype,
                         interpret=interpret)
    return o[:M, :N] if (pm or pn) else o


def flash_attention(q, k, v, config: fk.FlashConfig | None = None, *,
                    causal=True, window=None, interpret: bool | None = None):
    """q (B,Sq,Hq,hd), k/v (B,Skv,Hkv,hd) -> (B,Sq,Hq,hd).  GQA via KV head
    repeat; (B,H) folded into the kernel grid's batch dimension."""
    B, Sq, Hq, hd = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    config = config or fk.select_config(Sq, Skv, hd)
    interpret = interpret_default() if interpret is None else interpret
    if Hq != Hkv:
        rep = Hq // Hkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    fold = lambda x: x.transpose(0, 2, 1, 3).reshape(B * Hq, x.shape[1], hd)
    o = fk.flash_attention_kernel(fold(q), fold(k), fold(v), config,
                                  causal=causal, window=window,
                                  q_offset=Skv - Sq, interpret=interpret)
    return o.reshape(B, Hq, Sq, hd).transpose(0, 2, 1, 3)
