"""Attention: GQA + RoPE, memory-efficient (flash-style) training path with a
custom VJP, plain decode path with full / ring-buffer KV caches.

Layouts: activations (B, S, d); q (B, S, Hq, hd); k/v (B, S, Hkv, hd).

The training/prefill path never materializes the (S, S) score matrix: it
scans over KV blocks with an online softmax (forward) and recomputes scores
blockwise in the backward pass (FlashAttention-2 algorithm in pure JAX).  The
Pallas kernel in repro/kernels/flash_attention.py is the TPU-tiled version of
the same algorithm; this module is its jnp twin and the dry-run lowering path.

``causal_block_skip``: when True, strictly-upper-triangular KV blocks are not
computed at all (outer unrolled loop over query blocks, inner scan bounded by
the diagonal) — halves attention FLOPs for causal masks.  This is a
first-class §Perf knob; default False (paper-faithful dense-masked baseline).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.distributed import sharding as sh
from repro.models import layers as L

import os
DEFAULT_KV_BLOCK = int(os.environ.get("REPRO_KV_BLOCK", "256"))
NEG_INF = -1e30
# Name scope of the attention core (scores, softmax, values; forward and
# backward): compiled instructions carry it in their ``op_name`` metadata,
# so a device trace can tell attention ops from the projections around them.
SCOPE = "attention"


class AttnSpec(NamedTuple):
    causal: bool
    window: Optional[int] = None     # sliding window (causal) if set
    kv_block: int = DEFAULT_KV_BLOCK
    causal_block_skip: bool = False


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float):
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def apply_rope(x, positions, theta: float):
    """x: (..., S, H, hd); positions: broadcastable to (..., S)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                       # (hd/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs   # (..., S, hd/2)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    return jnp.concatenate([y1.astype(x.dtype), y2.astype(x.dtype)], axis=-1)


# ---------------------------------------------------------------------------
# Flash attention (pure JAX, custom VJP)
# ---------------------------------------------------------------------------

def _block_mask(q_pos, kv_pos, spec: AttnSpec, kv_len=None):
    """(Sq, Bk) ADDITIVE mask (0 / -inf) for one KV block; None if unmasked.

    Additive f32 (not boolean where) so that when XLA hoists the
    loop-indexed mask computation out of the KV scan it materializes only the
    (Sq, blk) pre-broadcast tensor, never the (B, H, Sq, blk) broadcast —
    this was a 3.5 GiB/device temp in the first dry-run (§Perf).

    ``kv_len``: true KV length when the cache was padded to a block multiple
    (ragged contexts, e.g. whisper's 1500 frames / vision's 1601 patches).
    """
    if not spec.causal and kv_len is None:
        return None
    m = None
    if spec.causal:
        m = q_pos[:, None] >= kv_pos[None, :]
        if spec.window is not None:
            m &= (q_pos[:, None] - kv_pos[None, :]) < spec.window
    if kv_len is not None:
        valid = (kv_pos < kv_len)[None, :] | jnp.zeros(
            (q_pos.shape[0], 1), bool)
        m = valid if m is None else (m & valid)
    return jnp.where(m, 0.0, NEG_INF).astype(jnp.float32)


def _fa_fwd_scan(q, k, v, q_offset, spec: AttnSpec, kv_lo, kv_hi, kv_len=None):
    """Online-softmax forward over KV blocks [kv_lo, kv_hi).

    q: (B, Sq, Hkv, G, hd); k/v: (B, Skv, Hkv, hd).  Returns (o, lse) with
    o (B, Sq, Hkv, G, hd) f32 and lse (B, Sq, Hkv, G) f32.
    """
    B, Sq, Hkv, G, hd = q.shape
    blk = min(spec.kv_block, k.shape[1])
    assert k.shape[1] % blk == 0, (k.shape, blk)
    scale = 1.0 / jnp.sqrt(hd).astype(jnp.float32)
    qf = q.astype(jnp.float32) * scale
    n_blocks = kv_hi - kv_lo

    kb = k.reshape(B, k.shape[1] // blk, blk, Hkv, hd)
    vb = v.reshape(B, v.shape[1] // blk, blk, Hkv, hd)
    q_pos = q_offset + jnp.arange(Sq)

    def step(carry, j):
        o, m, l = carry
        kj = jax.lax.dynamic_index_in_dim(kb, j, axis=1, keepdims=False)
        vj = jax.lax.dynamic_index_in_dim(vb, j, axis=1, keepdims=False)
        s = jnp.einsum("bqhgd,bkhd->bqhgk", qf, kj.astype(jnp.float32))
        kv_pos = j * blk + jnp.arange(blk)
        mask = _block_mask(q_pos, kv_pos, spec, kv_len)
        if mask is not None:
            s = s + mask[None, :, None, None, :]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bqhgk,bkhd->bqhgd", p, vj.astype(jnp.float32))
        o = o * corr[..., None] + pv
        return (o, m_new, l), None

    o0 = jnp.zeros((B, Sq, Hkv, G, hd), jnp.float32)
    m0 = jnp.full((B, Sq, Hkv, G), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Sq, Hkv, G), jnp.float32)
    (o, m, l), _ = jax.lax.scan(step, (o0, m0, l0),
                                kv_lo + jnp.arange(n_blocks))
    l = jnp.maximum(l, 1e-30)
    return o / l[..., None], m + jnp.log(l)


def _fa_bwd_scan(q, k, v, o, lse, do, q_offset, spec: AttnSpec, kv_lo, kv_hi,
                 kv_len=None):
    """FlashAttention-2 backward: recompute scores blockwise."""
    B, Sq, Hkv, G, hd = q.shape
    blk = min(spec.kv_block, k.shape[1])
    scale = 1.0 / jnp.sqrt(hd).astype(jnp.float32)
    qf = q.astype(jnp.float32)
    dof = do.astype(jnp.float32)
    D = jnp.sum(dof * o, axis=-1)  # (B,Sq,Hkv,G)
    kb = k.reshape(B, k.shape[1] // blk, blk, Hkv, hd)
    vb = v.reshape(B, v.shape[1] // blk, blk, Hkv, hd)
    q_pos = q_offset + jnp.arange(Sq)

    def step(dq, j):
        kj = jax.lax.dynamic_index_in_dim(kb, j, axis=1, keepdims=False).astype(jnp.float32)
        vj = jax.lax.dynamic_index_in_dim(vb, j, axis=1, keepdims=False).astype(jnp.float32)
        s = jnp.einsum("bqhgd,bkhd->bqhgk", qf * scale, kj)
        kv_pos = j * blk + jnp.arange(blk)
        mask = _block_mask(q_pos, kv_pos, spec, kv_len)
        if mask is not None:
            s = s + mask[None, :, None, None, :]
        p = jnp.exp(s - lse[..., None])                  # (B,Sq,Hkv,G,blk)
        dv_j = jnp.einsum("bqhgk,bqhgd->bkhd", p, dof)
        dp = jnp.einsum("bqhgd,bkhd->bqhgk", dof, vj)
        ds = p * (dp - D[..., None]) * scale
        dq = dq + jnp.einsum("bqhgk,bkhd->bqhgd", ds, kj)
        dk_j = jnp.einsum("bqhgk,bqhgd->bkhd", ds, qf)
        return dq, (dk_j, dv_j)

    dq0 = jnp.zeros((B, Sq, Hkv, G, hd), jnp.float32)
    dq, (dk_b, dv_b) = jax.lax.scan(step, dq0, kv_lo + jnp.arange(kv_hi - kv_lo))
    nb_total = k.shape[1] // blk
    dk = jnp.zeros((B, nb_total, blk, Hkv, hd), jnp.float32)
    dv = jnp.zeros_like(dk)
    idx = kv_lo + jnp.arange(kv_hi - kv_lo)
    dk = dk.at[:, idx].set(jnp.moveaxis(dk_b, 0, 1))
    dv = dv.at[:, idx].set(jnp.moveaxis(dv_b, 0, 1))
    return dq, dk.reshape(k.shape), dv.reshape(v.shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash_attn(q, k, v, q_offset: int, spec: AttnSpec, kv_len):
    o, _ = _fa_fwd_scan(q, k, v, q_offset, spec, 0,
                        k.shape[1] // min(spec.kv_block, k.shape[1]), kv_len)
    return o.astype(q.dtype)


def _flash_attn_fwd(q, k, v, q_offset, spec, kv_len):
    nb = k.shape[1] // min(spec.kv_block, k.shape[1])
    o, lse = _fa_fwd_scan(q, k, v, q_offset, spec, 0, nb, kv_len)
    o = o.astype(q.dtype)
    return o, (q, k, v, o, lse)  # residual o in compute dtype (FA-2 style)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _fa_bwd_fused(q, k, v, o, lse, do, q_offset, spec, kv_len):
    """FA-2 backward as a 'fused kernel' boundary: on TPU this is one Pallas
    kernel whose internals never touch HBM; the custom_vjp wrapper makes
    core/jaxpr_cost account it that way (call-boundary I/O only)."""
    nb = k.shape[1] // min(spec.kv_block, k.shape[1])
    with jax.named_scope(SCOPE):
        dq, dk, dv = _fa_bwd_scan(q, k, v, o, lse, do, q_offset, spec, 0, nb,
                                  kv_len)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


def _fa_bwd_fused_fwd(q, k, v, o, lse, do, q_offset, spec, kv_len):
    return _fa_bwd_fused(q, k, v, o, lse, do, q_offset, spec, kv_len), None


def _fa_bwd_fused_bwd(q_offset, spec, kv_len, res, g):
    raise NotImplementedError("second-order attention gradients unsupported")


_fa_bwd_fused.defvjp(_fa_bwd_fused_fwd, _fa_bwd_fused_bwd)


def _flash_attn_bwd(q_offset, spec, kv_len, res, do):
    q, k, v, o, lse = res
    return _fa_bwd_fused(q, k, v, o, lse, do, q_offset, spec, kv_len)


_flash_attn.defvjp(_flash_attn_fwd, _flash_attn_bwd)


def _flash_attn_causal_skip(q, k, v, q_offset, spec: AttnSpec):
    """Causal variant that never touches strictly-upper KV blocks.

    Unrolls over query blocks (few: Sq/kv_block); each query block runs the
    online-softmax scan over KV blocks [lo, hi) only, where ``hi`` is its
    diagonal and ``lo`` is set by the sliding window.  ~2x fewer attention
    FLOPs; identical output (validated in tests).
    """
    B, Sq, Hkv, G, hd = q.shape
    blk = min(spec.kv_block, k.shape[1])
    n_qb = Sq // blk
    outs = []
    for qi in range(n_qb):
        qs = q[:, qi * blk:(qi + 1) * blk]
        hi = qi + 1
        lo = 0
        if spec.window is not None:
            lo = max(0, (qi * blk - spec.window) // blk)
        o, _ = _fa_fwd_scan(qs, k, v, q_offset + qi * blk, spec, lo, hi)
        outs.append(o.astype(q.dtype))
    return jnp.concatenate(outs, axis=1)


def flash_attention(q, k, v, *, spec: AttnSpec, q_offset: int = 0):
    """q (B,Sq,Hq,hd), k/v (B,Skv,Hkv,hd) -> (B,Sq,Hq,hd).

    Ragged KV lengths (not a multiple of the block) are zero-padded and
    masked out via the additive block mask."""
    B, Sq, Hq, hd = q.shape
    Skv = k.shape[2 - 1]
    Hkv = k.shape[2]
    G = Hq // Hkv
    blk = min(spec.kv_block, Skv)
    pad = (-Skv) % blk
    kv_len = None
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kv_len = Skv
    qg = q.reshape(B, Sq, Hkv, G, hd)
    with jax.named_scope(SCOPE):
        if spec.causal_block_skip and spec.causal and Sq % blk == 0 and not pad:
            o = _flash_attn_causal_skip(qg, k, v, q_offset, spec)
        else:
            o = _flash_attn(qg, k, v, q_offset, spec, kv_len)
    return o.reshape(B, Sq, Hq, hd)


# ---------------------------------------------------------------------------
# Decode attention (one query token against a cache)
# ---------------------------------------------------------------------------

def decode_attention(q, k_cache, v_cache, slot_positions, pos, window=None):
    """q (B,1,Hq,hd); caches (B,W,Hkv,hd); slot_positions (W,) int32 giving
    each slot's absolute position (-1 = empty).  Returns (B,1,Hq,hd).

    Scores accumulate in f32 via preferred_element_type; the cache is NEVER
    cast to f32 (XLA hoists such casts out of the decode layer scan,
    materializing an f32 copy of the whole stacked cache — §Perf B).
    """
    B, _, Hq, hd = q.shape
    Hkv = k_cache.shape[2]
    G = Hq // Hkv
    with jax.named_scope(SCOPE):
        qg = q.reshape(B, Hkv, G, hd).astype(k_cache.dtype)
        scale = 1.0 / jnp.sqrt(hd).astype(jnp.float32)
        s = jnp.einsum("bhgd,bkhd->bhgk", qg, k_cache,
                       preferred_element_type=jnp.float32) * scale
        valid = (slot_positions >= 0) & (slot_positions <= pos)
        if window is not None:
            valid &= slot_positions > pos - window
        s = jnp.where(valid[None, None, None, :], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhgk,bkhd->bhgd", p.astype(v_cache.dtype), v_cache,
                       preferred_element_type=jnp.float32)
    return o.reshape(B, 1, Hq, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# Full attention module (params + apply)
# ---------------------------------------------------------------------------

def init_attn(key, cfg, *, cross=False):
    d, hq, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": L.init_linear(ks[0], d, hq * hd, bias=cfg.qkv_bias and not cross),
        "wk": L.init_linear(ks[1], d, hkv * hd, bias=cfg.qkv_bias and not cross),
        "wv": L.init_linear(ks[2], d, hkv * hd, bias=cfg.qkv_bias and not cross),
        "wo": L.init_linear(ks[3], hq * hd, d),
    }


def _project_qkv(p, x, ctx, cfg, compute_dtype):
    B = x.shape[0]
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    src = x if ctx is None else ctx
    q = L.linear(p["wq"], x, compute_dtype).reshape(B, -1, hq, hd)
    k = L.linear(p["wk"], src, compute_dtype).reshape(B, -1, hkv, hd)
    v = L.linear(p["wv"], src, compute_dtype).reshape(B, -1, hkv, hd)
    q = sh.constrain(q, "dp", None, "tp", None)
    k = sh.constrain(k, "dp", None, "tp", None)
    v = sh.constrain(v, "dp", None, "tp", None)
    return q, k, v


def attn_forward(p, x, cfg, spec: AttnSpec, *, ctx=None, positions=None,
                 compute_dtype=None, rope=True):
    """Training/prefill self- or cross-attention over a full sequence.

    Returns (out, kv) where kv=(k, v) post-RoPE for cache seeding.
    """
    B, S, _ = x.shape
    q, k, v = _project_qkv(p, x, ctx, cfg, compute_dtype)
    if rope and ctx is None:
        if positions is None:
            positions = jnp.arange(S)[None, :]
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    o = flash_attention(q, k, v, spec=spec)
    o = sh.constrain(o, "dp", None, "tp", None)
    out = L.linear(p["wo"], o.reshape(B, S, -1), compute_dtype)
    return out, (k, v)


def attn_decode(p, x, cfg, cache, pos, *, window=None, compute_dtype=None,
                rope=True, cross=False):
    """One-token decode. cache: {"k","v"} (B,W,Hkv,hd). Returns (out, cache)."""
    B = x.shape[0]
    W = cache["k"].shape[1]
    if cross:
        # static cross-attention context: cache holds precomputed k/v
        hq, hd = cfg.n_heads, cfg.head_dim
        q = L.linear(p["wq"], x, compute_dtype).reshape(B, 1, hq, hd)
        q = sh.constrain(q, "dp", None, "tp", None)
        slot_pos = jnp.arange(W)
        o = decode_attention(q, cache["k"], cache["v"], slot_pos, W)
        new_cache = cache
    else:
        q, k, v = _project_qkv(p, x, None, cfg, compute_dtype)
        if rope:
            q = apply_rope(q, jnp.full((B, 1), pos), cfg.rope_theta)
            k = apply_rope(k, jnp.full((B, 1), pos), cfg.rope_theta)
        slot = pos % W if window is not None else pos
        if os.environ.get("REPRO_DECODE_WRITE", "dus") == "where":
            # elementwise token write: stays LOCAL under a seq-sharded cache
            # (GSPMD all-gathers the whole cache for a dynamic-index DUS)
            sel = (jnp.arange(W) == slot)[None, :, None, None]
            kc = jnp.where(sel, k.astype(cache["k"].dtype), cache["k"])
            vc = jnp.where(sel, v.astype(cache["v"].dtype), cache["v"])
        else:
            kc = jax.lax.dynamic_update_slice_in_dim(
                cache["k"], k.astype(cache["k"].dtype), slot, axis=1)
            vc = jax.lax.dynamic_update_slice_in_dim(
                cache["v"], v.astype(cache["v"].dtype), slot, axis=1)
        kc = _constrain_kv_cache(kc)
        vc = _constrain_kv_cache(vc)
        j = jnp.arange(W)
        if window is not None:
            # ring buffer: slot j holds position pos - ((pos - j) mod W)
            slot_pos = pos - jnp.mod(pos - j, W)
        else:
            slot_pos = j
        o = decode_attention(q, kc, vc, slot_pos, pos, window=window)
        new_cache = {"k": kc, "v": vc}
    o = sh.constrain(o, "dp", None, "tp", None)
    out = L.linear(p["wo"], o.reshape(B, 1, -1), compute_dtype)
    return out, new_cache


def _constrain_kv_cache(kc):
    """(B, W, Hkv, hd): heads over 'model' when divisible, else cache seq —
    MUST agree with distributed/specs._cache_leaf_spec or GSPMD regathers
    the whole cache every decode step (§Perf B)."""
    Hkv = kc.shape[2]
    if Hkv % max(sh.tp_size(), 1) == 0:
        return sh.constrain(kc, "dp", None, "tp", None)
    return sh.constrain(kc, "dp", "tp", None, None)


def init_kv_cache(cfg, batch, seq_len, *, window=None, dtype=jnp.bfloat16):
    W = min(window, seq_len) if window is not None else seq_len
    shape = (batch, W, cfg.n_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
