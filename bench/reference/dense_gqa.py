"""Plain reference of a dense decoder with grouped-query attention (Llama,
Qwen2, Yi), in straightforward jax.numpy and float32.

It follows the published description: RMSNorm before attention and MLP,
rotary embeddings on the two halves of each head (``rotate_half``), causal
softmax attention with key/value heads shared by groups of query heads,
optional biases on the query/key/value projections, a SiLU-gated MLP, and an
output head that is the embedding (tied) or its own matrix.  Training is
mean token cross-entropy and AdamW with clipping by the global norm, a
linear warm-up and a cosine decay, and weight decay on every weight stored
with at least the optimizer section's ``decay_min_ndim`` dimensions (at 2,
as the mixes state it, the layers' weights are stored stacked, so their norm
gains and biases are among them; the final norm's gain is not).

It imports nothing of the program.  It reads the weights in the layout the
benchmark made them in: ``embed/w`` and ``unembed/w`` of shape (padded
vocabulary, d); ``blocks/sub0/...`` stacked over layers; matrices stored
(fan_in, fan_out).  Rows of the vocabulary past ``vocab_size`` are padding
and never read.

``prec`` is ``"f32"`` (every product at float32's full precision) or
``"fp8"``: the control, where every matrix product takes its operands
rounded to float8 e4m3 under a per-tensor scale, and its gradients rounded
to e5m2, with float32 sums.  It runs in blocks of query rows and layer by
layer, so that it fits beside nothing else on one chip.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


# ----- matrix products at the reference's precision -----

def _round(x, fp8_dtype):
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / float(jnp.finfo(fp8_dtype).max), 1.0)
    return (x / scale).astype(fp8_dtype).astype(jnp.float32) * scale


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _ein_fp8(spec, a, b):
    return jnp.einsum(spec, _round(a, jnp.float8_e4m3fn),
                      _round(b, jnp.float8_e4m3fn), precision=HIGHEST)


def _ein_fp8_fwd(spec, a, b):
    return _ein_fp8(spec, a, b), (a, b)


def _ein_fp8_bwd(spec, res, g):
    a, b = res
    _, vjp = jax.vjp(lambda x, y: jnp.einsum(spec, x, y, precision=HIGHEST),
                     _round(a, jnp.float8_e4m3fn), _round(b, jnp.float8_e4m3fn))
    return vjp(_round(g, jnp.float8_e5m2))


_ein_fp8.defvjp(_ein_fp8_fwd, _ein_fp8_bwd)


def ein(spec, a, b, prec):
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if prec == "fp8":
        return _ein_fp8(spec, a, b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


# ----- the model -----

def _dims(arch):
    d = arch["hidden_size"]
    hq = arch["num_attention_heads"]
    hkv = arch["num_key_value_heads"]
    hd = arch.get("head_dim") or d // hq
    return d, hq, hkv, hd


def rms_norm(x, scale, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def rope(x, positions, theta):
    """x (B, S, H, hd): rotate the two halves of each head."""
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _query_block(batch, heads, seq):
    """Query rows per attention block: score blocks of at most 2**27
    float32 elements."""
    blk = seq
    while blk > 64 and batch * heads * blk * seq > 1 << 27:
        blk //= 2
    while seq % blk:
        blk //= 2
    return max(blk, 1)


def attention(q, k, v, prec):
    """Causal attention; q (B, S, Hq, hd), k/v (B, S, Hkv, hd)."""
    B, S, hq, hd = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    q = q.reshape(B, S, hkv, g, hd)
    blk = _query_block(B, hq, S)
    kpos = jnp.arange(S)

    def one(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * blk, blk, axis=1)
        s = ein("bqhgd,bkhd->bhgqk", qi, k, prec) / math.sqrt(hd)
        qpos = i * blk + jnp.arange(blk)
        s = jnp.where(qpos[:, None] >= kpos[None, :], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return ein("bhgqk,bkhd->bqhgd", p, v, prec)

    out = jax.lax.map(one, jnp.arange(S // blk))       # (n, B, blk, hkv, g, hd)
    return jnp.moveaxis(out, 0, 1).reshape(B, S, hq * hd)


def layer(x, p, arch, prec, positions):
    d, hq, hkv, hd = _dims(arch)
    eps = arch["rms_norm_eps"]
    B, S, _ = x.shape
    h = rms_norm(x, p["ln1"]["scale"], eps)

    def proj(name, heads):
        y = ein("bsd,de->bse", h, p["attn"][name]["w"], prec)
        if "b" in p["attn"][name]:
            y = y + p["attn"][name]["b"].astype(jnp.float32)
        return y.reshape(B, S, heads, hd)

    q = rope(proj("wq", hq), positions, arch["rope_theta"])
    k = rope(proj("wk", hkv), positions, arch["rope_theta"])
    v = proj("wv", hkv)
    x = x + ein("bse,ed->bsd", attention(q, k, v, prec), p["attn"]["wo"]["w"],
                prec)
    h = rms_norm(x, p["ln2"]["scale"], eps)
    mlp = p["mlp"]
    gate = ein("bsd,df->bsf", h, mlp["w_gate"]["w"], prec)
    up = ein("bsd,df->bsf", h, mlp["w_in"]["w"], prec)
    return x + ein("bsf,fd->bsd", jax.nn.silu(gate) * up, mlp["w_out"]["w"],
                   prec)


def hidden(params, tokens, arch, prec="f32", remat=False):
    """Final-normed hidden states (B, S, d), float32."""
    vocab = arch["vocab_size"]
    x = params["embed"]["w"][:vocab].astype(jnp.float32)[tokens]
    positions = jnp.arange(tokens.shape[1])

    def body(x, p):
        return layer(x, p, arch, prec, positions), None

    if remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, params["blocks"]["sub0"])
    return rms_norm(x, params["final_norm"]["scale"], arch["rms_norm_eps"])


def head(params, arch):
    """Output head (vocab, d): the embedding when tied."""
    w = params["unembed"] if "unembed" in params else params["embed"]
    return w["w"][: arch["vocab_size"]]


def _row_blocks(n, vocab):
    """Rows of logits per block: at most 2**27 float32 elements."""
    blk = n
    while blk > 1 and blk * vocab > 1 << 27:
        blk //= 2
    while n % blk:
        blk //= 2
    return max(blk, 1)


def logit_gaps(params, tokens, chosen, start, arch, prec="f32"):
    """At each position ``start + i`` of each row, how far the reference's
    logit of ``chosen[:, i]`` (the token that follows that position) lies
    below the reference's best logit there, and the token that ``prec``
    ranks first.  Returns (gaps (B, n), firsts (B, n))."""
    h = hidden(params, tokens, arch, prec)
    B, n = chosen.shape
    h = h[:, start:start + n].reshape(B * n, -1)
    w = head(params, arch)
    blk = _row_blocks(B * n, w.shape[0])

    def one(args):
        hb, cb = args
        logits = ein("td,vd->tv", hb, w, prec)
        best = jnp.max(logits, -1)
        got = jnp.take_along_axis(logits, cb[:, None], -1)[:, 0]
        return best - got, jnp.argmax(logits, -1).astype(jnp.int32)

    gaps, firsts = jax.lax.map(one, (h.reshape(-1, blk, h.shape[-1]),
                                      chosen.reshape(-1, blk)))
    return gaps.reshape(B, n), firsts.reshape(B, n)


def gaps_at(params, tokens, chosen, start, arch, chooser_prec=None):
    """Gaps of ``chosen`` in the float32 reference; with ``chooser_prec``,
    the gaps of the tokens that reference precision ranks first instead
    (the control)."""
    if chooser_prec is not None:
        _, chosen = logit_gaps(params, tokens, chosen, start, arch,
                               chooser_prec)
    gaps, _ = logit_gaps(params, tokens, chosen, start, arch, "f32")
    return gaps


# ----- training -----

def loss(params, tokens, labels, arch, prec="f32"):
    """Mean next-token cross-entropy over the vocabulary."""
    h = hidden(params, tokens, arch, prec, remat=True)
    B, S, d = h.shape
    w = head(params, arch)
    blk = _row_blocks(B * S, w.shape[0])

    @jax.checkpoint
    def one(hb_lb):
        hb, lb = hb_lb
        logits = ein("td,vd->tv", hb, w, prec)
        lse = jax.nn.logsumexp(logits, -1)
        return jnp.sum(lse - jnp.take_along_axis(logits, lb[:, None], -1)[:, 0])

    sums = jax.lax.map(one, (h.reshape(-1, blk, d), labels.reshape(-1, blk)))
    return jnp.sum(sums) / (B * S)


def lr_at(step, o):
    step = jnp.asarray(step, jnp.float32)
    warm = jnp.minimum(step / max(o["warmup_steps"], 1), 1.0)
    t = jnp.clip((step - o["warmup_steps"])
                 / max(o["total_steps"] - o["warmup_steps"], 1), 0.0, 1.0)
    cos = 0.5 * (1.0 + jnp.cos(jnp.pi * t))
    return o["lr"] * warm * (o["min_lr_ratio"] + (1 - o["min_lr_ratio"]) * cos)


def adamw(params, grads, m, v, step, o):
    """One AdamW step (``step``, a float, counts from 1).  Returns (params, m, v,
    clipped grads)."""
    leaves = jax.tree.leaves(grads)
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in leaves))
    scale = jnp.minimum(1.0, o["grad_clip"] / jnp.maximum(gnorm, 1e-9))
    grads = jax.tree.map(lambda g: g * scale, grads)
    lr = lr_at(step, o)
    b1, b2 = o["b1"], o["b2"]
    flat_p, tdef = jax.tree.flatten(params)
    out_p, out_m, out_v = [], [], []
    for p, g, mi, vi in zip(flat_p, tdef.flatten_up_to(grads),
                            tdef.flatten_up_to(m), tdef.flatten_up_to(v)):
        mi = b1 * mi + (1 - b1) * g
        vi = b2 * vi + (1 - b2) * g * g
        delta = (mi / (1 - b1 ** step)) / (jnp.sqrt(vi / (1 - b2 ** step))
                                          + o["eps"])
        if p.ndim >= o["decay_min_ndim"]:
            delta = delta + o["weight_decay"] * p
        out_p.append(p - lr * delta)
        out_m.append(mi)
        out_v.append(vi)
    un = lambda xs: jax.tree.unflatten(tdef, xs)
    return un(out_p), un(out_m), un(out_v), grads


def leaf_norms(tree):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])


def train(params, batches, arch, o, prec="f32"):
    """``len(batches)`` AdamW steps from ``params`` (the arrays are consumed).
    Returns the loss of each step, the leaf norms of the first (clipped)
    gradient, and the final parameters."""
    params = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    grad_fn = jax.jit(jax.value_and_grad(
        lambda p, t, lb: loss(p, t, lb, arch, prec)))
    step_fn = jax.jit(lambda p, g, m, v, i: adamw(p, g, m, v, i, o),
                      donate_argnums=(0, 1, 2, 3))
    losses, first = [], None
    for i, (tokens, labels) in enumerate(batches, start=1):
        value, grads = grad_fn(params, tokens, labels)
        params, m, v, clipped = step_fn(params, grads, m, v, jnp.float32(i))
        losses.append(value)
        if first is None:
            first = jax.jit(leaf_norms)(clipped)
        del grads, clipped
    return jnp.stack(losses), first, params
