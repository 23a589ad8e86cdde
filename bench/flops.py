"""Operations that a step of a dense GQA decoder needs, computed
from its shapes.

What is counted is what the algorithm needs, not what the program runs:
recomputation under rematerialisation is not counted, and causal attention
counts only the query-key pairs at or below the diagonal.  Every count is
of multiply-adds as 2 operations.  ``arch`` is the ``architecture`` section
of a configuration file (``bench/configs/*.json``).
"""
from __future__ import annotations


def _dims(arch: dict):
    d = arch["hidden_size"]
    hq = arch["num_attention_heads"]
    hkv = arch["num_key_value_heads"]
    hd = arch.get("head_dim") or d // hq
    return d, hq, hkv, hd, arch["intermediate_size"], arch["vocab_size"]


def layer_linear_flops_per_token(arch: dict) -> float:
    """Projections and the gated MLP of one layer, per token."""
    d, hq, hkv, hd, ff, _ = _dims(arch)
    qkv = 2 * d * (hq + 2 * hkv) * hd
    out = 2 * hq * hd * d
    mlp = 2 * 3 * d * ff
    return float(qkv + out + mlp)


def causal_pairs(seq: int) -> int:
    """Query-key pairs a causal mask keeps in one sequence of ``seq``."""
    return seq * (seq + 1) // 2


def forward_flops(arch: dict, batch: int, seq: int, *,
                  logits: bool = True) -> float:
    """One forward over ``batch`` sequences of ``seq`` tokens, with logits at
    every position when ``logits``."""
    d, hq, _, hd, _, vocab = _dims(arch)
    n_layers = arch["num_hidden_layers"]
    tokens = batch * seq
    linear = n_layers * tokens * layer_linear_flops_per_token(arch)
    attn = n_layers * batch * 4 * hq * hd * causal_pairs(seq)
    head = 2 * tokens * d * vocab if logits else 0
    return float(linear + attn + head)


def train_flops(arch: dict, batch: int, seq: int) -> float:
    """One training step: forward plus a backward of twice its work."""
    return 3.0 * forward_flops(arch, batch, seq)
