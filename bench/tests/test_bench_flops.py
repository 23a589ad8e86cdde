"""bench/flops.py against counts made by hand, and bench/peaks.py."""
import pytest

import rehearse  # noqa: F401 - puts bench/ on the path
import flops
import peaks

# d=8, 2 query heads sharing 1 key/value head of 4, MLP 16, vocabulary 10
ARCH = {"hidden_size": 8, "num_attention_heads": 2, "num_key_value_heads": 1,
        "head_dim": 4, "intermediate_size": 16, "vocab_size": 10,
        "num_hidden_layers": 1, "attention_bias": False}


def test_forward_by_hand():
    # per token: q,k,v 2*8*(2+1+1)*4 = 256, o 2*8*8 = 128, MLP 2*3*8*16 = 768
    assert flops.layer_linear_flops_per_token(ARCH) == 1152
    # 3 tokens: 3*1152 linear; causal pairs 1+2+3 = 6, 4*2*4*6 = 192 in
    # attention; logits 2*3*8*10 = 480
    assert flops.forward_flops(ARCH, 1, 3) == 3 * 1152 + 192 + 480
    assert flops.forward_flops(ARCH, 1, 3, logits=False) == 3 * 1152 + 192
    assert flops.train_flops(ARCH, 2, 3) == 3 * 2 * (3 * 1152 + 192 + 480)


def test_causal_pairs_is_the_lower_triangle():
    import numpy as np
    for s in (1, 2, 7, 64):
        assert flops.causal_pairs(s) == int(np.tril(np.ones((s, s))).sum())


def test_peaks_of_v5e_and_unknown_kind():
    p = peaks.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks("TPU v9 imaginary")
