"""The peaks table and the training operation count."""
import pytest

from bench import flops

SMOLLM_360M = dict(hidden_size=960, num_hidden_layers=32,
                   num_attention_heads=15, num_key_value_heads=5,
                   head_dim=64, intermediate_size=2560, vocab_size=49152)


def test_v5e_peaks():
    p = flops.peak("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in p["source"]


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v5", ""])
def test_unknown_device_kind_raises(kind):
    with pytest.raises(KeyError, match="no peaks"):
        flops.peak(kind)


def test_smollm_360m_by_hand():
    # per layer: q 960x960, k and v 960x320 each, o 960x960, gate, up
    # and down 960x2560 each = 9,830,400; 32 layers; head 960 x 49,152
    layer = 960 * 960 * 2 + 960 * 320 * 2 + 960 * 2560 * 3
    assert layer == 9_830_400
    n = 32 * layer + 960 * 49_152
    assert flops.matmul_params(SMOLLM_360M) == n == 361_758_720
    # the model's 361,821,120 parameters less 65 RMSNorm scales of 960
    assert n + 65 * 960 == 361_821_120
    attn = 12 * 32 * 1024 * 960
    assert flops.train_flops_per_token(SMOLLM_360M, 1024) == 6 * n + attn
    assert flops.train_flops_per_token(SMOLLM_360M, 1024) == 2_548_039_680
