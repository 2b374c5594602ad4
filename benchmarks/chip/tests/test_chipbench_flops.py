"""The benchmark's operation and byte counts against hand counts."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import flops  # noqa: E402

PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
TINY = {"n_layers": 2, "d_model": 8, "n_heads": 2, "n_kv_heads": 1,
        "d_ff": 16, "vocab_size": 10, "mlp": "plain"}


def test_layer_weights_plain_and_gated():
    # wq 8x8, wk 4x8, wv 4x8, wo 8x8 = 192; plain MLP 2*8*16 = 256
    assert flops.layer_weight_params(TINY) == 192 + 256
    assert flops.layer_weight_params(dict(TINY, mlp="gated")) == 192 + 384


def test_forward_flops_counts_causal_keys():
    # per token 2*448 per layer; attention 4*H*dh = 32 per key;
    # 3 tokens see 1+2+3 = 6 keys
    per_layer = 3 * 2 * 448 + 32 * 6
    assert flops.decoder_forward_flops(TINY, 3) == 2 * per_layer
    assert flops.attention_flops(TINY, 5) == 160
    assert flops.head_flops(TINY) == 160


def test_gram_counts():
    assert flops.gram_flops(4, 3) == 72
    assert flops.gram_bytes(4, 3) == 4 * 3 * 4 + 9 * 4
    assert flops.gram_bytes(4, 3, x_bytes=2) == 4 * 3 * 2 + 36


def test_swap_search_counts():
    # 3*R*d^2 operations; the Gram streamed once per 8-row block
    assert flops.swap_search_flops(16, 4) == 768
    assert flops.swap_search_bytes(16, 4) == 2 * 64
    assert flops.swap_search_bytes(17, 4) == 3 * 64


def test_roofline_time_takes_the_larger_bound():
    assert flops.roofline_time(197e12, 0, PEAKS) == 1.0
    assert flops.roofline_time(0, 819e9, PEAKS) == 1.0
    assert flops.roofline_time(197e12, 2 * 819e9, PEAKS) == 2.0
