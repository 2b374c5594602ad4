"""Operations and bytes the work requires, from shapes alone.

These are the yardstick's counts, kept beside the benchmark so that a
change to the program cannot change how its work is counted. Every
count is what the algorithm needs, not what a kernel happens to do, so
a roofline share built on them never exceeds 100%.

Closed forms taken from the program's analytic tables
(``benchmarks/roofline.py``): one swap-search pass over a block of RB
rows streams the float32 Gram once (d²·4 bytes) and spends ≈3·RB·d²
operations scoring candidates.
"""
from __future__ import annotations


# -- dense decoder ------------------------------------------------------

def layer_weight_params(m: dict) -> int:
    """Weights of one decoder layer's linears (biases and norms aside)."""
    d, H, kvH, f = m["d_model"], m["n_heads"], m["n_kv_heads"], m["d_ff"]
    dh = d // H
    attn = d * H * dh + 2 * d * kvH * dh + H * dh * d
    mlp = (3 if m["mlp"] == "gated" else 2) * d * f
    return attn + mlp


def attention_flops(m: dict, n_keys: int) -> int:
    """Scores and weighted sum of one query against ``n_keys`` keys, all
    heads: 2 matmuls of 2·dh each per key and head."""
    d, H = m["d_model"], m["n_heads"]
    return 4 * H * (d // H) * n_keys


def decoder_forward_flops(m: dict, seq_len: int) -> int:
    """Forward of every layer over one causal sequence (no head)."""
    per_tok = 2 * layer_weight_params(m)
    keys = seq_len * (seq_len + 1) // 2        # causal: token i sees i+1
    return m["n_layers"] * (seq_len * per_tok + attention_flops(m, 1) * keys)


def head_flops(m: dict) -> int:
    """Logits of one position."""
    return 2 * m["d_model"] * m["vocab_size"]


# -- Gram kernel ------------------------------------------------------

def gram_flops(tokens: int, d: int) -> int:
    return 2 * tokens * d * d


def gram_bytes(tokens: int, d: int, x_bytes: int = 4) -> int:
    """Read the (tokens, d) activations once, write the float32 Gram."""
    return tokens * d * x_bytes + d * d * 4


# -- swap search ------------------------------------------------------

SEARCH_ROW_BLOCK = 8      # rows per block of the fused top-k search


def swap_search_flops(rows: int, d: int) -> int:
    """One search pass over ``rows`` rows: ≈3·d² per row."""
    return 3 * rows * d * d


def swap_search_bytes(rows: int, d: int,
                      row_block: int = SEARCH_ROW_BLOCK) -> int:
    """One search pass: the float32 Gram streamed once per row block."""
    blocks = -(-rows // row_block)
    return blocks * d * d * 4


# -- packed matmul ----------------------------------------------------

def roofline_time(flops: float, nbytes: float, peaks: dict) -> float:
    """The least time the chip could take: max of compute and memory."""
    return max(flops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"])

