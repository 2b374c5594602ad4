"""Served work over the window against the chip's bf16 peak: for every
token delivered in the window, 2·(kept weights) plus attention over its
keys plus one row of logits; a prefill counts its whole prompt. Kept
weights only, so a packed format cannot read above 100%."""
import flops


def read(run):
    f = run.facts
    if "tokens" not in f or not f["tokens"]:
        return None
    m = run.config["model"]
    L, kept = m["n_layers"], f["kept_params"]
    total = 0
    for P in f["prefill_prompts"]:
        keys = P * (P + 1) // 2
        total += 2 * kept * P + L * flops.attention_flops(m, 1) * keys
        total += flops.head_flops(m)
    for pos in f["decode_positions"]:
        total += 2 * kept + L * flops.attention_flops(m, pos + 1)
        total += flops.head_flops(m)
    return 100.0 * total / (run.window_s * run.peaks["bf16_flops"])
