"""The pruning job's required operations over the window, against the
chip's bf16 peak: each pass's layer forward over the calibration tokens
and one Gram per distinct site input (the discarded head left out), plus
3·R·d² per site instance per counted search pass."""
import flops


def read(run):
    f = run.facts
    if "passes" not in f or "group_passes" not in f:
        return None
    shapes = {name: (n, r, d) for name, n, r, d in f["topk_calls"]}
    search = sum(p * shapes[g][0] * flops.swap_search_flops(*shapes[g][1:])
                 for g, p in f["group_passes"])
    total = f["passes"] * f["flops_pass"] + search
    return 100.0 * total / (run.window_s * run.peaks["bf16_flops"])
