"""Share of its roofline the fused swap search reaches. Per search pass
of a site instance with R rows of width d: ≈3·R·d² operations and the
float32 Gram streamed once per block of 8 rows (R/8·d²·4 bytes), which
bounds it (memory). Passes are counted per group; the kernel's time is
its device time in the trace."""
import devtrace
import flops

KERNEL = "swap_topk_padded"


def read(run):
    f = run.facts
    if not run.trace or "group_passes" not in f:
        return None
    t = devtrace.kernel_seconds(run.trace, KERNEL)
    if t <= 0:
        return None
    shapes = {name: (n, r, d) for name, n, r, d in f["topk_calls"]}
    least = 0.0
    for g, p in f["group_passes"]:
        n, r, d = shapes[g]
        least += p * n * flops.roofline_time(
            flops.swap_search_flops(r, d), flops.swap_search_bytes(r, d),
            run.peaks)
    return 100.0 * least / t
