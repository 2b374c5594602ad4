"""Share of its roofline the Gram kernel reaches: for every kernel call
in the window, the larger of 2·T·d² over the bf16 peak and the bytes it
must move (float32 activations in, the Gram out) over HBM bandwidth,
summed, over the kernel's device time in the trace."""
import devtrace
import flops

KERNEL = "gram_xtx_padded"


def read(run):
    f = run.facts
    if not run.trace or "gram_calls" not in f:
        return None
    t = devtrace.kernel_seconds(run.trace, KERNEL)
    if t <= 0:
        return None
    least = sum(n * flops.roofline_time(flops.gram_flops(T, d),
                                        flops.gram_bytes(T, d), run.peaks)
                for T, d, n in f["gram_calls"]) * f["passes"]
    return 100.0 * least / t
