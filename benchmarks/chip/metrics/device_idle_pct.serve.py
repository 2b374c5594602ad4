"""Share of the serving window in which no operation ran on the device
(profiler trace; busy is the union of the device's op intervals)."""


def read(run):
    t = run.trace
    if not t or "tokens" not in run.facts:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
