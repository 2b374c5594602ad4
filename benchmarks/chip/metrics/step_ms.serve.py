"""Median milliseconds of the benchmark's spans around each
``ContinuousScheduler.step()`` in the window (the step ends once its
tokens are on the host)."""
import statistics


def read(run):
    d = run.span_durations("step")
    return 1e3 * statistics.median(d) if d else None
