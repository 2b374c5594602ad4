"""Median milliseconds of a decode chunk as the scheduler runs it
(``sched.decode`` spans: upload, dispatch, wait, fetch, bookkeeping and
leaves)."""
import statistics

import program_spans


def read(run):
    d = [s.t1 - s.t0 for s in program_spans.named(
        program_spans.in_window(run), "sched.decode")]
    return 1e3 * statistics.median(d) if d else None
