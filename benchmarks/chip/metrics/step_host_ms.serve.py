"""Median milliseconds per scheduler step that the host spends outside
device→host syncs: each ``sched.step`` span less the ``*.wait`` spans
inside it (program spans, traced runs)."""
import statistics

import program_spans


def read(run):
    recs = program_spans.in_window(run)
    waits = program_spans.waits(recs)
    host = []
    for s in program_spans.named(recs, "sched.step"):
        inside = sum(w.t1 - w.t0 for w in waits
                     if s.t0 <= w.t0 and w.t1 <= s.t1)
        host.append(s.t1 - s.t0 - inside)
    return 1e3 * statistics.median(host) if host else None
