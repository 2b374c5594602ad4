"""Device→host syncs per scheduler step: ``*.wait`` spans in the window
over ``sched.step`` spans (program counts, traced runs)."""
import program_spans


def read(run):
    recs = program_spans.in_window(run)
    steps = program_spans.named(recs, "sched.step")
    return len(program_spans.waits(recs)) / len(steps) if steps else None
