"""Share of the decode chunks' row-steps that deliver a token: over the
window's ``sched.step`` spans, Σ(n_active·n_steps − wasted) against
Σ(bucket·n_steps), the rows the chunk programs ran."""
import program_spans


def read(run):
    ran = useful = 0
    for s in program_spans.named(program_spans.in_window(run),
                                 "sched.step"):
        a = s.attrs
        if "bucket" in a:
            ran += a["bucket"] * a["n_steps"]
            useful += a["n_active"] * a["n_steps"] - a["wasted"]
    return 100.0 * useful / ran if ran else None
