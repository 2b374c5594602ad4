"""Share of the serving window the scheduler spends in its prefill lane
(``sched.prefill`` spans: admission, prefill and its first token)."""
import program_spans


def read(run):
    spans = program_spans.named(program_spans.in_window(run),
                                "sched.prefill")
    if not spans:
        return None
    return 100.0 * sum(s.t1 - s.t0 for s in spans) / run.window_s
