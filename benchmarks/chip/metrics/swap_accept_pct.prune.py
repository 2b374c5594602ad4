"""Share of the k candidate swaps per row and search pass that the
refinement commits: 100·Σ swaps over Σ k·rows_scored, over the window's
``prune.group`` spans (rows_scored = instances·rows·passes when a group's
rows form one block)."""
import program_spans


def read(run):
    swaps = offered = 0
    for s in program_spans.named(program_spans.in_window(run),
                                 "prune.group"):
        a = s.attrs
        if "k" in a:
            swaps += a["swaps"]
            offered += a["k"] * a["rows_scored"]
    return 100.0 * swaps / offered if offered else None
