"""95th percentile of the gaps between successive delivered tokens of a
request, over every gap that ends inside the window (nearest rank).
Tokens of one decode chunk arrive together, so this reads the gap from
chunk to chunk, prefills that cut in included."""
import harness


def read(run):
    gaps = run.facts.get("itl")
    return 1e3 * harness.percentile(gaps, 95) if gaps else None
