"""Swap-search passes per layer, counted by the program's
``count_search_passes`` hook around each group (traced runs only)."""


def read(run):
    g = run.facts.get("group_passes")
    layers = run.facts.get("layers_pruned")
    return sum(p for _, p in g) / layers if g and layers else None
