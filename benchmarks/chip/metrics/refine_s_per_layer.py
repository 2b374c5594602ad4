"""Refinement seconds per layer: spans from each group's start to its
done callback, blocked on the group's losses."""


def read(run):
    layers = run.facts.get("layers_pruned")
    t = run.span_total("refine")
    return t / layers if layers and t else None
