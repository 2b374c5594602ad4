"""Calibration seconds per layer: spans from the executor's plan to its
first group, blocked on the accumulated statistics."""


def read(run):
    layers = run.facts.get("layers_pruned")
    t = run.span_total("calib")
    return t / layers if layers and t else None
