"""Mean over site instances of 100·(1 − refined / warmstart) layer-wise
loss, both recomputed by the float32 reference from the last pass's
masks and Grams after the window."""


def read(run):
    return run.facts.get("loss_reduction_pct")
