"""Window seconds over transformer layers fully pruned (host clock).

The window holds whole passes; each pass calibrates and refines every
site group of every layer, so the rate covers all of the job's work.
"""


def read(run):
    layers = run.facts.get("layers_pruned")
    return run.window_s / layers if layers else None
