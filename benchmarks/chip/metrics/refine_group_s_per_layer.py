"""Refinement seconds per layer from the program's own ``prune.group``
spans (restore or refine, mask check, checkpoint; the executor's
callbacks fall outside)."""
import program_spans


def read(run):
    layers = run.facts.get("layers_pruned")
    t = sum(s.t1 - s.t0 for s in program_spans.named(
        program_spans.in_window(run), "prune.group"))
    return t / layers if layers and t else None
