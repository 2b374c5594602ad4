"""Reduce a JAX profiler trace to device busy time, op times and gaps.

The profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``;
``jax.profiler.ProfileData`` reads it. Device planes are named
``/device:TPU:<n>``; their ``XLA Ops`` line holds one event per executed
operation, named by its HLO text (``%swap_topk_padded.4 = (...)
custom-call(...)``): ``op_name`` keeps the instruction's name without
its number, which for a Pallas kernel is the jitted function around it.
A loop is an op that holds the ops of its body, so op times are taken
over leaf ops only; busy time is the union of all of them. The
benchmark's own spans are ``bench:<name>`` annotations on the host
plane, on the same clock; ``bench:window`` bounds the window.

``reduce_events`` does the arithmetic on plain event lists, so it can be
checked on a small recorded trace without a chip.
"""
from __future__ import annotations

import collections
from pathlib import Path

OPS_LINE = "XLA Ops"
WINDOW = "bench:window"
TOP = 10


def load_events(path: Path) -> dict:
    """{"device": {plane: [[op name, start_ns, dur_ns], ...]},
    "host": [[span name, start_ns, dur_ns], ...] of bench spans}."""
    import jax
    pd = jax.profiler.ProfileData.from_file(str(path))
    device, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and "SparseCore" not in \
                plane.name:
            evs = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    evs.append([op_name(e.name), int(e.start_ns),
                                int(e.duration_ns)])
            device[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench:"):
                        host.append([e.name, int(e.start_ns),
                                     int(e.duration_ns)])
    return {"device": device, "host": host}


def op_name(text: str) -> str:
    """``%swap_topk_padded.4 = (...) custom-call(...)`` -> swap_topk_padded"""
    head = text.split(" = ", 1)[0].strip().lstrip("%")
    base, _, num = head.rpartition(".")
    return base if base and num.isdigit() else head


def _leaves(evs):
    """The events that hold no other event (sorted by start)."""
    evs = sorted(evs, key=lambda e: (e[1], -e[2]))
    out = []
    for i, e in enumerate(evs):
        nxt = evs[i + 1] if i + 1 < len(evs) else None
        if nxt is None or nxt[1] >= e[1] + e[2]:
            out.append(e)
    return out


def _union(intervals):
    out = []
    for s, t in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], t)
        else:
            out.append([s, t])
    return out


def reduce_events(ev: dict) -> dict:
    """Busy and window seconds (busy averaged over chips), seconds per op
    name, and the breakdown: the ops that took most time, and idle time
    inside the window by the innermost benchmark span around it (a gap
    that spans several is cut at their edges)."""
    host = ev["host"]
    wins = [(s, s + d) for n, s, d in host if n == WINDOW]
    chips = [evs for evs in ev["device"].values() if evs]
    if wins:
        w0, w1 = wins[0]
    elif chips:
        w0 = min(e[1] for evs in chips for e in evs)
        w1 = max(e[1] + e[2] for evs in chips for e in evs)
    else:
        w0 = w1 = 0
    spans = [(n[len("bench:"):], s, s + d) for n, s, d in host
             if n != WINDOW]
    busy_total = 0.0
    ops = collections.defaultdict(float)
    counts = collections.Counter()
    gaps = collections.defaultdict(float)
    for evs in chips:
        iv = []
        for _, s, d in evs:
            a, b = max(s, w0), min(s + d, w1)
            if b > a:
                iv.append((a, b))
        for name, s, d in _leaves(evs):
            a, b = max(s, w0), min(s + d, w1)
            if b <= a:
                continue
            ops[name] += (b - a) / 1e9
            counts[name] += 1
        busy = _union(iv)
        busy_total += sum(b - a for a, b in busy) / 1e9
        edges = [w0] + [x for ab in busy for x in ab] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            cuts = sorted({a, b} | {x for _, s, e in spans for x in (s, e)
                                    if a < x < b})
            for c0, c1 in zip(cuts, cuts[1:]):
                gaps[_label(spans, (c0 + c1) / 2)] += \
                    (c1 - c0) / 1e9 / len(chips)
    n = max(len(chips), 1)
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:TOP]
    top_gaps = sorted(gaps.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "busy_s": busy_total / n,
        "window_s": max(w1 - w0, 1) / 1e9,
        "ops": [[k, v / n, counts[k]] for k, v in ops.items()],
        "breakdown": {"device_ops": [[k, v / n] for k, v in top_ops],
                      "idle_gaps": [[k, v] for k, v in top_gaps]},
    }


def _label(spans, t) -> str:
    """The innermost benchmark span holding time ``t`` ("host" if none)."""
    best = None
    for name, s, e in spans:
        if s <= t <= e and (best is None or e - s < best[1]):
            best = (name, e - s)
    return best[0] if best else "host"


def reduce_dir(trace_dir: Path) -> dict:
    paths = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no profiler trace under {trace_dir}")
    return reduce_events(load_events(paths[-1]))


def kernel_seconds(trace: dict, name: str) -> float:
    """Device seconds of the leaf ops called ``name`` (see ``op_name``)."""
    return sum(s for op, s, _ in trace["ops"] if op == name)
