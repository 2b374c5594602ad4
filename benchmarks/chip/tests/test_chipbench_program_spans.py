"""The program's own spans in the benchmark: idle gaps named after them,
and the readers of the metrics that rest on them, on hand-made input."""
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import devtrace  # noqa: E402
import harness  # noqa: E402

import repro.runtime  # noqa: E402
from repro.runtime import trace  # noqa: E402

MS = 1_000_000


def test_a_program_span_names_the_gap_it_holds():
    host = [["bench:window", 0, 100 * MS], ["bench:step", 0, 60 * MS]]
    device = {"/device:TPU:0": [["fusion", 10 * MS, 10 * MS],
                                ["fusion", 50 * MS, 30 * MS]]}
    plain = devtrace.reduce_events({"host": host, "device": device})
    named = devtrace.reduce_events({
        "host": host + [["repro:sched.decode.fetch.wait", 20 * MS, 15 * MS]],
        "device": device})
    for k in ("busy_s", "window_s", "ops"):
        assert named[k] == plain[k]
    gaps = dict(named["breakdown"]["idle_gaps"])
    # idle [0,10) and [35,50) inside step; [20,35) inside the wait;
    # [80,100) outside every span
    assert gaps["sched.decode.fetch.wait"] == pytest.approx(0.015)
    assert gaps["step"] == pytest.approx(0.025)
    assert gaps["host"] == pytest.approx(0.020)
    assert dict(plain["breakdown"]["idle_gaps"])["step"] == \
        pytest.approx(0.040)


def _rec(i, name, t0, t1, parent=None, /, **attrs):
    return trace.Record(i, name, t0, t1, parent, attrs)


SERVE = [
    _rec(0, "sched.step", 95.0, 95.1),                      # before window
    _rec(1, "engine.prefill.wait", 100.01, 100.03, 2),
    _rec(2, "sched.prefill", 100.0, 100.05, 5),
    _rec(3, "sched.prefill.first.wait", 100.03, 100.04, 2),
    _rec(4, "engine.decode.wait", 100.08, 100.16, 6),
    _rec(6, "sched.decode", 100.06, 100.19, 5),
    _rec(7, "sched.decode.fetch.wait", 100.16, 100.17, 6),
    _rec(5, "sched.step", 100.0, 100.2, None, n_active=6, bucket=8,
         n_steps=4, wasted=2, n_queued=3),
    _rec(8, "engine.decode.wait", 100.31, 100.39, 9),
    _rec(10, "sched.decode.fetch.wait", 100.39, 100.40, 9),
    _rec(9, "sched.decode", 100.3, 100.41, 11),
    _rec(11, "sched.step", 100.3, 100.42, None, n_active=8, bucket=8,
         n_steps=4, wasted=0, n_queued=0),
]

PRUNE = [
    _rec(0, "prune.group", 101.0, 103.0, name="a", instances=2, rows=50,
         d_in=64, k=8, passes=1, rows_scored=100, swaps=200),
    _rec(1, "prune.group", 103.0, 104.0, name="b", instances=1, rows=25,
         d_in=64, k=8, passes=2, rows_scored=50, swaps=100),
    _rec(2, "prune.group", 104.0, 104.5, name="c", instances=1, rows=25,
         d_in=64),                                          # restored
]


def _run(records, monkeypatch):
    monkeypatch.setattr(trace, "records", lambda: list(records))
    run = harness.Run(cell={}, config={}, mix={}, seed=0, seconds=10.0,
                      trace=True, peaks={}, t_start=0.0)
    run.window = (100.0, 110.0)
    run.facts["layers_pruned"] = 2
    return run


@pytest.mark.parametrize("name,records,want", [
    ("step_host_ms.serve", SERVE, 55.0),       # median of 80 and 30 ms
    ("prefill_lane_pct.serve", SERVE, 0.5),    # 50 ms of 10 s
    ("decode_ms.serve", SERVE, 120.0),         # median of 130 and 110 ms
    ("host_syncs_per_step.serve", SERVE, 3.0),
    ("decode_useful_pct.serve", SERVE, 100.0 * 54 / 64),
    ("refine_group_s_per_layer", PRUNE, 1.75),
    ("swap_accept_pct.prune", PRUNE, 25.0),    # 300 of 8 * 150
])
def test_reader(name, records, want, monkeypatch):
    run = _run(records, monkeypatch)
    assert harness.metric_reader(name).read(run) == pytest.approx(want)
    # no spans in the window, or a program without the tracing module:
    # the metric is left out
    assert harness.metric_reader(name).read(_run([], monkeypatch)) is None
    run = _run(records, monkeypatch)
    monkeypatch.delattr(repro.runtime, "trace")
    monkeypatch.setitem(sys.modules, "repro.runtime.trace", None)
    assert harness.metric_reader(name).read(run) is None
