"""The trace reduction, on hand-made events and on a recorded TPU trace."""
import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import devtrace  # noqa: E402

RECORDED = HERE / "tests" / "fixtures" / "trace_events.json"


def test_hand_made_events():
    ms = 1_000_000
    ev = {"host": [["bench:window", 0, 100 * ms],
                   ["bench:step", 0, 60 * ms],
                   ["bench:refine", 70 * ms, 30 * ms]],
          "device": {"/device:TPU:0": [
              ["fusion", 10 * ms, 10 * ms],
              ["while", 20 * ms, 20 * ms],           # holds the next two
              ["_spmm_padded", 20 * ms, 15 * ms],
              ["fusion", 35 * ms, 5 * ms],
              ["fusion", 80 * ms, 10 * ms],
              ["fusion", 95 * ms, 10 * ms]]}}   # runs past the window
    red = devtrace.reduce_events(ev)
    assert red["window_s"] == pytest.approx(0.1)
    # busy: [10, 40] and [80, 90] and [95, 100] -> 45 ms
    assert red["busy_s"] == pytest.approx(0.045)
    assert devtrace.kernel_seconds(red, "_spmm_padded") == \
        pytest.approx(0.015)
    assert devtrace.kernel_seconds(red, "fusion") == pytest.approx(0.030)
    assert devtrace.kernel_seconds(red, "while") == 0
    gaps = dict(red["breakdown"]["idle_gaps"])
    # idle [0,10] and [40,60) inside step; [60,70) host; [70,80),[90,95)
    # inside refine
    assert gaps["step"] == pytest.approx(0.030)
    assert gaps["host"] == pytest.approx(0.010)
    assert gaps["refine"] == pytest.approx(0.015)
    ops = red["breakdown"]["device_ops"]
    assert ops[0][0] == "fusion" and len(ops) == 2


def test_op_names():
    assert devtrace.op_name("%swap_topk_padded.4 = (f32[8]) custom-call("
                            "f32[8] %a.1)") == "swap_topk_padded"
    assert devtrace.op_name("%while.55 = (s32[]) while(%t)") == "while"
    assert devtrace.op_name("copy-start") == "copy-start"


def test_busy_is_averaged_over_chips():
    ev = {"host": [["bench:window", 0, 100]],
          "device": {"/device:TPU:0": [["a", 0, 100]],
                     "/device:TPU:1": [["a", 0, 50]]}}
    red = devtrace.reduce_events(ev)
    assert red["busy_s"] == pytest.approx(75e-9)


def test_recorded_tpu_trace():
    """A trace recorded on one v5e chip: the Gram, swap-search and spmm
    kernels of the program inside ``bench:step`` spans."""
    ev = json.loads(RECORDED.read_text())
    red = devtrace.reduce_events(ev)
    assert 0 < red["busy_s"] <= red["window_s"]
    (w0, d), = [(s, d) for n, s, d in ev["host"] if n == "bench:window"]
    for kernel in ("gram_xtx_padded", "swap_topk_padded", "_spmm_padded"):
        want = sum(min(s + dur, w0 + d) - max(s, w0)
                   for evs in ev["device"].values()
                   for name, s, dur in evs
                   if name == kernel and s < w0 + d and s + dur > w0) / 1e9
        assert want > 0
        assert devtrace.kernel_seconds(red, kernel) == pytest.approx(want)
    labels = {k for k, _ in red["breakdown"]["idle_gaps"]}
    assert labels <= {"step", "host"}
