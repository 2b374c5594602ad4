"""The float8 control, put in the program's place and judged by the
cell's own checks and limits, comes out not correct where the program
comes out correct. On the chip the control runs at each cell's own size
(``control.py``, readings in PERF.md); here, at a CPU size, the test
keeps the control's path and its verdict working."""
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

import harness  # noqa: E402

FIX = HERE / "tests" / "fixtures"


def control_run(config: str, mix: str) -> harness.Run:
    cfg, mx = harness.config_doc(config, FIX), harness.mix_doc(mix, FIX)
    peaks = harness.load_json(HERE / "peaks.json")["TPU v5 lite"]
    run = harness.Run(cell={"name": "tiny", "config": config,
                            "traffic": mix, "chips": 1},
                      config=cfg, mix=mx, seed=2**32 + 5, seconds=0.3,
                      trace=False, peaks=peaks)
    run.control = True
    harness.runner(mx).run(run)
    return run


def test_prune_control_fails_the_limits():
    run = control_run("tiny-gated", "prune-tiny")
    assert run.correct, run.checks
    low = run.control_run
    assert not low.correct, low.checks
    prune = harness.load_module(HERE / "runners" / "prune.py")
    assert set(low.checks) == set(prune.LIMITS)
    assert not low.checks["gram_gap"]["ok"]
    assert low.checks["gram_gap"]["value"] > \
        3 * run.checks["gram_gap"]["value"]


def test_serve_control_reads_worse_than_the_program():
    run = control_run("small-plain", "serve-tiny")
    assert run.correct, run.checks
    low = run.control_run
    assert not low.correct, low.checks
    assert not low.checks["mean_gap"]["ok"]
    assert low.checks["mean_gap"]["value"] > \
        3 * run.checks["mean_gap"]["value"]
    assert low.facts["widest_gap"] > run.facts["widest_gap"]
