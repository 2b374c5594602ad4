#!/usr/bin/env python3
"""Readings that set a cell's limits: the program's and the control's.

    python3 benchmarks/chip/control.py --workload minitron-4b.prune-0.6 \
        --seconds 1 --seeds 1 2 3

Runs the cell as ``run.py`` does (a short window is enough: one prune
pass, or one closed loop of requests), once per seed in one process, and
besides the program's compared numbers reads the control's: the plain
reference computed in float8 (e4m3, scaled), the precision below the
configuration's bfloat16, put in the program's place and judged by the
same checks and limits. Prints one JSON line per seed: each compared
number of the program and of the control, the limits, and both
verdicts (the program's ``correct`` is expected true, the control's
false). The benchmark's own runs never run the control.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import harness  # noqa: E402
import run as run_lib  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    try:
        bench = harness.benchmark()
        cell = harness.cell(bench, args.workload)
        cfg = harness.config_doc(cell["config"])
        mix = harness.mix_doc(cell["traffic"])
        sys.path.insert(0, str(harness.ROOT / "src"))
        devs = run_lib._devices(cell["chips"])
        peaks = harness.load_json(HERE / "peaks.json")[devs[0].device_kind]
        run_lib._enable_cache()
        runner = harness.runner(mix)
    except (harness.Refusal, KeyError) as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    for seed in args.seeds:
        run = harness.Run(cell=cell, config=cfg, mix=mix, seed=seed,
                          seconds=args.seconds, trace=False, peaks=peaks)
        run.control = True
        runner.run(run)
        low = run.control_run
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "program": {k: c["value"] for k, c in run.checks.items()},
            "limits": {k: c["limit"] for k, c in run.checks.items()},
            "control": {k: c["value"] for k, c in low.checks.items()},
            "correct": run.correct, "control_correct": low.correct}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
