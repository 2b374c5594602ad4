#!/usr/bin/env python3
"""One run of one benchmark cell on the chip this process finds.

    python3 benchmarks/chip/run.py --workload minitron-4b.prune-0.6 \
        --seed 7 --seconds 30 --trace 0

Reads ``BENCHMARK.json`` at the checkout root, finds the cell's
configuration and mix files by name, sets up (weights from the seed,
warm-up from the compile cache), measures for ``--seconds``, checks what
the timed path produced against the plain reference, and prints one JSON
line: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics; with ``--trace 1`` its per-layer metrics from a
profiled run), ``device`` and ``checks`` (each compared number with its
limit). With no TPU, fewer chips than the cell asks for, or a device
missing from ``peaks.json``, it exits 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import harness  # noqa: E402


def _devices(chips: int):
    """The local devices, or a refusal when they are not TPU chips."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise harness.Refusal(f"no TPU found (JAX platform is "
                              f"{devs[0].platform!r}); this benchmark "
                              f"runs on the chip only")
    if len(devs) < chips:
        raise harness.Refusal(f"cell asks for {chips} chips, JAX sees "
                              f"{len(devs)}")
    return devs


def _enable_cache() -> str:
    """JAX's persistent compile cache at the program's fixed place in the
    checkout (or ``JAX_COMPILATION_CACHE_DIR``), holding every program so
    that a second run compiles nothing."""
    import jax
    from repro.launch import compile_cache
    path = compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        bench = harness.benchmark()
        cell = harness.cell(bench, args.workload)
        cfg = harness.config_doc(cell["config"])
        mix = harness.mix_doc(cell["traffic"])
        src = harness.ROOT / "src"
        if not (src / "repro").is_dir():
            raise harness.Refusal(f"{src / 'repro'} not found: run from a "
                                  f"checkout of the repository")
        sys.path.insert(0, str(src))
        devs = _devices(cell["chips"])
        peaks = harness.load_json(HERE / "peaks.json")
        kind = devs[0].device_kind
        if kind not in peaks:
            raise harness.Refusal(f"device {kind!r} has no entry in "
                                  f"peaks.json")
        cache = _enable_cache()
        runner = harness.runner(mix)
    except harness.Refusal as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2

    trace_dir = harness.ROOT / "results" / "chipbench" / args.workload
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
    run = harness.Run(cell=cell, config=cfg, mix=mix, seed=args.seed,
                      seconds=args.seconds, trace=bool(args.trace),
                      peaks=peaks[kind], t_start=T_START,
                      trace_dir=trace_dir)
    print(f"chipbench: {args.workload} on {devs[0].platform} {kind} "
          f"x{len(devs)}; seed {args.seed}; window {args.seconds} s; "
          f"trace {args.trace}; compile cache {cache}", file=sys.stderr,
          flush=True)
    runner.run(run)

    if args.trace:
        import devtrace
        run.trace = devtrace.reduce_dir(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
    metrics = {}
    for entry in harness.cell_metrics(bench, cell["name"],
                                      trace=bool(args.trace)):
        value = harness.metric_reader(entry["name"]).read(run)
        if value is not None:
            metrics[entry["name"]] = {"value": float(value),
                                      "unit": entry["unit"]}
    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(devs), "memory_peak_bytes": run.memory_peak}
    line = {"correct": run.correct, "attempted": run.attempted,
            "failed": run.failed, "metrics": metrics, "device": device}
    if run.trace is not None:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        line["breakdown"] = run.trace["breakdown"]
    line["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                      for k, c in run.checks.items()}
    print(f"chipbench: setup {run.setup_s!r} s, window {run.window_s!r} s, "
          f"compiles in window {len(run.compiled_in_window)}, peak "
          f"{run.memory_peak} B", file=sys.stderr)
    for msg in run.compiled_in_window[:8]:
        print(f"chipbench: in window: {msg}", file=sys.stderr)
    for k, c in run.checks.items():
        print(f"check {k}: {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if c['ok'] else 'FAIL'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
