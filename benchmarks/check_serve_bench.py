"""Schema + regression guard for BENCH_serve.json (CI).

    python benchmarks/check_serve_bench.py [path] \
        [--max-nm24-prefill-ratio 2.0] [--require-continuous-wins]

Asserts the bench doc is machine-readable — one ``prefill`` and one
``decode`` row per per-phase variant, every row carrying the keys
downstream tooling reads (``kernel_used`` included, so a jnp path stays
visible in the perf trajectory) — and that nm24 prefill
has not regressed past the given ratio of dense prefill. The default
2.0 is the CI guard on the interpret/jnp path; the committed repo-root
bench holds the tighter 1.5 acceptance ratio.

``phase == "load"`` rows (the ``serve_load.py`` arrival-rate sweep) are
validated separately: p50/p99 TTFT and per-token latency present and
ordered, the TTFT breakdown (``queue_wait`` + ``prefill``) present,
ordered, and summing to TTFT in the mean (an exact per-request identity
in the generator, so the means must agree to float tolerance), goodput
≤ offered load (an accounting invariant — delivered tokens can never
exceed requested tokens over the same makespan), waste/shipping and
robustness counters (``shed``/``expired``/``cancelled``/``evicted``)
non-negative, and ``kernel_used`` tagged. Rows carrying an ``error``
field (a sweep cell that raised) are tolerated but flagged as warnings
— they must still name their cell and they never count toward the
-wins gates.
``--require-continuous-wins`` additionally demands that wherever a
(variant, arrival_rate) pair carries both modes, continuous batching's
goodput strictly beats the fixed-batch path; ``--require-disagg-wins``
demands that at each variant's HIGHEST swept arrival rate (the
saturating point) the disaggregated rows beat the continuous baseline
on p99 TTFT at equal-or-better goodput (within a 2% noise band — the
two modes share the same decode plateau). Both are acceptance bars for
the committed run, off by default for CI smoke regenerations where
timing variance is real.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]

DOC_KEYS = {"arch", "batch", "prompt_len", "gen", "devices", "rows"}
ROW_KEYS = {"variant", "phase", "kernel", "kernel_used", "tok_s",
            "weight_bytes", "pack_s"}
PHASE_KEYS = {"prefill": {"prefill_s"}, "decode": {"cold_tok_s"}}
LOAD_KEYS = {"mode", "arrival_rate", "duration_s", "seed", "n_requests",
             "completed", "makespan_s", "offered_tok_s", "goodput_tok_s",
             "p50_ttft_s", "p99_ttft_s", "p50_tok_latency_s",
             "p99_tok_latency_s", "p50_queue_wait_s", "p99_queue_wait_s",
             "p50_prefill_s", "p99_prefill_s", "mean_ttft_s",
             "mean_queue_wait_s", "mean_prefill_s",
             "wasted_decode_tokens", "shipped_bytes",
             "shed", "expired", "cancelled", "evicted"}
LOAD_MODES = {"continuous", "fixed", "disaggregated"}
# a sweep cell that raised records an error row instead of aborting the
# whole bench — these identity keys must still be present so the failing
# cell is attributable
ERROR_ROW_KEYS = {"variant", "phase", "mode", "kernel", "arrival_rate",
                  "error"}


def _check_load_row(i: int, r: dict, errs: list) -> None:
    missing = LOAD_KEYS - r.keys()
    if missing:
        errs.append(f"load row {i} missing {sorted(missing)}")
        return
    tag = f"load row {i} ({r['variant']}/{r['mode']}@{r['arrival_rate']})"
    if r["mode"] not in LOAD_MODES:
        errs.append(f"{tag}: unknown mode {r['mode']!r}")
    if r["completed"] > r["n_requests"]:
        errs.append(f"{tag}: completed > n_requests")
    if r["goodput_tok_s"] > r["offered_tok_s"] * (1 + 1e-9):
        errs.append(f"{tag}: goodput {r['goodput_tok_s']:.1f} tok/s "
                    f"exceeds offered load {r['offered_tok_s']:.1f}")
    for a, b in (("p50_ttft_s", "p99_ttft_s"),
                 ("p50_tok_latency_s", "p99_tok_latency_s"),
                 ("p50_queue_wait_s", "p99_queue_wait_s"),
                 ("p50_prefill_s", "p99_prefill_s")):
        if r[a] < 0 or r[b] < r[a]:
            errs.append(f"{tag}: want 0 <= {a} <= {b}, got "
                        f"{r[a]:.4f} / {r[b]:.4f}")
    parts = r["mean_queue_wait_s"] + r["mean_prefill_s"]
    if abs(parts - r["mean_ttft_s"]) > 1e-6 + 1e-4 * abs(r["mean_ttft_s"]):
        errs.append(f"{tag}: TTFT breakdown does not sum — "
                    f"queue_wait {r['mean_queue_wait_s']:.6f} + prefill "
                    f"{r['mean_prefill_s']:.6f} != ttft "
                    f"{r['mean_ttft_s']:.6f} (mean)")
    for k in ("wasted_decode_tokens", "shipped_bytes",
              "shed", "expired", "cancelled", "evicted"):
        if r[k] < 0:
            errs.append(f"{tag}: {k} negative ({r[k]})")
    if r["mode"] != "disaggregated" and r["shipped_bytes"] != 0:
        errs.append(f"{tag}: shipped_bytes {r['shipped_bytes']} outside "
                    "disaggregated mode")


def check(doc: dict, *, max_nm24_prefill_ratio: float,
          require_continuous_wins: bool = False,
          require_disagg_wins: bool = False,
          warnings: list | None = None) -> list[str]:
    errs = []
    warnings = warnings if warnings is not None else []
    missing = DOC_KEYS - doc.keys()
    if missing:
        errs.append(f"doc missing keys {sorted(missing)}")
        return errs
    by, load_by = {}, {}
    for i, r in enumerate(doc["rows"]):
        if "error" in r:
            # tolerated-but-flagged: the cell failed, metrics are absent;
            # it never registers for the -wins gates
            missing = ERROR_ROW_KEYS - r.keys()
            if missing:
                errs.append(f"error row {i} missing {sorted(missing)}")
            else:
                warnings.append(
                    f"error row {i} ({r['variant']}/{r['mode']}"
                    f"@{r['arrival_rate']}): {r['error']}")
            continue
        missing = ROW_KEYS - r.keys()
        if missing:
            errs.append(f"row {i} missing keys {sorted(missing)}")
            continue
        phase = r["phase"]
        if not isinstance(r["kernel_used"], str) or not r["kernel_used"]:
            errs.append(f"row {i} ({r['variant']}/{phase}): kernel_used "
                        f"must be a non-empty string, got "
                        f"{r['kernel_used']!r}")
        if r["tok_s"] <= 0:
            errs.append(f"row {i} ({r['variant']}/{phase}): tok_s <= 0")
        if phase == "load":
            _check_load_row(i, r, errs)
            key = (r["variant"], r.get("mode"), r.get("arrival_rate"))
            if key in load_by:
                errs.append(f"duplicate load row for {key}")
            load_by[key] = r
            continue
        if phase not in PHASE_KEYS:
            errs.append(f"row {i}: unknown phase {phase!r}")
            continue
        missing = PHASE_KEYS[phase] - r.keys()
        if missing:
            errs.append(f"row {i} ({r['variant']}/{phase}) missing "
                        f"{sorted(missing)}")
        key = (r["variant"], phase)
        if key in by:
            errs.append(f"duplicate row for {key}")
        by[key] = r
    # per-phase completeness applies to variants with per-phase rows —
    # a doc may carry load rows for variants it never phase-timed
    for variant in {v for v, _ in by}:
        for phase in PHASE_KEYS:
            if (variant, phase) not in by:
                errs.append(f"missing {phase} row for variant {variant!r}")
    dense = by.get(("dense", "prefill"))
    nm24 = by.get(("nm24", "prefill"))
    if dense and nm24:
        ratio = nm24["prefill_s"] / dense["prefill_s"]
        if ratio > max_nm24_prefill_ratio:
            errs.append(
                f"nm24 prefill regression: {nm24['prefill_s']*1e3:.2f} ms "
                f"is {ratio:.2f}x dense ({dense['prefill_s']*1e3:.2f} ms), "
                f"bound {max_nm24_prefill_ratio:.2f}x")
    if require_continuous_wins:
        pairs = {(v, r) for v, m, r in load_by}
        if not pairs:
            errs.append("--require-continuous-wins: no load rows in doc")
        for v, rate in sorted(pairs):
            cont = load_by.get((v, "continuous", rate))
            fixed = load_by.get((v, "fixed", rate))
            if cont is None or fixed is None:
                errs.append(f"load sweep for {v!r}@{rate}: need both "
                            "continuous and fixed rows")
            elif cont["goodput_tok_s"] <= fixed["goodput_tok_s"]:
                errs.append(
                    f"continuous batching does not win for {v!r}@{rate}: "
                    f"{cont['goodput_tok_s']:.1f} <= "
                    f"{fixed['goodput_tok_s']:.1f} tok/s goodput")
    if require_disagg_wins:
        variants = {v for v, m, _ in load_by if m == "disaggregated"}
        if not variants:
            errs.append("--require-disagg-wins: no disaggregated load "
                        "rows in doc")
        for v in sorted(variants):
            rate = max(r for vv, m, r in load_by
                       if vv == v and m == "disaggregated")
            dis = load_by.get((v, "disaggregated", rate))
            cont = load_by.get((v, "continuous", rate))
            if cont is None:
                errs.append(f"disagg sweep for {v!r}@{rate}: no continuous "
                            "baseline row at the same rate")
            else:
                if dis["p99_ttft_s"] >= cont["p99_ttft_s"]:
                    errs.append(
                        f"disaggregation does not cut p99 TTFT for "
                        f"{v!r}@{rate}: {dis['p99_ttft_s']:.4f} >= "
                        f"{cont['p99_ttft_s']:.4f} s")
                # "equal-or-better" up to bench noise: goodputs at the
                # saturation plateau differ by well under 1% run to run
                if dis["goodput_tok_s"] < cont["goodput_tok_s"] * 0.98:
                    errs.append(
                        f"disaggregation loses goodput for {v!r}@{rate}: "
                        f"{dis['goodput_tok_s']:.1f} < "
                        f"{cont['goodput_tok_s']:.1f} tok/s")
    return errs


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("path", nargs="?",
                    default=str(ROOT / "BENCH_serve.json"))
    ap.add_argument("--max-nm24-prefill-ratio", type=float, default=2.0)
    ap.add_argument("--require-continuous-wins", action="store_true",
                    help="fail unless continuous goodput strictly beats "
                         "fixed at every (variant, rate) with both modes")
    ap.add_argument("--require-disagg-wins", action="store_true",
                    help="fail unless disaggregated serving beats the "
                         "continuous baseline on p99 TTFT at equal-or-"
                         "better goodput at each variant's highest rate")
    args = ap.parse_args(argv)
    doc = json.loads(Path(args.path).read_text())
    warnings: list[str] = []
    errs = check(doc, max_nm24_prefill_ratio=args.max_nm24_prefill_ratio,
                 require_continuous_wins=args.require_continuous_wins,
                 require_disagg_wins=args.require_disagg_wins,
                 warnings=warnings)
    for w in warnings:
        print(f"WARN: {w}", file=sys.stderr)
    if errs:
        for e in errs:
            print(f"FAIL: {e}", file=sys.stderr)
        return 1
    n = len(doc["rows"])
    n_load = sum(1 for r in doc["rows"] if r.get("phase") == "load")
    print(f"ok: {args.path} — {n} rows ({n_load} load), schema + nm24 "
          f"prefill ratio <= {args.max_nm24_prefill_ratio}x"
          + (f", {len(warnings)} error row(s) flagged" if warnings else "")
          + (", continuous wins" if args.require_continuous_wins else "")
          + (", disagg wins" if args.require_disagg_wins else ""))
    return 0


if __name__ == "__main__":
    sys.exit(main())
