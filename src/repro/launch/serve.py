"""Serving launcher: batched prefill + decode on dense or packed weights.

    PYTHONPATH=src python -m repro.launch.serve --arch llama31-8b --tiny \
        --batch 4 --prompt-len 32 --gen 16

Sparse serving loads real pruning artifacts and packs them once at
startup (``repro.serve.ServeEngine``):

    # prune, checkpointing masks under out/prune_ckpt/groups/<site>/
    python -m repro.launch.prune --arch llama31-8b --tiny \
        --sparsity 2:4 --out-dir out
    # serve the refined masks from the packed 2:4 format
    python -m repro.launch.serve --arch llama31-8b --tiny \
        --masks-from out --format nm24

``--masks-from`` accepts any pruning-run artifact: an executor
checkpoint dir (``groups/<site>/step_*``), a masks-tree checkpoint, or
the launcher ``--out-dir`` root. ``--format`` picks the weight
representation (dense / masked / nm24 / gathered), ``--kernel`` the
spmm path (auto = Pallas on TPU, jnp elsewhere). ``--bench`` times
dense vs masked-dense vs packed and writes ``BENCH_serve.json`` at the
repo root — one prefill row and one decode row per format, each with
the kernel the trace actually lowered (``kernel_used``), decode/prefill
tok/s, and resident weight bytes.
"""
from __future__ import annotations

import argparse
import json
from pathlib import Path

import jax

import repro.configs as configs
import repro.models as models
from repro.data import synthetic
from repro.launch import compile_cache
from repro.launch import mesh as mesh_lib
from repro.serve import ServeEngine, bench_rows

BENCH_OUT = Path(__file__).resolve().parents[3] / "BENCH_serve.json"


def serve(arch: str, *, tiny: bool = True, n_layers: int | None = None,
          batch: int = 4,
          prompt_len: int = 32, gen: int = 16, masks=None,
          masks_from: str | None = None, fmt: str | None = None,
          kernel: str = "auto", mesh: str | None = None, seed: int = 0,
          bench: bool = False, bench_out: Path | None = None,
          sample=None, load_bench: bool = False, load_rates=(16.0, 128.0),
          load_duration: float = 2.0, load_seed: int = 0,
          load_prompt_len=(8, 24), load_output_len=(4, 16),
          load_deadline: float | None = None,
          load_queue_ttl: float | None = None, load_shed: bool = False,
          load_max_queue: int | None = None,
          disaggregate: bool = False, prefill_chunk: int | None = None,
          chaos: bool = False, chaos_seed: int = 0,
          verbose: bool = True) -> dict:
    """Serve a batch of prompts; returns tokens + timing (+ bench rows)
    and the ``engine`` that served them.

    ``n_layers`` cuts the config's depth and nothing else (weights are
    random from ``seed``).

    ``masks``/``masks_from`` feed the sparse formats. ``fmt=None`` picks
    the faithful default — "masked" when a mask source is given, "dense"
    otherwise; an explicit "dense" is honored either way (the unpruned
    baseline). ``mesh``: None, "host", or "production".

    ``sample`` is an optional ``serve.SamplingParams`` (greedy when
    None). ``load_bench`` runs the continuous-vs-fixed load-generator
    sweep (``serve.loadgen``) over ``load_rates`` arrivals/s and merges
    the ``phase == "load"`` rows into the bench doc — the ``--bench``
    per-phase rows are left untouched. ``disaggregate`` adds a third
    sweep mode: prefill into its own page pool, ship sessions to the
    decode pool on join (``prefill_chunk`` sets the chunked-prefill
    window width for that mode).

    Robustness knobs: ``load_deadline``/``load_queue_ttl`` bound each
    request's total lifetime / queue wait on the simulated clock;
    ``load_shed`` returns typed ``Rejected`` instead of raising when
    the queue is full; ``load_max_queue`` caps the queue. ``chaos``
    runs the deterministic fault-injection harness
    (``loadgen.run_chaos`` with ``FaultPlan.chaos(chaos_seed)``) and
    exits nonzero if any fault path leaks pages or perturbs a
    completed token stream.
    """
    cfg = configs.resolve(arch, tiny=tiny, n_layers=n_layers)
    if verbose:
        print(configs.describe(cfg))
    api = models.build(cfg)
    params = api.init(jax.random.key(seed))
    mesh_obj = None
    if mesh:
        mesh_obj = (mesh_lib.make_production_mesh() if mesh == "production"
                    else mesh_lib.make_host_mesh())

    prompt = make_prompt(cfg, batch, prompt_len, seed)

    mask_src = masks_from if masks_from is not None else masks
    if fmt is None:
        fmt = "masked" if mask_src is not None else "dense"
    # resolve the mask source ONCE — a checkpoint may also carry updated
    # weights (sparsegpt); every engine below reuses the same trees.
    # ``params`` stays the untouched dense baseline.
    from repro.core import packed as packed_lib
    params_srv = params
    if isinstance(mask_src, (str, Path)):
        mask_src, params_srv = packed_lib.load_masks_and_weights(
            cfg, params, mask_src)

    engine = ServeEngine(api, params if fmt == "dense" else params_srv,
                         masks=mask_src, fmt=fmt, kernel=kernel,
                         mesh=mesh_obj)
    res = engine.generate(prompt, gen, sampling=sample)
    out = {"tokens": res.tokens, "wall_s": res.prefill_s + res.decode_s,
           "tok_s": res.tok_s, "weight_bytes": engine.weight_bytes(),
           "format": fmt, "engine": engine}
    if verbose:
        print(f"{arch}: served {batch} requests, {gen} new tokens each in "
              f"{out['wall_s']:.2f}s ({res.tok_s:.1f} decode tok/s, "
              f"format={fmt}, {out['weight_bytes']/2**20:.1f} MiB weights)")
        print("sample output ids:", res.tokens[0][:12].tolist())

    if bench:
        formats = ["dense"]
        if mask_src is not None:
            formats += ["masked", "nm24", "gathered"]
        rows = bench_rows(api, params, mask_src, prompt, gen,
                          formats=_servable(formats, api, params_srv,
                                            mask_src),
                          kernel=kernel, mesh=mesh_obj,
                          masked_params=params_srv)
        doc = {"arch": arch, "batch": batch, "prompt_len": prompt_len,
               "gen": gen, "devices": len(jax.devices()), "rows": rows}
        path = bench_out or BENCH_OUT
        path.write_text(json.dumps(doc, indent=1))
        out["bench"] = rows
        if verbose:
            for r in rows:
                extra = (f"prefill {r['prefill_s']*1e3:7.2f} ms"
                         if r["phase"] == "prefill" else
                         f"cold {r['cold_tok_s']:8.1f} tok/s")
                print(f"  {r['variant']:8s} {r['phase']:7s} "
                      f"{r['tok_s']:9.1f} tok/s  {extra}  "
                      f"[{r['kernel_used']}]  "
                      f"{r['weight_bytes']/2**20:8.2f} MiB")
            print(f"wrote {path}")

    if load_bench:
        from repro.serve import loadgen
        from repro.serve.sampling import GREEDY
        formats = ["masked", "nm24", "gathered"] if mask_src is not None \
            else ["dense"]
        load_cfg = loadgen.LoadConfig(
            duration_s=load_duration, seed=load_seed,
            prompt_len=tuple(load_prompt_len),
            output_len=tuple(load_output_len),
            sampling=sample if sample is not None else GREEDY,
            deadline_s=load_deadline, queue_ttl_s=load_queue_ttl)
        modes = ("continuous", "fixed")
        if disaggregate:
            modes += ("disaggregated",)
        sched_kw = {}
        if load_shed:
            sched_kw["admission"] = "shed"
        if load_max_queue is not None:
            sched_kw["max_queue"] = load_max_queue
        load_rows = loadgen.bench_load_rows(
            api, params, mask_src,
            formats=_servable(formats, api, params_srv, mask_src),
            rates=tuple(load_rates), load=load_cfg, kernel=kernel,
            mesh=mesh_obj, masked_params=params_srv, max_batch=batch,
            modes=modes, prefill_chunk=prefill_chunk, **sched_kw)
        path = bench_out or BENCH_OUT
        doc = json.loads(path.read_text()) if path.exists() else {
            "arch": arch, "batch": batch, "prompt_len": prompt_len,
            "gen": gen, "devices": len(jax.devices()), "rows": []}
        loadgen.merge_load_rows(doc, load_rows)
        path.write_text(json.dumps(doc, indent=1))
        out["load_bench"] = load_rows
        if verbose:
            for r in load_rows:
                if "error" in r:
                    print(f"  {r['variant']:8s} {r['mode']:13s} rate "
                          f"{r['arrival_rate']:5.1f}/s  ERROR {r['error']}")
                    continue
                print(f"  {r['variant']:8s} {r['mode']:13s} "
                      f"rate {r['arrival_rate']:5.1f}/s  goodput "
                      f"{r['goodput_tok_s']:8.1f} tok/s  p99 TTFT "
                      f"{r['p99_ttft_s']*1e3:7.1f} ms (wait "
                      f"{r['p99_queue_wait_s']*1e3:7.1f} + prefill "
                      f"{r['p99_prefill_s']*1e3:6.1f})  waste "
                      f"{r['wasted_decode_tokens']:5d}  "
                      f"[{r['kernel_used']}]")
            print(f"wrote {path}")
        errors = [r for r in load_rows if "error" in r]
        if errors:
            raise SystemExit(
                f"{len(errors)} load cell(s) failed: " + "; ".join(
                    f"{r['variant']}/{r['mode']}@{r['arrival_rate']}: "
                    f"{r['error']}" for r in errors))

    if chaos:
        from repro.serve import FaultPlan, loadgen
        from repro.serve.sampling import GREEDY
        chaos_cfg = loadgen.LoadConfig(
            arrival_rate=float(load_rates[0]), duration_s=load_duration,
            seed=load_seed, prompt_len=tuple(load_prompt_len),
            output_len=tuple(load_output_len),
            sampling=sample if sample is not None else GREEDY)
        workload = loadgen.make_workload(chaos_cfg)
        plan = FaultPlan.chaos(chaos_seed)
        verdict = loadgen.run_chaos(engine, workload, plan,
                                    max_batch=batch)
        out["chaos"] = verdict
        if verbose:
            print(f"chaos [{verdict['plan']}]: "
                  f"{verdict['completed_faulted']}/{verdict['n_requests']} "
                  f"completed, leaked {verdict['leaked_bytes']} B, "
                  f"{verdict['stream_mismatches']} stream mismatches, "
                  f"fired {verdict['faults_fired']}, "
                  f"counters {verdict['counters']}")
            print("chaos verdict:", "OK" if verdict["ok"] else "FAILED")
        if not verdict["ok"]:
            raise SystemExit(1)
    return out


def make_prompt(cfg, batch: int, prompt_len: int, seed: int) -> dict:
    """The (batch, prompt_len) validation-split prompt ``serve`` runs."""
    corpus = synthetic.CorpusConfig(cfg.vocab_size, seed=seed)
    pipe = synthetic.DataPipeline(corpus, batch, prompt_len, split="val")
    return synthetic.with_modality(pipe.get(0), cfg, jax.random.key(seed))


def _servable(formats, api, params, mask_src) -> list:
    """Drop packed formats the mask source cannot represent (e.g. nm24
    for an unstructured per-row mask) instead of failing the bench.
    Representability is a mask property — no weights are packed here."""
    from repro.core import packed as packed_lib
    masks = mask_src.masks if hasattr(mask_src, "masks") else mask_src
    return [fmt for fmt in formats
            if fmt not in ("nm24", "gathered")
            or packed_lib.representable(api.cfg, masks, fmt)]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--n-layers", type=int, default=None,
                    help="cut the depth to N layers (widths unchanged)")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--masks-from", default=None,
                    help="pruning artifact dir: executor ckpt "
                         "(groups/<site>/), masks-tree ckpt, or --out-dir "
                         "root")
    ap.add_argument("--format", default=None,
                    choices=["dense", "masked", "nm24", "gathered"],
                    help="weight representation (default: masked when "
                         "--masks-from is given, dense otherwise; an "
                         "explicit dense serves the unpruned baseline)")
    ap.add_argument("--kernel", default="auto",
                    choices=["auto", "pallas", "jnp"],
                    help="spmm kernel for packed formats")
    ap.add_argument("--mesh", default=None, choices=["host", "production"])
    ap.add_argument("--bench", action="store_true",
                    help="time dense vs masked vs packed; write "
                         "BENCH_serve.json")
    ap.add_argument("--bench-out", default=None,
                    help="where --bench writes its rows (default: the "
                         "repo-root BENCH_serve.json)")
    ap.add_argument("--sample", default=None, metavar="TEMP[,TOP_P[,TOP_K]]",
                    help="sample instead of greedy decode, e.g. "
                         "'0.8,0.95,40' (temperature, nucleus mass, top-k)")
    ap.add_argument("--load-bench", action="store_true",
                    help="run the continuous-vs-fixed load-generator "
                         "sweep and merge phase='load' rows into the "
                         "bench doc")
    ap.add_argument("--load-rates", default="16,128",
                    help="comma-separated arrival rates (requests/s)")
    ap.add_argument("--load-duration", type=float, default=2.0,
                    help="simulated arrival window in seconds")
    ap.add_argument("--load-seed", type=int, default=0)
    ap.add_argument("--load-prompt-len", default="8:24", metavar="MIN:MAX",
                    help="uniform prompt-length bounds for the workload")
    ap.add_argument("--load-output-len", default="4:16", metavar="MIN:MAX",
                    help="uniform output-length bounds for the workload")
    ap.add_argument("--load-deadline", type=float, default=None,
                    help="per-request total-lifetime deadline (simulated "
                         "seconds); expiries are counted, not served late")
    ap.add_argument("--load-queue-ttl", type=float, default=None,
                    help="per-request queue-wait bound (simulated seconds)")
    ap.add_argument("--load-shed", action="store_true",
                    help="shed (typed Rejected) instead of raising when "
                         "the admission queue is full")
    ap.add_argument("--load-max-queue", type=int, default=None,
                    help="admission queue cap for the load sweep")
    ap.add_argument("--disaggregate", action="store_true",
                    help="add the disaggregated prefill/decode mode to "
                         "the load sweep (separate pools, page shipping)")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="chunked-prefill window width (power of two) for "
                         "the disaggregated mode")
    ap.add_argument("--chaos", action="store_true",
                    help="run the deterministic fault-injection harness "
                         "(fault-free vs faulted pass) and exit nonzero "
                         "on leaked pages or stream mismatches")
    ap.add_argument("--chaos-seed", type=int, default=0,
                    help="seed for FaultPlan.chaos")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    compile_cache.enable()
    from repro.serve.sampling import parse_sample_flag
    span = lambda s: tuple(int(x) for x in s.split(":", 1))
    serve(args.arch, tiny=args.tiny, n_layers=args.n_layers,
          batch=args.batch,
          prompt_len=args.prompt_len, gen=args.gen,
          masks_from=args.masks_from, fmt=args.format, kernel=args.kernel,
          mesh=args.mesh, seed=args.seed, bench=args.bench,
          bench_out=Path(args.bench_out) if args.bench_out else None,
          sample=parse_sample_flag(args.sample) if args.sample else None,
          load_bench=args.load_bench,
          load_rates=tuple(float(r) for r in args.load_rates.split(",")),
          load_duration=args.load_duration, load_seed=args.load_seed,
          load_prompt_len=span(args.load_prompt_len),
          load_output_len=span(args.load_output_len),
          load_deadline=args.load_deadline,
          load_queue_ttl=args.load_queue_ttl, load_shed=args.load_shed,
          load_max_queue=args.load_max_queue,
          disaggregate=args.disaggregate, prefill_chunk=args.prefill_chunk,
          chaos=args.chaos, chaos_seed=args.chaos_seed)


if __name__ == "__main__":
    main()
