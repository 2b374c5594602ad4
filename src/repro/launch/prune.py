"""Pruning launcher: the paper's pipeline as a deployable job.

    PYTHONPATH=src python -m repro.launch.prune --arch llama31-8b --tiny \
        --sparsity 0.6 --warmstart wanda --method sparseswaps --t-max 50

Loads (or trains) a model, plans the run (``--plan-only`` prints the
resolved per-site table — engine paths, weight/Gram bytes — and exits
without spending a FLOP), calibrates, executes the plan group-by-group
with resumable checkpoints, evaluates dense vs pruned, and writes masks +
a JSON report. ``--recipe recipe.json`` swaps the single global rule for
a declarative per-site recipe (mixed N:M + unstructured, skip-lists,
per-rule t_max); ``--from-ckpt`` prunes a trained checkpoint.

Calibration streams through ``pruning.stats``: recipe-aware tap
selection (skip-rule sites accumulate nothing), a donated-carry
accumulator, and — with ``--mesh`` — batches sharded along the data axis.
``--calib-stats minimal`` additionally drops dsnot-only sites to O(d)
moments. Accumulation checkpoints every ``--calib-ckpt-every`` batches
under ``<out>/prune_ckpt/calib``, and with ``--out-dir`` every completed
site group's masks land under ``<out>/prune_ckpt`` — an interrupted run
resumes at the calibration batch / site group it died on (DESIGN §6).

``--recover norms_biases [--recover-steps N --recover-lr LR]`` appends
PERP post-prune recovery (``pruning.recover``): masked-gradient AdamW on
the selected ~1% of params over the calibration stream, resumable under
``<out>/prune_ckpt/recover``, with the recovered changed leaves dumped
to ``<out>/weights`` so ``launch/serve.py --masks-from <out>`` serves
the recovered model.
"""
from __future__ import annotations

import argparse
import json
from pathlib import Path

import jax
import jax.numpy as jnp

import repro.configs as configs
import repro.models as models
from repro import ckpt, pruning
from repro.core import masks as masks_lib
from repro.launch import compile_cache
from repro.train import steps as steps_lib

# the one shared parser (core.masks); kept under its historical name
parse_pattern = masks_lib.parse_pattern


def _build_recipe(pattern, *, recipe: str | None, warmstart: str,
                  method: str, t_max: int,
                  k_swaps: int | None = None) -> pruning.PruneRecipe:
    if recipe is not None:
        return pruning.PruneRecipe.from_json(Path(recipe).read_text())
    return pruning.PruneRecipe.single(
        parse_pattern(pattern), method=method, warmstart=warmstart,
        t_max=t_max, k_swaps=k_swaps)


def prune(arch: str, *, tiny: bool = True, n_layers: int | None = None,
          pattern="0.6",
          warmstart: str = "wanda", method: str = "sparseswaps",
          t_max: int = 50, k_swaps: int | None = None,
          compact_every: int | None = None,
          n_calib: int = 16, calib_seq: int = 128,
          calib_batch: int = 4, from_ckpt: str | None = None,
          out_dir: str | None = None, seed: int = 0,
          calib_ckpt_every: int = 0, mesh: str | None = None,
          recipe: str | None = None, plan_only: bool = False,
          calib_stats: str = "full", recover: str | None = None,
          recover_steps: int = 50, recover_lr: float = 1e-3,
          verbose: bool = True) -> dict:
    """``n_layers`` cuts the config's depth and nothing else (weights
    are random from ``seed``).

    ``mesh``: None (single device), "host" (all local devices), or
    "production" — sparseswaps refinement then runs row-sharded via
    repro.dist (groups whose method has no distributed refiner are marked
    "single-device" in the plan).

    ``recover``: a PERP selection name ("norms", "biases", "norms_biases",
    "all_masked", "lora") runs post-prune recovery for ``recover_steps``
    steps at ``recover_lr`` on the calibration stream; it overrides a
    recipe-attached ``recover`` spec. Recovered weights are evaluated,
    checkpointed under ``<out>/prune_ckpt/recover``, and their changed
    leaves dumped to ``<out>/weights`` — ``launch/serve.py --masks-from
    <out>`` then serves the recovered model directly.

    Returns the report, the dense and pruned evaluations, and the
    ``executor`` (its calibration statistics stay reachable)."""
    import dataclasses as _dc

    cfg = configs.resolve(arch, tiny=tiny, n_layers=n_layers)
    if verbose:
        print(configs.describe(cfg))
    api = models.build(cfg)
    rec = _build_recipe(pattern, recipe=recipe, warmstart=warmstart,
                        method=method, t_max=t_max, k_swaps=k_swaps)
    if recover is not None:
        # CLI wins over a recipe-attached spec; calibration geometry and
        # seed follow the pruning run's own calibration stream
        rec = _dc.replace(rec, recover=pruning.RecoverSpec(
            select=recover, steps=recover_steps, lr=recover_lr,
            batch_size=calib_batch, seq_len=calib_seq, seed=seed))
    mesh_obj = None
    if mesh:
        from repro.launch import mesh as mesh_lib
        mesh_obj = (mesh_lib.make_production_mesh() if mesh == "production"
                    else mesh_lib.make_host_mesh())

    if plan_only:
        # shapes only — no weights materialized, no FLOP spent
        abstract = jax.eval_shape(lambda: api.init(jax.random.key(seed)))
        plan = pruning.plan_pruning(api, abstract, rec, mesh=mesh_obj,
                                    compact_every=compact_every)
        print(plan.describe())
        return {"plan": plan}

    params = api.init(jax.random.key(seed))
    if from_ckpt:
        latest = ckpt.latest_valid(from_ckpt)
        if latest is None:
            raise FileNotFoundError(f"no valid checkpoint under {from_ckpt}")
        state, _ = ckpt.restore(
            from_ckpt, latest,
            jax.eval_shape(lambda: steps_lib.init_state(api, jax.random.key(seed))))
        params = state.params

    plan = pruning.plan_pruning(api, params, rec, mesh=mesh_obj,
                                compact_every=compact_every)
    if verbose:
        print(plan.describe())

    batches = list(pruning.calibration_batches(
        cfg, n_samples=n_calib, seq_len=calib_seq, batch_size=calib_batch,
        seed=seed))

    # streaming recipe-aware calibration (pruning.stats) driven by the
    # executor: skip-rule taps never accumulate; "minimal" drops
    # dsnot-only sites to feature moments; mesh= shards the batches
    spec = plan.calib_spec(minimal=(calib_stats == "minimal"))
    executor = pruning.PruneExecutor(
        api, params, plan, calib_spec=spec,
        calib_ckpt_every=calib_ckpt_every,
        ckpt_dir=Path(out_dir) / "prune_ckpt" if out_dir else None,
        callback=pruning.PrintProgress() if verbose else None)
    report = executor.run(batches)
    dense_eval = pruning.evaluate(api, params, seed=seed)
    eval_params = report.updated_params if report.updated_params is not None \
        else params
    sparse_eval = pruning.evaluate(api, eval_params, masks=report.masks,
                                   seed=seed)
    if verbose:
        print(report.summary())
        print(f"dense : ppl {dense_eval['perplexity']:.2f}  "
              f"acc {100*dense_eval['accuracy']:.2f}%")
        print(f"pruned: ppl {sparse_eval['perplexity']:.2f}  "
              f"acc {100*sparse_eval['accuracy']:.2f}%")

    recovered_eval = rec_res = None
    if plan.recover is not None:
        rec_res = executor.recover(checkpoint_every=calib_ckpt_every,
                                   verbose=verbose)
        recovered_eval = pruning.evaluate(
            api, report.updated_params, masks=report.masks, seed=seed)
        if verbose:
            print(f"recovered ({plan.recover.select}, "
                  f"{rec_res.steps_run + rec_res.start_step} steps, "
                  f"{100*rec_res.trainable_frac:.2f}% of params): "
                  f"ppl {recovered_eval['perplexity']:.2f}  "
                  f"acc {100*recovered_eval['accuracy']:.2f}%")

    if out_dir:
        out = Path(out_dir)
        out.mkdir(parents=True, exist_ok=True)
        ckpt.save(out / "masks", 0, report.masks)
        if report.updated_params is not None:
            from repro.pruning.executor import changed_leaves
            upd = changed_leaves(params, report.updated_params)
            if upd:
                # serve --masks-from <out> splices these over a fresh init
                ckpt.save(out / "weights", 0, upd)
        (out / "recipe.json").write_text(rec.to_json())
        doc = {
            "arch": arch, "method": report.method,
            "warmstart": report.warmstart, "pattern": report.pattern,
            "mean_error_reduction": report.mean_error_reduction(),
            "dense": dense_eval, "pruned": sparse_eval,
            "wall_time_s": report.wall_time_s,
            "sites": [{"name": s.name, "pattern": s.pattern,
                       "method": s.method,
                       "err_red": [float(x) for x in s.error_reduction]}
                      for s in report.sites],
        }
        if recovered_eval is not None:
            doc["recovered"] = recovered_eval
            doc["recovery"] = {
                "spec": plan.recover.to_json_dict(),
                "trainable_count": rec_res.trainable_count,
                "trainable_frac": rec_res.trainable_frac,
                "steps_run": rec_res.steps_run,
                "start_step": rec_res.start_step,
                "diverged": rec_res.diverged,
                "ce_start": rec_res.ce_history[0] if rec_res.ce_history
                else None,
                "ce_end": rec_res.ce_history[-1] if rec_res.ce_history
                else None,
            }
        (out / "report.json").write_text(json.dumps(doc, indent=1))
    out_d = {"report": report, "dense": dense_eval, "pruned": sparse_eval,
             "executor": executor}
    if recovered_eval is not None:
        out_d["recovered"] = recovered_eval
        out_d["recover_result"] = rec_res
    return out_d


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--n-layers", type=int, default=None,
                    help="cut the depth to N layers (widths unchanged)")
    ap.add_argument("--sparsity", default="0.6", help="fraction or N:M")
    ap.add_argument("--warmstart", default="wanda",
                    choices=["magnitude", "wanda", "ria"])
    ap.add_argument("--method", default="sparseswaps",
                    choices=["none", "sparseswaps", "dsnot", "sparsegpt"])
    ap.add_argument("--t-max", type=int, default=50)
    ap.add_argument("--k-swaps", type=int, default=None,
                    help="swaps committed per search pass (default: auto)")
    ap.add_argument("--compact-every", type=int, default=None,
                    help="gather converged rows out every S passes")
    ap.add_argument("--n-calib", type=int, default=16)
    ap.add_argument("--from-ckpt", default=None)
    ap.add_argument("--out-dir", default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mesh", default=None, choices=["host", "production"],
                    help="shard refinement over a device mesh (repro.dist)")
    ap.add_argument("--recipe", default=None, metavar="recipe.json",
                    help="per-site rules (overrides --sparsity/--method/...)")
    ap.add_argument("--plan-only", action="store_true",
                    help="print the resolved plan table and exit")
    ap.add_argument("--calib-stats", default="full",
                    choices=["full", "minimal"],
                    help="full: skip-aware Gram for every refined site; "
                         "minimal: dsnot-only sites drop to O(d) moments "
                         "(their reported losses become diagonal proxies)")
    ap.add_argument("--calib-ckpt-every", type=int, default=0,
                    help="checkpoint the calibration accumulator every k "
                         "batches (under <out>/prune_ckpt/calib)")
    ap.add_argument("--recover", default=None,
                    choices=["norms", "biases", "norms_biases",
                             "all_masked", "lora"],
                    help="run PERP post-prune recovery on this param "
                         "selection (overrides a recipe-attached spec)")
    ap.add_argument("--recover-steps", type=int, default=50,
                    help="recovery AdamW steps over the calibration stream")
    ap.add_argument("--recover-lr", type=float, default=1e-3,
                    help="recovery peak learning rate (warmup-cosine)")
    args = ap.parse_args(argv)
    compile_cache.enable()
    prune(args.arch, tiny=args.tiny, n_layers=args.n_layers,
          pattern=args.sparsity,
          warmstart=args.warmstart, method=args.method, t_max=args.t_max,
          k_swaps=args.k_swaps, compact_every=args.compact_every,
          n_calib=args.n_calib, from_ckpt=args.from_ckpt,
          out_dir=args.out_dir, seed=args.seed, mesh=args.mesh,
          recipe=args.recipe, plan_only=args.plan_only,
          calib_stats=args.calib_stats,
          calib_ckpt_every=args.calib_ckpt_every,
          recover=args.recover, recover_steps=args.recover_steps,
          recover_lr=args.recover_lr)


if __name__ == "__main__":
    main()
