"""Plan execution: calibrate -> refine per group -> apply, resumably.

``PruneExecutor`` runs a ``PrunePlan`` stage by stage. Each completed
site group's masks and per-row losses are checkpointed through
``repro.ckpt`` (atomic, hash-verified) under ``ckpt_dir/groups/<site>/``,
tagged with the group's *resolved* rule — an interrupted 70B-class
refinement resumes at the site group it died on and reproduces the final
masks bit-identically (npz round-trips fp32/int32 exactly; a checkpoint
whose resolved rule or weight/Gram content hash no longer matches the
plan is recomputed, not trusted). Every group's output is validated against its resolved pattern
*before* checkpointing, so a bad refiner fails fast at the offending
group instead of poisoning the resume state.

Progress flows through a callback protocol (``PruneCallback``) instead of
``progress=`` prints; ``PrintProgress`` reproduces the old console lines.

The monolithic ``prune_model`` survives in ``pipeline.py`` as a thin
compat shim over ``PruneRecipe.single`` + ``plan_pruning`` + this class,
verified bit-identical in tests.
"""
from __future__ import annotations

import contextlib
import dataclasses
import hashlib
import json
import warnings
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro import ckpt
from repro.core import masks as masks_lib
from repro.core import sparseswaps
from repro.runtime import fault_tolerance as ft
from repro.runtime import trace
from repro.models import ModelApi

from . import engine as engine_lib
from . import plan as plan_lib
from . import sites as sites_lib
from . import stats as stats_lib


@dataclasses.dataclass
class SiteReport:
    name: str                    # site-group name
    labels: list[str]            # per-instance labels
    loss_init: jnp.ndarray       # (N,) summed row loss per instance, warmstart
    loss_final: jnp.ndarray      # (N,) after refinement
    swaps: jnp.ndarray           # (N,) accepted swaps (sparseswaps only)
    pattern: str = ""            # resolved pattern for THIS site ("2:4", ...)
    method: str = ""             # resolved method for THIS site

    @property
    def error_reduction(self) -> jnp.ndarray:
        return (self.loss_init - self.loss_final) / jnp.maximum(
            self.loss_init, 1e-30)


@dataclasses.dataclass
class PruneReport:
    masks: dict                          # pytree for loss(..., masks=...)
    sites: list[SiteReport]
    method: str                          # run-level; "mixed" if per-site
    warmstart: str
    pattern: str
    wall_time_s: float                   # prune.run span, masks ready
    updated_params: dict | None = None   # sparsegpt only
    plan: plan_lib.PrunePlan | None = None

    def mean_error_reduction(self) -> float:
        """Mean relative per-layer error reduction (paper Tables 3/4)."""
        if not self.sites:            # e.g. an all-skip recipe
            return 0.0
        vals = jnp.concatenate([s.error_reduction for s in self.sites])
        return float(trace.wait(jnp.mean(vals), "prune.report", np.asarray))

    def total_loss(self, which: str = "final") -> float:
        key = {"init": "loss_init", "final": "loss_final"}[which]
        total = sum(jnp.sum(getattr(s, key)) for s in self.sites)
        return float(trace.wait(total, "prune.report", np.asarray))

    def summary(self) -> str:
        lines = [f"method={self.method} warmstart={self.warmstart} "
                 f"pattern={self.pattern} wall={self.wall_time_s:.1f}s",
                 f"mean error reduction: {100*self.mean_error_reduction():.2f}%"]
        mixed = self.method == "mixed" or self.pattern == "mixed"
        for s in self.sites:
            red = 100 * float(trace.wait(jnp.mean(s.error_reduction),
                                         "prune.report", np.asarray))
            tag = f"  [{s.pattern} {s.method}]" if mixed else ""
            lines.append(f"  {s.name:28s} n={len(s.labels):3d} "
                         f"err-reduction {red:6.2f}%{tag}")
        return "\n".join(lines)


# ---------------------------------------------------------------------------
# progress callbacks
# ---------------------------------------------------------------------------

class PruneCallback:
    """Executor progress protocol. Subclass and override what you need."""

    def on_plan(self, plan: plan_lib.PrunePlan) -> None:
        """Called once before any work, with the resolved plan."""

    def on_group_start(self, planned: plan_lib.PlannedGroup,
                       index: int, total: int) -> None:
        """Called before each active group refines (or restores)."""

    def on_group_done(self, planned: plan_lib.PlannedGroup,
                      report: SiteReport, *, restored: bool) -> None:
        """Called after each group; ``restored`` = loaded from checkpoint."""

    def on_run_done(self, report: PruneReport) -> None:
        """Called once with the assembled report."""


class PrintProgress(PruneCallback):
    """The old ``progress=True`` console lines, as a callback."""

    def on_group_done(self, planned, report, *, restored):
        red = 100 * float(trace.wait(jnp.mean(report.error_reduction),
                                     "prune.progress", np.asarray))
        tag = " (restored)" if restored else ""
        print(f"  {report.name:28s} err-reduction {red:6.2f}%{tag}")


# ---------------------------------------------------------------------------
# executor
# ---------------------------------------------------------------------------

def _write_updated_weights(new_params: dict, g: sites_lib.SiteGroup,
                           W1: jnp.ndarray):
    """Insert a group's updated weight stack at its param path."""
    W1 = W1.reshape(*g.stack_shape, *W1.shape[1:]) if g.stack_shape else W1[0]
    node = new_params
    for k in g.mask_path[:-1]:
        node = node[k]
    node[g.mask_path[-1]] = W1.astype(node[g.mask_path[-1]].dtype)


def _rule_tag(pg: plan_lib.PlannedGroup) -> dict:
    """The resolved-rule fingerprint a group checkpoint must match."""
    r = pg.rule
    return {"pattern": r.pattern_str, "method": r.method,
            "warmstart": r.warmstart, "t_max": r.t_max, "eps": r.eps,
            "k_swaps": r.k_swaps}


def _data_fingerprint(g: sites_lib.SiteGroup) -> str:
    """Content hash of a group's refinement inputs (weights + Gram).

    Group checkpoints are only trusted when the data they were computed
    from is byte-identical — a rerun with a different seed, --from-ckpt or
    calibration set into the same out dir recomputes instead of silently
    restoring masks of the old weights. Hashing is O(bytes) on host,
    negligible next to refinement; only paid when ckpt_dir is set.
    Moments-level groups (no full Gram) hash diag + mean instead.
    """
    h = hashlib.sha256()
    stats = ((g.gram.G,) if g.gram.G is not None
             else (g.gram.gram_diag, g.gram.mean))
    for arr in trace.wait((g.weights, *stats), "prune.fingerprint",
                          jax.device_get):
        h.update(np.ascontiguousarray(arr).tobytes())
    return h.hexdigest()


def _summarize(values: list[str], *, empty: str = "-") -> str:
    uniq = sorted(set(values))
    return uniq[0] if len(uniq) == 1 else ("mixed" if uniq else empty)


class PruneExecutor:
    """Executes a ``PrunePlan`` with group-granular checkpoint/resume.

    Args:
        api/params: the model being pruned.
        plan: output of ``plan_pruning`` (resolved rules + engine paths).
        taps: precomputed calibration statistics (legacy dict); when both
            ``taps`` and ``stats`` are ``None``, ``run(calib_batches)``
            accumulates a ``CalibStats`` through ``pruning.stats`` first
            (skip-aware, donated-carry, data-sharded when the plan has a
            mesh, resumable under ``<ckpt_dir>/calib/``).
        stats: a ``pruning.stats.CalibStats`` — the streaming subsystem's
            output. Validated against the plan: statistics accumulated at
            a lower level than a group's method needs fail here, before
            any refinement runs.
        calib_spec: overrides the spec ``run`` auto-calibrates with
            (e.g. ``plan.calib_spec(minimal=True)`` to drop dsnot-only
            sites to moments level). Default: the skip-aware full-Gram
            spec, whose reports are bit-compatible with the legacy path.
        ckpt_dir: enables per-group checkpointing under
            ``<ckpt_dir>/groups/<site>/`` and resume-on-rerun. Group
            checkpoints are keyed by the resolved rule AND a content hash
            of the group's weights/Gram — different seeds, source
            checkpoints or calibration data recompute instead of
            restoring stale masks.
        callback: a ``PruneCallback``; ``None`` = silent.
        engine_mode: "batched" (default) or "reference" (per-instance
            loop, for verification).
    """

    def __init__(self, api: ModelApi, params: dict,
                 plan: plan_lib.PrunePlan, *, taps: dict | None = None,
                 stats: stats_lib.CalibStats | None = None,
                 calib_spec: stats_lib.CalibSpec | None = None,
                 calib_ckpt_every: int = 0,
                 ckpt_dir: str | Path | None = None,
                 callback: PruneCallback | None = None,
                 engine_mode: str = "batched"):
        if engine_mode not in ("batched", "reference"):
            raise ValueError(f"unknown engine_mode {engine_mode!r}")
        if taps is not None and stats is not None:
            raise ValueError("pass either taps= (legacy dict) or stats= "
                             "(CalibStats), not both")
        self.api = api
        self.params = params
        self.plan = plan
        self.stats = stats
        self.calib_spec = calib_spec
        if stats is not None:
            need = plan.calib_spec(minimal=True)
            if not stats.spec.covers(need):
                raise ValueError(
                    "CalibStats were accumulated under a spec that does "
                    "not cover this plan — rebuild with "
                    "plan.calib_spec() (stats has "
                    f"{stats.spec.levels}, plan needs {need.levels})")
            taps = stats.taps
        if calib_spec is not None:
            # same up-front check for the spec run() will calibrate with:
            # an insufficient level must fail here, not after the whole
            # calibration pass
            need = plan.calib_spec(minimal=True)
            if not calib_spec.covers(need):
                raise ValueError(
                    "calib_spec does not cover this plan — build it with "
                    f"plan.calib_spec() (spec has {calib_spec.levels}, "
                    f"plan needs {need.levels})")
        self.taps = taps
        self.calib_ckpt_every = calib_ckpt_every
        self.ckpt_dir = Path(ckpt_dir) if ckpt_dir is not None else None
        self.callback = callback or PruneCallback()
        self.engine_mode = engine_mode
        self._last_report: PruneReport | None = None

    # -- group checkpointing ------------------------------------------------

    def _group_dir(self, name: str) -> Path:
        return self.ckpt_dir / "groups" / name

    def _restore_group(self, pg: plan_lib.PlannedGroup,
                       g: sites_lib.SiteGroup,
                       fingerprint: str) -> engine_lib.GroupResult | None:
        """Load a finished group's result iff its checkpoint matches the
        plan's resolved rule AND the current weights/Gram bytes."""
        if self.ckpt_dir is None:
            return None
        gdir = self._group_dir(pg.name)
        step = ckpt.latest_valid(gdir)
        if step is None:
            return None
        man_path = gdir / f"step_{step:08d}" / "MANIFEST.json"
        try:
            man = json.loads(man_path.read_text())
        except (OSError, json.JSONDecodeError):
            return None
        extra = man.get("extra", {})
        if (extra.get("rule") != _rule_tag(pg)
                or extra.get("data") != fingerprint):
            return None
        target = {e["path"]: jax.ShapeDtypeStruct(tuple(e["shape"]),
                                                  e["dtype"])
                  for e in man["leaves"]}
        if ("masks" not in target
                or target["masks"].shape != tuple(g.weights.shape)):
            return None
        tree, _ = ckpt.restore(gdir, step, target)
        return engine_lib.GroupResult(
            masks=jnp.asarray(tree["masks"]),
            loss_init=jnp.asarray(tree["loss_init"]),
            loss_final=jnp.asarray(tree["loss_final"]),
            swaps=jnp.asarray(tree["swaps"]),
            new_weights=(jnp.asarray(tree["new_weights"])
                         if "new_weights" in tree else None))

    def _save_group(self, pg: plan_lib.PlannedGroup, index: int,
                    res: engine_lib.GroupResult, fingerprint: str) -> None:
        if self.ckpt_dir is None:
            return
        tree = {"masks": res.masks, "loss_init": res.loss_init,
                "loss_final": res.loss_final, "swaps": res.swaps}
        if res.new_weights is not None:
            tree["new_weights"] = res.new_weights
        gdir = self._group_dir(pg.name)
        # a stale checkpoint (e.g. from an earlier recipe) may occupy this
        # step — publish past it, then drop everything but the newest
        existing = ckpt.steps(gdir)
        step = index if not existing else max(max(existing) + 1, index)
        # a transient OSError here would otherwise abort a multi-hour run
        # after the group's refinement already finished — retry with backoff
        ft.retry(ckpt.save, gdir, step, tree,
                 retries=3, base_delay=0.05, max_delay=1.0,
                 extra={"rule": _rule_tag(pg), "data": fingerprint,
                        "engine_path": pg.engine_path})
        ckpt.gc(gdir, keep=1)

    # -- execution ----------------------------------------------------------

    def run(self, calib_batches=None) -> PruneReport:
        """Execute the plan: calibrate -> refine per group -> apply.

        Spans (``runtime.trace``): ``prune.run`` around
        ``prune.calibrate``, ``prune.sites``, one ``prune.group`` per
        active group and ``prune.assemble``. A group span holds
        ``prune.refine`` and ``prune.check.wait`` and carries ``name``,
        ``instances``, ``rows`` and ``d_in``; for a refined group, while
        spans are recorded, also ``passes`` and ``rows_scored`` (the
        search-pass count, ``core.sparseswaps.count_search_passes``),
        ``swaps`` (committed swaps) and, for sparseswaps, ``k`` (the
        candidate swaps per row and pass). ``wall_time_s`` is the
        ``prune.run`` span, closed once the masks are ready.
        """
        plan = self.plan
        with trace.timed("prune.run") as run_span:
            self.callback.on_plan(plan)
            single = plan.single_device_groups()
            if single:
                # exactly once per run — the plan's describe() already
                # marked these groups "single-device" before execution
                warnings.warn(
                    f"mesh= is only honored by method='sparseswaps'; "
                    f"{len(single)} group(s) refine single-device: "
                    + ", ".join(single))

            if self.taps is None:
                if calib_batches is None:
                    raise ValueError("no taps and no calib_batches to "
                                     "accumulate them from")
                with trace.span("prune.calibrate"):
                    self._calibrate(calib_batches)
            active = [pg for pg in plan.groups if not pg.skip]
            # skip-listed groups never materialize their stacked
            # weights/Grams
            with trace.span("prune.sites"):
                groups = {g.name: g for g in sites_lib.enumerate_sites(
                    self.api.cfg, self.params, self.taps,
                    only={pg.name for pg in active})}

            new_params = None
            if any(pg.rule.method == "sparsegpt" for pg in active):
                new_params = jax.tree.map(lambda x: x, self.params)

            site_masks: dict[str, jnp.ndarray] = {}
            reports: list[SiteReport] = []
            for i, pg in enumerate(active):
                g = groups[pg.name]
                self.callback.on_group_start(pg, i, len(active))
                N, R, d = g.weights.shape
                with trace.span("prune.group", name=pg.name, instances=N,
                                rows=R, d_in=d) as span:
                    res, restored = self._group(pg, g, i, span)
                    site_masks[g.name] = res.masks
                    rep = SiteReport(
                        name=g.name, labels=g.labels(),
                        loss_init=jnp.sum(res.loss_init, axis=1),
                        loss_final=jnp.sum(res.loss_final, axis=1),
                        swaps=jnp.sum(res.swaps, axis=1),
                        pattern=pg.rule.pattern_str, method=pg.rule.method)
                    reports.append(rep)
                    if res.new_weights is not None:
                        _write_updated_weights(new_params, g,
                                               res.new_weights)
                self.callback.on_group_done(pg, rep, restored=restored)

            with trace.span("prune.assemble"):
                mask_tree = sites_lib.build_mask_tree(
                    self.api.cfg, site_masks,
                    [groups[pg.name] for pg in active])
                # skip rules may empty a whole top-level family the models
                # index directly (masks["layers"], ...) — keep those keys
                # present. The family tables define group names mirroring
                # param paths, so the first dotted component IS the
                # top-level tree key.
                for pg in plan.groups:
                    mask_tree.setdefault(pg.spec.name.split(".", 1)[0], {})
            trace.wait(mask_tree, "prune.masks")

        report = PruneReport(
            masks=mask_tree,
            sites=reports,
            method=_summarize([pg.rule.method for pg in active]),
            warmstart=_summarize([pg.rule.warmstart for pg in active]),
            pattern=_summarize([pg.rule.pattern_str for pg in active]),
            wall_time_s=run_span.seconds,
            updated_params=new_params,
            plan=plan,
        )
        self._last_report = report
        self.callback.on_run_done(report)
        return report

    def _calibrate(self, calib_batches) -> None:
        """Streaming, skip-aware, donated-carry accumulation; batches
        shard over the plan's mesh when they divide its data axes."""
        spec = (self.calib_spec if self.calib_spec is not None
                else self.plan.calib_spec(minimal=False))
        self.stats = stats_lib.accumulate_stats(
            self.api, self.params, calib_batches, spec=spec,
            mesh=self.plan.mesh,
            ckpt_dir=(self.ckpt_dir / "calib"
                      if self.ckpt_dir is not None else None),
            checkpoint_every=self.calib_ckpt_every)
        self.taps = self.stats.taps

    def _group(self, pg: plan_lib.PlannedGroup, g: sites_lib.SiteGroup,
               index: int, span) -> tuple[engine_lib.GroupResult, bool]:
        """One group's result, restored from its checkpoint or refined,
        validated and checkpointed; ``span`` takes its counts."""
        fp = _data_fingerprint(g) if self.ckpt_dir is not None else ""
        res = self._restore_group(pg, g, fp)
        if res is not None:
            return res, True
        run_fn = {"batched": engine_lib.refine_group,
                  "reference": engine_lib.refine_group_reference}[
                      self.engine_mode]
        ctx = self.plan.group_context(pg)
        counting = trace.enabled()
        with trace.span("prune.refine"), (
                sparseswaps.count_search_passes() if counting
                else contextlib.nullcontext()) as cnt:
            res = run_fn(pg.rule.method, g, pg.rule.pattern, ctx)
        pattern = pg.rule.pattern
        if not trace.wait(res.masks, "prune.check",
                          partial(masks_lib.validate_mask, pattern=pattern)):
            raise ValueError(
                f"refiner {pg.rule.method!r} produced masks "
                f"violating {pg.rule.pattern_str!r} at group "
                f"{pg.name!r}")
        if counting:
            span.set(passes=cnt.passes, rows_scored=cnt.rows_scored,
                     swaps=int(trace.wait(jnp.sum(res.swaps), "prune.swaps",
                                          np.asarray)))
            if pg.rule.method == "sparseswaps":
                d = g.weights.shape[-1]
                span.set(k=sparseswaps._pick_k(ctx.k_swaps, d,
                                               pattern.block(d)))
        self._save_group(pg, index, res, fp)
        return res, False

    # -- post-prune recovery ------------------------------------------------

    def recover(self, spec=None, *, checkpoint_every: int = 0,
                batches=None, verbose: bool = False):
        """Run the PERP recovery pass on the last ``run()``'s masks.

        ``spec`` defaults to the plan's attached ``RecoverSpec`` (recipe
        ``recover=``), else ``RecoverSpec()``. Recovery trains on top of
        the report's ``updated_params`` when the refiner produced them
        (sparsegpt), checkpoints under ``<ckpt_dir>/recover``, and
        installs the recovered tree back into the report — the very next
        ``export_packed()`` ships it, so ``ServeEngine``/``--masks-from``
        serve the recovered model with zero new serving code.
        """
        # note: ``from . import recover`` would resolve to the re-exported
        # function on the package, not this submodule
        from .recover import RecoverSpec
        from .recover import recover as _recover

        report = self._last_report
        if report is None:
            raise ValueError("nothing to recover — call run() first")
        if spec is None:
            spec = self.plan.recover or RecoverSpec()
        base = (report.updated_params
                if report.updated_params is not None else self.params)
        res = _recover(
            self.api, base, report.masks, spec, mesh=self.plan.mesh,
            ckpt_dir=self.ckpt_dir, checkpoint_every=checkpoint_every,
            batches=batches, verbose=verbose)
        report.updated_params = res.params
        return res

    # -- serving export -----------------------------------------------------

    def export_packed(self, out_dir: str | Path, fmt: str = "nm24",
                      *, report: "PruneReport | None" = None) -> Path:
        """Export the refined masks as a servable packed checkpoint.

        Packs the executor's weights under the last ``run()``'s masks
        (or an explicit ``report``) into ``core.packed`` format ``fmt``
        and checkpoints the packed values/idx trees atomically under
        ``out_dir`` — the artifact ``repro.serve.ServeEngine`` (and
        ``launch/serve.py --masks-from``) consumes without re-packing.
        SparseGPT runs export their *updated* weights.
        """
        from repro.core import packed as packed_lib

        report = report if report is not None else self._last_report
        if report is None:
            raise ValueError("nothing to export — call run() first or "
                             "pass report=")
        params = (report.updated_params
                  if report.updated_params is not None else self.params)
        tree = packed_lib.pack_tree(self.api.cfg, params, report.masks, fmt)
        vals, idx, meta = {}, {}, {}
        flat = jax.tree_util.tree_flatten_with_path(
            tree, is_leaf=lambda x: isinstance(x, packed_lib.PackedWeight))[0]
        for path, leaf in flat:
            if not isinstance(leaf, packed_lib.PackedWeight):
                continue
            name = ".".join(str(p.key) for p in path)
            vals[name] = leaf.values
            idx[name] = leaf.idx
            meta[name] = {"fmt": leaf.fmt, "d_in": leaf.d_in,
                          "n": leaf.n, "m": leaf.m,
                          "dtype": str(leaf.values.dtype)}
        out = Path(out_dir)
        ckpt.save(out / "packed", 0, {"values": vals, "idx": idx},
                  extra={"format": fmt, "sites": meta})
        # masks ride along so masked-dense serving (and re-packing into
        # the other format) works from the same artifact
        ckpt.save(out / "masks", 0, report.masks)
        if report.updated_params is not None:
            # dump every leaf that differs from the executor's base
            # params: sparsegpt's updated site weights AND recovered
            # norms/biases/adapter merges all ride the same splice path
            # (core.packed._splice_weights keys on dotted names)
            upd = changed_leaves(self.params, params)
            if upd:
                ckpt.save(out / "weights", 0, upd)
        return out


def changed_leaves(base: dict, new: dict) -> dict:
    """Flat {dotted name: leaf} of every leaf in ``new`` that differs
    from ``base`` — the minimal weight dump the serving splice path
    (``core.packed._splice_weights``) restores over a fresh init."""
    out = {}
    base_flat = jax.tree_util.tree_flatten_with_path(base)[0]
    new_flat = jax.tree_util.tree_flatten_with_path(new)[0]
    for (bpath, bleaf), (_, nleaf) in zip(base_flat, new_flat):
        if nleaf is bleaf:
            continue
        if np.array_equal(*trace.wait((nleaf, bleaf), "prune.export",
                                      jax.device_get)):
            continue
        out[".".join(str(p.key) for p in bpath)] = nleaf
    return out
