"""Runner for pruning mixes: the paper's job, pass after pass.

Set-up makes the weights from the seed, plans the job from the mix, and
compiles every program a pass runs: the calibration step on two batches,
and each site group's refinement on all-zero statistics (no swap lowers
a zero loss, so each search stops after its first pass).

The window runs whole passes through the program's entry point,
``PruneExecutor.run``, until ``--seconds`` have gone by. Pass ``p``
calibrates on a fresh draw of token ids from ``(seed, p)``, then refines
every site group. After the window the plain reference recomputes the
last pass's Grams in float32 from the same tokens, and with them the
exact loss of the Wanda warmstart and of the program's refined masks.

Compared with the reference (each with its limit, ``LIMITS``):

  gram_gap      worst relative Frobenius distance of a program Gram
  loss_gap      worst relative distance of a reported site loss from
                the exact loss of the same mask (refined) or of the
                reference warmstart (warmstart)
  rows_off      rows whose kept count is not the pattern's (exact)
  loss_rises    site instances whose refined mask loses more than the
                warmstart (exact)
  least_gain    the smallest loss reduction of any site instance, in %
                (must reach its limit: a refinement that returns its
                warmstart reads 0)
"""
from __future__ import annotations

import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np

import harness
import weights as weights_lib

# Limits on the compared numbers; PERF.md gives the readings they were
# set from (program runs against the float8 control).
LIMITS = {"gram_gap": 0.015, "loss_gap": 0.0025, "rows_off": 0,
          "loss_rises": 0, "least_gain": 0.25}


def calib_batches(seed: int, draw: int, mix: dict, vocab: int):
    """Draw ``draw`` of the calibration set, as the program's batches."""
    n, S, B = mix["calib_sequences"], mix["calib_seq_len"], mix["calib_batch"]
    toks = weights_lib.token_ids(seed, weights_lib.CALIB, draw, (n, S),
                                 vocab)
    toks = toks.reshape(n // B, B, S)
    labels = jnp.roll(toks, -1, axis=-1)
    return [{"tokens": toks[i], "labels": labels[i]}
            for i in range(n // B)], toks


class _Spans:
    """Executor callback of a traced run: blocked spans of calibration and
    of each group's refinement, and each group's counted search passes
    (counting reads the pass count to the host, so only traced runs do)."""

    def __init__(self, run: harness.Run):
        self.run, self.ex, self._span, self._count = run, None, None, None

    def _enter(self, name):
        self._span = self.run.span(name)
        self._span.__enter__()

    def _exit(self, *arrays):
        jax.block_until_ready(arrays)
        self._span.__exit__(None, None, None)

    def on_plan(self, plan):
        self._enter("calib")

    def on_group_start(self, planned, index, total):
        if index == 0:
            self._exit(self.ex.taps)
        self._enter("refine")
        from repro.core import sparseswaps
        self._count = sparseswaps.count_search_passes()
        self._cnt = self._count.__enter__()

    def on_group_done(self, planned, report, *, restored):
        self._count.__exit__(None, None, None)
        self._exit(report.loss_final)
        self.run.facts.setdefault("group_passes", []).append(
            (planned.name, self._cnt.passes))

    def on_run_done(self, report):
        pass


def run(run: harness.Run) -> None:
    from repro import pruning
    from repro.core import masks as masks_lib

    mix, seed = run.mix, run.seed
    cfg, api = harness.program_model(run.config)
    ref = harness.reference(run.config)
    pattern = masks_lib.parse_pattern(mix["pattern"])
    params = weights_lib.make_params(api.init, seed)
    recipe = pruning.PruneRecipe.single(
        pattern, method=mix["method"], warmstart=mix["warmstart"],
        t_max=mix["t_max"], k_swaps=mix["k_swaps"])
    plan = pruning.plan_pruning(api, params, recipe)
    spec = plan.calib_spec(minimal=False)
    run.note("weights and plan")

    # warm-up: the calibration step on two batches (the second call takes
    # the step's own output as its carry, a signature of its own), then
    # every group's refinement programs on zero statistics
    batches, _ = calib_batches(seed, 0, mix, cfg.vocab_size)
    stats = pruning.accumulate_stats(api, params, batches[:2], spec=spec)
    zero = pruning.CalibStats(
        taps=jax.tree.map(jnp.zeros_like, stats.taps), spec=spec, batches=1)
    run.note("calibration step")
    jax.block_until_ready(pruning.PruneExecutor(
        api, params, plan, stats=zero).run().masks)
    del stats, zero
    run.note("refinement programs")

    t0 = run.open_window()
    passes = 0
    while True:
        ex = report = None          # one pass's state alive at a time
        batches, toks = calib_batches(seed, passes + 1, mix, cfg.vocab_size)
        spans = _Spans(run) if run.tracing else None
        ex = pruning.PruneExecutor(api, params, plan, calib_spec=spec,
                                   callback=spans)
        if spans is not None:
            spans.ex = ex
        report = ex.run(batches)
        jax.block_until_ready(report.masks)
        passes += 1
        if time.perf_counter() - t0 >= run.seconds:
            break
    run.close_window()
    run.read_memory_peak()

    taps = ex.taps
    del ex, batches
    run.attempted = passes * sum(len(s.labels) for s in report.sites)
    run.facts.update(passes=passes, layers_pruned=passes * cfg.n_layers)
    run.facts["flops_pass"] = pass_flops(run.config, mix, taps)
    run.facts["gram_calls"] = gram_calls(taps, mix)
    run.facts["topk_calls"] = topk_calls(report.plan, params)

    # --- the reference: Grams of the last pass, exact losses ----------
    m = ref.model_fields(run.config)
    B = mix["calib_batch"]
    batches = toks.reshape(-1, B, toks.shape[-1])
    grams = ref.calib_grams(params, batches, m)
    compare(run, ref, params, report, taps, grams, pattern)
    run.note("reference")
    if run.control:
        control(run, ref, params, report, grams, pattern, batches, m)


def control(run, ref, params, report, grams, pattern, batches, m) -> None:
    """The reference in float8 put in the program's place: its Grams, its
    Wanda masks and their losses, compared exactly as the program's."""
    low = ref.calib_grams(params, batches, m, quant="fp8")
    taps = {name: {"g": low[ref.SITE_INPUT[name.split(".")[-1]]]}
            for name in (s.name for s in report.sites)}
    sites, masks = [], {}
    for site in report.sites:
        path = site.name.split(".")
        W = harness.tree_get(params, path)
        G = low[ref.SITE_INPUT[path[-1]]]
        keep = pattern.keep_per_row(W.shape[-1])
        M = jnp.stack([ref.wanda_mask(W[i], G[i], keep=keep)
                       for i in range(W.shape[0])])
        loss = jnp.stack([jnp.sum(ref.row_loss(W[i], M[i], G[i]))
                          for i in range(W.shape[0])])
        node = masks
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = M
        sites.append(SimpleNamespace(name=site.name, loss_init=loss,
                                     loss_final=loss))
    run.control_run = run.shadow()
    compare(run.control_run, ref, params,
            SimpleNamespace(sites=sites, masks=masks),
            {k.split(".")[-1]: v for k, v in taps.items()}, grams, pattern)


def compare(run, ref, params, report, taps, grams, pattern) -> None:
    """The checks and the loss reduction, from the reference Grams."""
    gram_gap = 0.0
    for name, ent in taps.items():
        G_ref = grams[ref.SITE_INPUT[name]]
        G = ent["g"].astype(jnp.float32)
        gap = jnp.linalg.norm((G - G_ref).reshape(G.shape[0], -1), axis=1) / \
            jnp.linalg.norm(G_ref.reshape(G.shape[0], -1), axis=1)
        gram_gap = max(gram_gap, float(jnp.max(gap)))
    loss_gap, rows_off, rises, red = 0.0, 0, 0, []
    for site in report.sites:
        path = site.name.split(".")
        W = harness.tree_get(params, path)
        M = harness.tree_get(report.masks, path)
        G_ref = grams[ref.SITE_INPUT[path[-1]]]
        keep = pattern.keep_per_row(W.shape[-1])
        rows_off += int(jnp.sum(jnp.sum(M, axis=-1) != keep))
        for i in range(W.shape[0]):
            m0 = ref.wanda_mask(W[i], G_ref[i], keep=keep)
            l0 = float(jnp.sum(ref.row_loss(W[i], m0, G_ref[i])))
            l1 = float(jnp.sum(ref.row_loss(W[i], M[i], G_ref[i])))
            r0, r1 = float(site.loss_init[i]), float(site.loss_final[i])
            loss_gap = max(loss_gap, abs(r0 - l0) / l0, abs(r1 - l1) / l1)
            rises += int(l1 > l0)
            red.append(100.0 * (1.0 - l1 / l0))
    run.facts["loss_reduction_pct"] = float(np.mean(red))
    run.check("gram_gap", gram_gap, LIMITS["gram_gap"])
    run.check("loss_gap", loss_gap, LIMITS["loss_gap"])
    run.check("rows_off", rows_off, LIMITS["rows_off"])
    run.check("loss_rises", rises, LIMITS["loss_rises"])
    run.check("least_gain", min(red), LIMITS["least_gain"], above=True)


# --- work counts (read by the per-layer metrics) ------------------------

def pass_flops(config: dict, mix: dict, taps: dict) -> float:
    """FLOPs one pass requires: the layers' forward over the calibration
    tokens, one Gram per distinct site input, and 3·R·d² per site per
    search pass is added by the reader from the counted passes."""
    import flops
    m = config["model"]
    T = mix["calib_sequences"] * mix["calib_seq_len"]
    fwd = flops.decoder_forward_flops(m, mix["calib_seq_len"]) * \
        mix["calib_sequences"]
    d, f = m["d_model"], m["d_ff"]
    grams = m["n_layers"] * (3 * flops.gram_flops(T, d)
                             + flops.gram_flops(T, f))
    return float(fwd + grams)


def gram_calls(taps: dict, mix: dict) -> list:
    """(tokens, d, calls per pass) of the Gram kernel: one call per tap
    per layer per calibration batch."""
    T = mix["calib_batch"] * mix["calib_seq_len"]
    n_b = mix["calib_sequences"] // mix["calib_batch"]
    out = []
    for ent in taps.values():
        L, d, _ = ent["g"].shape
        out.append((T, int(d), int(L) * n_b))
    return out


def topk_calls(plan, params) -> list:
    """(name, instances, rows, d_in) of each refined group."""
    out = []
    for pg in plan.groups:
        if pg.skip:
            continue
        W = harness.tree_get(params, pg.name.split("."))
        out.append((pg.name, int(np.prod(W.shape[:-2])), int(W.shape[-2]),
                    int(W.shape[-1])))
    return out
