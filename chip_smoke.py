#!/usr/bin/env python3
"""Prune -> pack -> serve minitron-4b at its published widths on a TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the mesh path on four chips

One process drives the main path through the entry points a user calls
(``launch.prune.prune``, ``launch.serve.serve``, ``ServeEngine``,
``ContinuousScheduler``). Widths, vocabulary and dtype are the published
ones; only the depth is cut (``N_LAYERS``) and the weights are random
from ``--seed``. Phases, one chip:

  prune-0.6    calibrate + SparseSwaps (Wanda warmstart, unstructured 0.6);
               the down-proj group's reported losses equal the exact
               losses of its masks, and refined <= warmstart
  refine-check the Pallas swap search against the chunked jnp search on
               one down-proj Gram
  prune-2:4    the same for 2:4
  reference    masked-dense float32 prefill logits of both mask sets
  serve-<fmt>  masked / gathered (0.6 masks) and nm24 (2:4 masks):
               greedy decode, prefill logits against the reference, and
               requests through the continuous scheduler (paged KV)

``--chips 4`` runs only the mesh path and what it is compared with:
row-sharded refinement and data-sharded calibration (``mesh="host"``)
against the single-device refinement of the same group, and
mesh-placed serving against one device.

Every packed matmul must lower to the Pallas kernel chosen by ``auto``.
Each phase prints its wall time with compile time apart, and the
device's memory. The last line is one JSON object naming the device; a
failed phase exits non-zero without it. With no TPU the script exits
non-zero at once: it never falls back to the CPU.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import shutil
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp

ROOT = Path(__file__).resolve().parent
SRC = ROOT / "src"
ARCH = "minitron-4b"
OUT = ROOT / "results" / "chip_smoke"

# depth cut from the published 32 (4 layers fit one 16 GB chip with room:
# the refinement of a stacked 4-layer down-proj group is the peak)
N_LAYERS = 4
# swap search passes per site, and 128-token calibration sequences
T_MAX, N_CALIB = 4, 8
# serving shapes: prefill T = batch * prompt_len = 512 tokens, decode T = 8
BATCH, PROMPT_LEN, GEN = 8, 64, 8

# prefill logits of a bf16-served format against the float32 masked-dense
# forward (max |diff| / max |ref|). bf16 keeps 8 mantissa bits and the
# rounding compounds through the layers and the 3072-long head
# contraction: on one v5e chip masked read 7.1e-3 and 7.8e-3, gathered
# 8.2e-3. The limit is about 2.5x the largest reading; a wrong kernel (a
# misplaced column, a lost slot) moves logits by their own scale.
LOGIT_TOL = 2e-2

# a packed format against the served masked format (same bf16 weights, the
# same kept entries). Their bf16 roundings of activations fall differently
# and do not cancel: gathered read 8.4e-3 from masked on the chip, as far
# as either lies from float32. So the same limit holds.
PACKED_TOL = LOGIT_TOL

# fraction of the warmstart loss within which (a) a reported site loss
# must equal the exact loss recomputed from its mask, the bookkeeping
# L + ΣΔL adding float32 rounding per accepted swap, and (b) the Pallas
# and chunked swap searches must agree when their masks differ: both score
# the same candidates in float32 but sum in different orders, so near-tied
# candidates can resolve differently; every accepted swap is still exactly
# monotone.
LOSS_TOL = 1e-3

# lowering + XLA compile; tracing nests (a jit traced inside another's
# trace) and so stays in the "rest" of a phase
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


class SmokeFailure(RuntimeError):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def memory() -> str:
    parts = []
    for d in jax.local_devices():
        st = d.memory_stats() or {}
        parts.append(f"dev{d.id} in_use {st.get('bytes_in_use', 0)} peak "
                     f"{st.get('peak_bytes_in_use', 0)} limit "
                     f"{st.get('bytes_limit', 0)}")
    return "; ".join(parts)


class PhaseLog:
    """Per-phase wall time with compile time apart (from JAX's monitoring
    events) and device memory after each phase."""

    def __init__(self):
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event in _COMPILE_EVENTS:
            self.compile_s += duration

    @contextlib.contextmanager
    def phase(self, name: str):
        t0, c0 = time.perf_counter(), self.compile_s
        print(f"[{name}] start | {memory()}", flush=True)
        yield
        wall = time.perf_counter() - t0
        comp = self.compile_s - c0
        print(f"[{name}] wall {wall:.3f} s (compile {comp:.3f} s, rest "
              f"{wall - comp:.3f} s) | {memory()}", flush=True)


def expect_pallas(rec: list, fmt: str) -> None:
    """Every packed matmul traced for ``fmt`` ran the Pallas kernel that
    ``kernel="auto"`` picked — never jnp."""
    if fmt in ("dense", "masked"):
        check(not rec, f"{fmt}: unexpected spmm dispatches {rec[:3]}")
        print(f"  kernel_used {fmt}: dense (no spmm)")
        return
    check(rec, f"{fmt}: no spmm dispatch recorded")
    bad = [r for r in rec if (r["kernel"], r["reason"]) != ("pallas", "auto")]
    check(not bad, f"{fmt}: non-Pallas dispatch {bad[:3]}")
    shapes = sorted({(r["fmt"], r["T"], r["d_out"], r["d_in"]) for r in rec})
    print(f"  kernel_used {fmt}: pallas/auto for {len(rec)} dispatches; "
          f"(fmt, T, d_out, d_in) = {shapes}")


def down_site_check(res) -> None:
    """The down-proj group's reported losses against the exact loss of
    its warmstart and refined masks (recomputed at highest precision):
    they must agree, and refinement must not raise the exact loss."""
    from repro.pruning import engine as engine_lib
    from repro.core import swap_math as sm

    name = "layers.mlp.w_down"
    report = res["report"]
    site = next(s for s in report.sites if s.name == name)
    pg = next(p for p in report.plan.groups if p.name == name)
    g = down_group(res)
    W = g.weights.astype(jnp.float32)
    m0 = engine_lib._warmstart_batch(W, g.gram.G, pg.rule.pattern, "wanda")
    m1 = jnp.asarray(report.masks["layers"]["mlp"]["w_down"], jnp.float32)
    with jax.default_matmul_precision("highest"):
        loss = jax.vmap(sm.row_loss)
        e0 = [float(x) for x in jnp.sum(loss(W, m0, g.gram.G), axis=1)]
        e1 = [float(x) for x in jnp.sum(loss(W, m1, g.gram.G), axis=1)]
    l0 = [float(x) for x in site.loss_init]
    l1 = [float(x) for x in site.loss_final]
    print(f"  {name} [{site.pattern}]: reported loss {l0} -> {l1}; exact "
          f"{e0} -> {e1}")
    for r0, r1, x0, x1 in zip(l0, l1, e0, e1):
        check(abs(r0 - x0) <= LOSS_TOL * x0 and abs(r1 - x1) <= LOSS_TOL * x0,
              f"{name}: reported losses ({r0}, {r1}) are not the exact "
              f"({x0}, {x1})")
        check(0.0 <= x1 <= x0, f"{name}: refined loss {x1} above "
              f"warmstart {x0}")


def prune_run(prune, pattern: str, out: Path, seed: int, **kw):
    return prune(ARCH, tiny=False, n_layers=N_LAYERS, pattern=pattern,
                 warmstart="wanda", method="sparseswaps", t_max=T_MAX,
                 n_calib=N_CALIB, calib_seq=128, calib_batch=4,
                 out_dir=str(out), seed=seed, **kw)


def down_group(res):
    """The down-proj site group (stacked weights + Grams) of a prune run."""
    from repro.pruning import sites as sites_lib
    ex = res["executor"]
    return sites_lib.enumerate_sites(ex.api.cfg, ex.params, ex.taps,
                                     only={"layers.mlp.w_down"})[0]


def backend_check(res) -> None:
    """Pallas vs chunked swap search on the first down-proj instance."""
    from repro.core import masks as masks_lib, sparseswaps, swap_math as sm
    from repro.core.warmstart import warmstart_mask

    g = down_group(res)
    W = g.weights[0].astype(jnp.float32)
    G = g.gram.G[0].astype(jnp.float32)
    pat = masks_lib.PerRow(0.6)
    m0 = warmstart_mask(W, G, pat, "wanda")
    out = {}
    for method in ("pallas", "chunked"):
        r = sparseswaps.refine(W, G, m0, pat, t_max=T_MAX, method=method,
                               k_swaps=8)
        with jax.default_matmul_precision("highest"):
            loss = float(jnp.sum(sm.row_loss(W, r.mask, G)))
        out[method] = (r.mask, loss)
    with jax.default_matmul_precision("highest"):
        l0 = float(jnp.sum(sm.row_loss(W, m0, G)))
    (mp, lp), (mc, lc) = out["pallas"], out["chunked"]
    differ = int(jnp.sum(mp != mc))
    print(f"  pallas vs chunked on {g.labels()[0]} ({W.shape[0]}x"
          f"{W.shape[1]}, t_max {T_MAX}): {differ} mask entries differ; "
          f"loss warmstart {l0!r} pallas {lp!r} chunked {lc!r}")
    check(lp <= l0 and lc <= l0, "a backend raised the loss")
    if differ:
        check(abs(lp - lc) <= LOSS_TOL * l0,
              f"backend losses differ by {abs(lp - lc) / l0:.2e} of the "
              f"warmstart loss (tolerance {LOSS_TOL})")


def reference_logits(cfg, params, masks, prompt):
    """Last-position prefill logits of the masked-dense model in float32
    at highest matmul precision, (B, vocab)."""
    import repro.models as models

    cfg32 = cfg.replace(dtype="float32")
    api32 = models.build(cfg32)

    @jax.jit
    def run(params, masks, tokens):
        p = jax.tree.map(lambda x: x.astype(jnp.float32), params)
        hidden, _, _ = api32.forward(p, {"tokens": tokens}, masks=masks)
        return api32.module.lm_head(p, hidden[:, -1:], cfg32)[:, 0]

    with jax.default_matmul_precision("highest"):
        return jax.block_until_ready(run(params, masks, prompt["tokens"]))


def logits_err(got, ref) -> float:
    got = got.astype(jnp.float32)
    return float(jnp.max(jnp.abs(got - ref)) / jnp.max(jnp.abs(ref)))


def schedule(engine, prompt, n_req: int = 4, max_new: int = GEN) -> dict:
    """``n_req`` greedy requests of mixed prompt lengths through the
    continuous scheduler; all must finish with ``max_new`` tokens."""
    from repro.serve import ContinuousScheduler
    sch = ContinuousScheduler(engine, max_batch=4, capacity=128,
                              page_size=16, decode_chunk=4, prefill_budget=4)
    rids = [sch.submit(prompt["tokens"][i, :PROMPT_LEN - 4 * i], max_new)
            for i in range(n_req)]
    done = sch.run_until_idle()
    check(sorted(done) == sorted(rids),
          f"scheduler finished {sorted(done)} of {rids}")
    for rid in rids:
        check(len(done[rid].tokens) == max_new,
              f"request {rid} returned {len(done[rid].tokens)} tokens")
    return {rid: list(done[rid].tokens) for rid in rids}


def serve_format(serve, fmt: str, masks_from: Path, ref, seed: int, log,
                 **kw):
    """serve() one format; check kernels, decode, logits (against
    ``ref`` when given) and the scheduler."""
    from repro.kernels import spmm
    from repro.launch.serve import make_prompt

    with log.phase(f"serve-{fmt}" + ("-mesh" if kw.get("mesh") else "")):
        with spmm.record_dispatch() as rec:
            out = serve(ARCH, tiny=False, n_layers=N_LAYERS, batch=BATCH,
                        prompt_len=PROMPT_LEN, gen=GEN,
                        masks_from=str(masks_from), fmt=fmt, seed=seed, **kw)
            engine = out["engine"]
            cfg = engine.cfg
            toks = out["tokens"]
            check(toks.shape == (BATCH, GEN), f"tokens shape {toks.shape}")
            check(bool(jnp.all((toks >= 0) & (toks < cfg.vocab_size))),
                  "token ids out of range")
            prompt = make_prompt(cfg, BATCH, PROMPT_LEN, seed)
            logits = engine.logits_trace(prompt, 1)[0]
            check(logits.shape == (BATCH, cfg.vocab_size),
                  f"logits shape {logits.shape}")
            check(bool(jnp.all(jnp.isfinite(logits))), "non-finite logits")
            if ref is not None:
                err = logits_err(logits, ref)
                print(f"  {fmt}: prefill logits vs float32 masked-dense: "
                      f"max |diff| / max |ref| = {err!r} (tolerance "
                      f"{LOGIT_TOL})")
                check(err <= LOGIT_TOL, f"{fmt}: logits off by {err:.3e}")
            sched = schedule(engine, prompt)
            print(f"  {fmt}: greedy decode tokens[0] {toks[0].tolist()}; "
                  f"scheduler finished {len(sched)} requests")
        expect_pallas(rec, fmt)
    return engine, toks, logits, sched


def one_chip(seed: int, log) -> None:
    from repro.core import packed as packed_lib
    from repro.launch.prune import prune
    from repro.launch.serve import make_prompt, serve
    import repro.configs as configs
    import repro.models as models

    with log.phase("prune-0.6"):
        res = prune_run(prune, "0.6", OUT / "u", seed)
        down_site_check(res)
    with log.phase("refine-check"):
        backend_check(res)
    del res
    with log.phase("prune-2:4"):
        res = prune_run(prune, "2:4", OUT / "nm", seed)
        down_site_check(res)
    del res

    with log.phase("reference"):
        cfg = configs.resolve(ARCH, n_layers=N_LAYERS)
        params = models.build(cfg).init(jax.random.key(seed))
        prompt = make_prompt(cfg, BATCH, PROMPT_LEN, seed)
        refs = {}
        for tag in ("u", "nm"):
            masks, p = packed_lib.load_masks_and_weights(cfg, params,
                                                         OUT / tag)
            refs[tag] = reference_logits(cfg, p, masks, prompt)
            del masks, p
        del params

    served = {}
    for fmt, tag in (("masked", "u"), ("gathered", "u"), ("masked", "nm"),
                     ("nm24", "nm")):
        logits = serve_format(serve, fmt, OUT / tag, refs[tag], seed,
                              log)[2]
        if fmt == "masked":
            served[tag] = logits.astype(jnp.float32)
            continue
        err = logits_err(logits, served[tag])
        print(f"  {fmt}: prefill logits vs served masked (same bf16 "
              f"weights): max |diff| / max |ref| = {err!r} (tolerance "
              f"{PACKED_TOL})")
        check(err <= PACKED_TOL, f"{fmt}: logits off masked by {err:.3e}")


def four_chips(seed: int, log) -> None:
    """Mesh path (``mesh="host"``) against one device, same process."""
    from repro.launch.prune import prune
    from repro.launch.serve import serve
    from repro.pruning import engine as engine_lib

    check(len(jax.devices()) == 4, f"want 4 devices, have "
          f"{len(jax.devices())}")
    with log.phase("prune-0.6-mesh"):
        res = prune_run(prune, "0.6", OUT / "mesh", seed, mesh="host")
        down_site_check(res)
    with log.phase("refine-single-device"):
        g = down_group(res)
        pg = next(p for p in res["report"].plan.groups
                  if p.name == "layers.mlp.w_down")
        ctx = dataclasses.replace(res["report"].plan.group_context(pg),
                                  swap_method="chunked", mesh=None)
        ref = engine_lib.refine_group("sparseswaps", g, pg.rule.pattern, ctx)
        got = res["report"].masks["layers"]["mlp"]["w_down"]
        differ = int(jnp.sum(jnp.asarray(got) != ref.masks))
        print(f"  layers.mlp.w_down masks, 4-device rows-sharded vs one "
              f"device: {differ} entries differ")
        check(differ == 0, "sharded masks differ from one device")
    del res, g, ref, got

    runs = {}
    for mesh in ("host", None):     # each engine is dropped after its phase
        runs[mesh] = serve_format(serve, "gathered", OUT / "mesh", None,
                                  seed, log, mesh=mesh)[1:]
    (t4, l4, s4), (t1, l1, s1) = runs["host"], runs[None]
    check(bool(jnp.all(t4 == t1)), "greedy tokens differ from one device")
    check(s4 == s1, "scheduler tokens differ from one device")
    err = logits_err(l4, l1.astype(jnp.float32))
    print(f"  mesh vs one device: greedy tokens equal, scheduler tokens "
          f"equal, prefill logits max |diff| / max |ref| = {err!r}")
    check(err <= LOGIT_TOL, f"mesh logits off by {err:.3e}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and every input")
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: {SRC / 'repro'} not found; run the script from "
              f"a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: no TPU found (JAX backend is {backend!r}); "
              f"this smoke runs on the chip only", file=sys.stderr)
        return 2
    from repro.launch import compile_cache
    cache = compile_cache.enable()
    log = PhaseLog()
    dev = jax.devices()[0]
    print(f"device {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
          f"{ARCH} n_layers={N_LAYERS}; compile cache {cache}; "
          f"{memory()}", flush=True)
    shutil.rmtree(OUT, ignore_errors=True)     # a rerun redoes every phase
    t0 = time.perf_counter()
    (four_chips if args.chips == 4 else one_chip)(args.seed, log)
    print(f"total {time.perf_counter() - t0:.3f} s (compile "
          f"{log.compile_s:.3f} s) | {memory()}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
