"""Batched sparse serving engine: pack once, serve from packed weights.

The serving counterpart of the pruning pipeline. ``ServeEngine`` takes a
model + a mask source (an in-memory tree, a ``PruneReport``, or any
pruning-run checkpoint directory — executor group checkpoints included)
and serves batched prefill + greedy decode in one of four weight
formats:

* ``dense``    — the unpruned baseline;
* ``masked``   — dense weights multiplied by 0/1 masks every matmul (the
  pre-packing reference path; arithmetic-faithful, zero bytes saved);
* ``nm24``     — 2:4/N:M index-packed values + uint8 metadata through
  ``kernels.spmm.spmm_nm24``;
* ``gathered`` — per-row kept-column gather through ``spmm_gather``.

Packing happens ONCE at construction (``core.packed.pack_tree``); the
packed leaves are ordinary pytree nodes, so the models' scan-over-layers
and ``dist.specs`` mesh sharding consume them unchanged — on a mesh the
packed values/idx shard exactly like the dense weight they replace.
Kernel selection mirrors the rest of the repo: ``"auto"`` is Pallas on
TPU and the take-along-columns jnp path elsewhere (the Pallas kernels
run under interpret off-TPU when forced).

``bench_rows`` emits the ``BENCH_serve.json`` rows the launcher writes:
separate prefill and decode rows per format (dense vs masked-dense vs
packed), each tagged with the kernel the trace actually lowered
(``kernel_used``) so a jnp path shows up in the perf trajectory instead
of hiding inside an aggregate tok/s.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.core import packed as packed_lib
from repro.dist import specs as specs_lib
from repro.kernels import spmm
from repro.models import ModelApi, common
from repro.serve import sampling as sampling_lib

FORMATS = ("dense", "masked", "nm24", "gathered")


def next_pow2(n: int) -> int:
    """Smallest power of two >= n (shape bucketing for jit stability)."""
    return 1 << max(int(n) - 1, 0).bit_length()


@dataclasses.dataclass
class ServeResult:
    """One timed generate() call."""

    tokens: jnp.ndarray        # (B, n_new) int32
    prefill_s: float
    decode_s: float
    n_new: int
    batch: int

    @property
    def tok_s(self) -> float:
        """Decode throughput (the serving steady state).

        With a single generated token there are zero decode steps, so
        fall back to end-to-end throughput instead of dividing the one
        prefill-produced token by an empty loop's microseconds.
        """
        steps = self.n_new - 1
        if steps <= 0:
            return self.batch * self.n_new / max(
                self.prefill_s + self.decode_s, 1e-9)
        return self.batch * steps / max(self.decode_s, 1e-9)


class ServeEngine:
    """Pack once at startup, then serve batched prefill/decode.

    Args:
        api/params: the model to serve (dense weights).
        masks: mask source for the sparse formats — a masks pytree, a
            ``PruneReport``, or a checkpoint directory (executor
            ``groups/``, a masks-tree checkpoint, or a launcher
            ``--out-dir`` root; see ``core.packed.load_mask_tree``).
            Required for ``masked``/``nm24``/``gathered``.
        fmt: one of ``FORMATS``.
        kernel: spmm kernel for packed formats ("auto"/"pallas"/"jnp").
        mesh: optional ``jax.sharding.Mesh`` — weights (packed or not)
            are placed with ``dist.specs.param_pspecs``-style sharding
            and the model's logical-axis rules are activated around
            every call.
    """

    def __init__(self, api: ModelApi, params: dict, *, masks=None,
                 fmt: str = "masked", kernel: str = "auto", mesh=None):
        if fmt not in FORMATS:
            raise ValueError(f"unknown serve format {fmt!r} "
                             f"(want one of {FORMATS})")
        self.api = api
        self.cfg = api.cfg
        self.fmt = fmt
        self.kernel = kernel
        self.mesh = mesh
        if fmt == "dense":
            masks = None           # baseline: original weights, no masks
        else:
            masks, params = self._resolve_masks(params, masks)
            if masks is None:
                raise ValueError(f"format {fmt!r} needs masks "
                                 "(tree, PruneReport, or checkpoint dir)")

        t0 = time.time()
        if fmt in ("nm24", "gathered"):
            self.params = packed_lib.pack_tree(self.cfg, params, masks, fmt)
            self.masks = None
        else:
            self.params = params
            self.masks = masks if fmt == "masked" else None
        self.pack_s = time.time() - t0
        self._policy = common.PackedMatmulPolicy(kernel, mesh=mesh)
        self._steps = None              # (prefill, decode) jits, built once
        self._scans: dict = {}          # (n_steps, want_logits, sampled) -> jit
        self._fns: dict = {}            # scheduler-facing compiled fns
        # per-phase kernel actually lowered at trace time ("dense" for the
        # unpacked formats, else "jnp" / "pallas")
        self.kernel_used: dict = {}
        # fault-injection seam: called as hook(phase) inside the timed
        # dispatch region of every scheduler-facing entry point, so an
        # injected slow step lands in the measured lane time exactly
        # like a real straggler (serve.faultinject)
        self.dispatch_hook = None

        if mesh is not None:
            pspecs = specs_lib.param_pspecs(self.cfg, self.params, mesh)
            self.params = jax.device_put(
                self.params, specs_lib.named(mesh, pspecs))
            if self.masks is not None:
                mspecs = specs_lib.param_pspecs(self.cfg, self.masks, mesh)
                self.masks = jax.device_put(
                    self.masks, specs_lib.named(mesh, mspecs))

    def _resolve_masks(self, params, masks):
        """-> (masks tree | None, params) — a checkpoint source may also
        carry updated weights (sparsegpt), a report always does."""
        if masks is None or isinstance(masks, dict):
            return masks, params
        if isinstance(masks, (str, Path)):
            return packed_lib.load_masks_and_weights(self.cfg, params, masks)
        if hasattr(masks, "masks"):           # PruneReport
            if getattr(masks, "updated_params", None) is not None:
                params = masks.updated_params
            return masks.masks, params
        raise TypeError(f"cannot interpret masks source {type(masks)!r}")

    @classmethod
    def from_executor_ckpt(cls, api: ModelApi, params: dict,
                           ckpt_dir: str | Path, **kw) -> "ServeEngine":
        """Serve the masks a (possibly still-running) executor published."""
        return cls(api, params, masks=ckpt_dir, **kw)

    # -- accounting ---------------------------------------------------------

    def weight_bytes(self) -> int:
        """Resident weight bytes this engine serves from (masks included:
        the masked-dense path genuinely keeps them in memory)."""
        total = packed_lib.packed_bytes(self.params)
        if self.masks is not None:
            total += sum(int(l.nbytes) for l in jax.tree.leaves(self.masks))
        return total

    # -- serving ------------------------------------------------------------

    def _ctx(self):
        if self.mesh is None:
            return contextlib.nullcontext()
        from repro.launch import mesh as mesh_lib
        return mesh_lib.activate(self.mesh, self.cfg)

    def _serve_steps(self):
        if self._steps is None:
            from repro.train import steps as steps_lib
            self._steps = steps_lib.make_serve_steps(self.api,
                                                     masks=self.masks)
        return self._steps

    def _decode_scan(self, n_steps: int, want_logits: bool,
                     sampled: bool = False):
        """One jitted ``lax.scan`` over the whole decode loop.

        A Python decode loop pays one dispatch (pytree flatten + device
        round-trip) per token; at serving batch sizes that fixed cost
        swamps the per-step matmul work and buries the packed-kernel
        advantage in noise. Scanning the step in-graph makes decode a
        single dispatch for all ``n_steps`` tokens — what the timed
        phase should measure. Compiled once per (n_steps, want_logits,
        sampled) and cached on the engine like the prefill/decode jits;
        the greedy graph stays pure argmax (no sort in the timed phase),
        the sampled graph takes the per-row knobs as traced (B,) arrays
        so changing temperature/seed never recompiles.
        """
        key = (n_steps, want_logits, sampled)
        if key not in self._scans:
            api = self.api      # the jit closes over api, never self
            def run(params, masks, tok0, cache, samp):
                def step(carry, _):
                    tok, cache = carry
                    logits, cache = api.decode_step(
                        params, tok[:, None], cache, masks=masks)
                    if sampled:
                        # post-step cache.t IS the absolute position of
                        # the token being sampled (the PRNG key index)
                        nxt = sampling_lib.sample_tokens(
                            logits[:, -1], samp["temp"], samp["top_p"],
                            samp["top_k"], samp["seed"], cache.t)
                    else:
                        nxt = jnp.argmax(logits[:, -1],
                                         axis=-1).astype(jnp.int32)
                    out = (nxt, logits[:, -1].astype(jnp.float32)) \
                        if want_logits else nxt
                    return (nxt, cache), out

                (_, cache), ys = jax.lax.scan(step, (tok0, cache), None,
                                              length=n_steps)
                return ys

            self._scans[key] = jax.jit(run)
        return self._scans[key]

    def _greedy_loop(self, prompt: dict, n_new: int, *,
                     want_logits: bool = False, sampling=None):
        """The one prefill → sample → decode loop both surfaces consume.

        The active ``MatmulPolicy`` is installed around the traced calls,
        so packed leaves lower through the spmm kernels inside the same
        jitted prefill/decode programs the dense path uses. Returns
        (tokens (B, n_new), last-step logits (n_new, B, V) fp32 or None,
        prefill_s, decode_s). The logits trace is only accumulated when
        asked — the casts/stack must not sit inside timed decode.

        The cache is sized to the pow2 bucket of ``S + n_new`` (extra
        slots carry pos = -1 and are masked out of every score), so the
        decode scan compiles once per (bucket, n_new) instead of once
        per exact (prompt_len, n_new) pair.

        ``sampling`` is None for greedy, else a ``SamplingParams`` (or
        one per batch row); the token at absolute position p draws from
        ``fold_in(key(seed), p)`` — the same key the continuous
        scheduler uses, so a request replays identically on both paths.
        """
        B, S = prompt["tokens"].shape
        samp = None
        if sampling is not None:
            per_row = sampling if isinstance(sampling, (list, tuple)) \
                else [sampling] * B
            samp = sampling_lib.params_arrays(list(per_row))
        with self._ctx(), common.use_matmul_policy(self._policy):
            if self.mesh is not None:
                prompt = jax.device_put(prompt, specs_lib.named(
                    self.mesh, specs_lib.batch_pspecs(self.cfg, prompt,
                                                      self.mesh)))
            cache = self.api.init_cache(self.params, B,
                                        next_pow2(S + n_new))
            prefill, _ = self._serve_steps()
            t0 = time.time()
            # dispatch decisions are trace-time constants, so the records
            # only materialize on the cold (tracing) call of each jit —
            # warm calls leave the log empty and keep the noted value.
            with spmm.record_dispatch() as rec_p:
                logits0, cache = prefill(self.params, prompt, cache)
            if samp is None:
                tok0 = jnp.argmax(logits0[:, -1], axis=-1).astype(jnp.int32)
            else:
                tok0 = sampling_lib.sample_tokens(
                    logits0[:, -1], samp["temp"], samp["top_p"],
                    samp["top_k"], samp["seed"], jnp.int32(S))
            jax.block_until_ready(tok0)
            t1 = time.time()
            rec_d: list = []
            trace = None
            if n_new > 1:
                # the whole decode loop is ONE scanned dispatch — the
                # timed phase measures graph cost, not n_new-1 python
                # round-trips (see _decode_scan)
                run = self._decode_scan(n_new - 1, want_logits,
                                        samp is not None)
                with spmm.record_dispatch() as rec_d:
                    ys = run(self.params, self.masks, tok0, cache, samp)
                toks, logit_steps = ys if want_logits else (ys, None)
                out = jnp.concatenate([tok0[:, None], toks.T], axis=1)
            else:
                out, logit_steps = tok0[:, None], None
            jax.block_until_ready(out)
            t2 = time.time()
        self._note_kernels("prefill", rec_p)
        self._note_kernels("decode", rec_d)
        if want_logits:
            first = logits0[:, -1].astype(jnp.float32)[None]
            trace = first if logit_steps is None else \
                jnp.concatenate([first, logit_steps], axis=0)
        return out, trace, t1 - t0, t2 - t1

    def _note_kernels(self, phase: str, rec: list) -> None:
        if rec:
            self.kernel_used[phase] = _kernel_summary(rec)
        elif phase not in self.kernel_used:
            # no spmm dispatches traced: dense/masked serve plain matmuls
            self.kernel_used[phase] = "dense"

    def generate(self, prompt: dict, n_new: int, *,
                 sampling=None) -> ServeResult:
        """Batched prefill + ``n_new`` decode steps, timed.

        ``sampling=None`` decodes greedily (the historical behaviour);
        a ``SamplingParams`` — or a list of one per batch row — samples
        with per-request seeds (see ``serve.sampling``).
        """
        tokens, _, prefill_s, decode_s = self._greedy_loop(
            prompt, n_new, sampling=sampling)
        return ServeResult(tokens=tokens, prefill_s=prefill_s,
                           decode_s=decode_s, n_new=n_new,
                           batch=tokens.shape[0])

    def logits_trace(self, prompt: dict, n_new: int) -> jnp.ndarray:
        """(n_new, B, vocab) greedy logits — the parity-test surface."""
        return self._greedy_loop(prompt, n_new, want_logits=True)[1]

    # -- continuous-batching step fns (consumed by serve.scheduler) ---------

    @property
    def supports_continuous(self) -> bool:
        """Continuous batching needs the plain decoder-only KV layout:
        per-token pages and a per-row decode clock. Recurrent families
        (rwkv, zamba) carry state, not per-token KV; cross-attn caches
        (VLM) and encoder-decoder models add a second, unpaged cache."""
        from repro.models import transformer
        return (self.api.module is transformer
                and not getattr(self.cfg, "cross_attn_every", 0))

    def _require_continuous(self):
        if not self.supports_continuous:
            raise NotImplementedError(
                f"continuous batching supports plain decoder-only "
                f"transformers; {self.cfg.name!r} is not one")

    def prefill_session(self, tokens: jnp.ndarray, n_valid: int, samp: dict):
        """Prefill ONE session from a right-padded prompt row.

        ``tokens`` is (1, S_bucket) int32 with the real prompt in the
        first ``n_valid`` positions; ``samp`` holds (1,) sampling arrays
        (``sampling.params_arrays``). Returns ``(tok0 (1,) int32,
        k (L, S_bucket, kvH, dh), v)`` — the first generated token
        (sampled at PRNG position ``n_valid``) and the dense cache row to
        scatter into pages. Compiled once per S_bucket: ``n_valid`` is a
        traced scalar, so every prompt length in a bucket shares the jit.
        """
        self._require_continuous()
        s_bucket = tokens.shape[1]
        key = ("prefill_session", s_bucket)
        if key not in self._fns:
            api = self.api      # the jit closes over api, never self
            def fn(params, masks, tokens, n_valid, samp):
                cache = api.init_cache(params, 1, s_bucket)
                logits, cache = api.prefill(
                    params, {"tokens": tokens, "n_valid": n_valid}, cache,
                    masks=masks)
                tok0 = sampling_lib.sample_tokens(
                    logits[:, -1], samp["temp"], samp["top_p"],
                    samp["top_k"], samp["seed"], n_valid)
                kv = cache.kv
                return tok0, kv.k[:, 0], kv.v[:, 0]

            self._fns[key] = jax.jit(fn)
        with self._ctx(), common.use_matmul_policy(self._policy):
            if self.dispatch_hook is not None:
                self.dispatch_hook("prefill")
            with spmm.record_dispatch() as rec:
                out = self._fns[key](self.params, self.masks, tokens,
                                     jnp.int32(n_valid), samp)
            jax.block_until_ready(out[0])
        self._note_kernels("prefill", rec)
        return out

    def prefill_chunk(self, tokens: jnp.ndarray, offset: int, n_valid: int,
                      cache, samp: dict):
        """One fixed-width window of a chunked prefill (B=1).

        ``tokens`` is (1, W) int32 — the prompt slice at absolute
        positions ``[offset, offset + W)`` (the final window right-pads
        past ``n_valid``); ``cache`` is the session's continuation cache
        (B=1, capacity = the prompt's pow2 bucket) holding the previous
        windows' KV. Returns ``(tok0 (1,) int32, cache')`` — the token
        sampled from the last real position seen so far (only the FINAL
        window's ``tok0`` is the request's first token; earlier windows'
        are a one-row lm_head by-product the scheduler ignores).

        Compiled once per (W, capacity): ``offset`` and ``n_valid`` are
        traced scalars, so every window of every prompt in a bucket
        shares the jit, and the cache buffers are donated between
        windows. Driving ⌈S/W⌉ windows is bitwise-identical to one
        ``prefill_session`` call over the same bucket — same per-row
        reduction lengths, masked slots contribute exact zeros (see
        ``models.attention.window_attention``).
        """
        self._require_continuous()
        if self.api.prefill_window is None:
            raise NotImplementedError(
                f"{self.cfg.name!r} has no windowed-prefill continuation")
        w = tokens.shape[1]
        capacity = cache.kv.k.shape[2]
        key = ("prefill_chunk", w, capacity)
        if key not in self._fns:
            api = self.api      # the jit closes over api, never self
            def fn(params, masks, tokens, offset, n_valid, cache, samp):
                logits, cache = api.prefill_window(
                    params, {"tokens": tokens, "offset": offset,
                             "n_valid": n_valid}, cache, masks=masks)
                tok0 = sampling_lib.sample_tokens(
                    logits[:, -1], samp["temp"], samp["top_p"],
                    samp["top_k"], samp["seed"], n_valid)
                return tok0, cache

            self._fns[key] = jax.jit(fn, donate_argnums=5)
        with self._ctx(), common.use_matmul_policy(self._policy):
            if self.dispatch_hook is not None:
                self.dispatch_hook("prefill")
            with spmm.record_dispatch() as rec:
                tok0, cache = self._fns[key](
                    self.params, self.masks, tokens, jnp.int32(offset),
                    jnp.int32(n_valid), cache, samp)
            jax.block_until_ready(tok0)
        self._note_kernels("prefill", rec)
        return tok0, cache

    def decode_chunk(self, tok: jnp.ndarray, cache, active: jnp.ndarray,
                     samp: dict, *, n_steps: int, bucket: int):
        """Run ``n_steps`` decode steps on rows ``[:bucket]`` of a
        full-width working cache; rows beyond the bucket pass through
        untouched.

        ``tok`` (B,) holds each slot's last token, ``active`` (B,) bool
        masks live slots — inactive rows hold their token and FREEZE
        their clock ``t`` (their in-step KV write lands in the slack
        region past their session length, where the contiguity contract
        already says garbage lives, so nothing real is harmed). Returns
        ``(toks (n_steps, bucket), cache')``. Compiled once per
        (n_steps, bucket) — the slice/write-back lives in-graph so the
        whole chunk stays one dispatch, and the cache buffers are
        donated.
        """
        self._require_continuous()
        from repro.models import attention as attn
        from repro.models.transformer import DecodeCache
        key = ("chunk", n_steps, bucket)
        if key not in self._fns:
            api = self.api      # the jit closes over api, never self
            def fn(params, masks, tok, cache, active, samp):
                kv = cache.kv
                sub = DecodeCache(
                    kv=attn.KVCache(kv.k[:, :bucket], kv.v[:, :bucket],
                                    kv.pos[:, :bucket], kv.rolling),
                    cross_kv=None, t=cache.t[:bucket])
                act = active[:bucket]

                def step(carry, _):
                    tk, c = carry
                    logits, c2 = api.decode_step(
                        params, tk[:, None], c, masks=masks)
                    nxt = sampling_lib.sample_tokens(
                        logits[:, -1], samp["temp"][:bucket],
                        samp["top_p"][:bucket], samp["top_k"][:bucket],
                        samp["seed"][:bucket], c2.t)
                    nxt = jnp.where(act, nxt, tk)
                    c2 = c2._replace(t=jnp.where(act, c2.t, c.t))
                    return (nxt, c2), nxt

                (_, sub), toks = jax.lax.scan(
                    step, (tok[:bucket], sub), None, length=n_steps)
                kv2 = sub.kv
                kv_out = attn.KVCache(
                    kv.k.at[:, :bucket].set(kv2.k),
                    kv.v.at[:, :bucket].set(kv2.v),
                    kv.pos.at[:, :bucket].set(kv2.pos), kv.rolling)
                return toks, DecodeCache(
                    kv=kv_out, cross_kv=None,
                    t=cache.t.at[:bucket].set(sub.t))

            self._fns[key] = jax.jit(fn, donate_argnums=3)
        with self._ctx(), common.use_matmul_policy(self._policy):
            if self.dispatch_hook is not None:
                self.dispatch_hook("decode")
            with spmm.record_dispatch() as rec:
                toks, cache = self._fns[key](self.params, self.masks, tok,
                                             cache, active, samp)
            jax.block_until_ready(toks)
        self._note_kernels("decode", rec)
        return toks, cache

    def compiled_fn_keys(self) -> list:
        """Keys of the scheduler-facing compiled fns (jit-churn tests)."""
        return sorted(self._fns, key=repr)


def kernel_summary(rec: list) -> str:
    """Collapse trace-time dispatch records into one bench-row tag."""
    return "+".join(sorted({r["kernel"] for r in rec}))


_kernel_summary = kernel_summary


def bench_rows(api: ModelApi, params: dict, masks, prompt: dict,
               n_new: int, *, formats=("dense", "masked", "nm24"),
               kernel: str = "auto", mesh=None, repeats: int = 3,
               masked_params: dict | None = None) -> list:
    """Dense vs masked-dense vs packed serving rows for BENCH_serve.json.

    Each format contributes TWO rows — ``phase == "prefill"`` and
    ``phase == "decode"`` — so the prefill gap is tracked directly
    instead of inferred from aggregate tok/s. Shared keys: ``variant``,
    ``kernel`` (requested), ``kernel_used`` (what the trace actually
    lowered, per phase — a jnp path is visible here), ``tok_s`` (best
    warm repeat), ``weight_bytes``, ``pack_s``. Prefill rows add
    ``prefill_s`` (best warm, tok_s = batch · prompt_len / prefill_s);
    decode rows add ``cold_tok_s`` (first call, pays compilation).
    ``masked_params`` are the weights the masks belong to when they
    differ from the dense baseline (sparsegpt updates); the dense row
    always serves ``params``.
    """
    B, S = prompt["tokens"].shape
    engines, cold = {}, {}
    for fmt in formats:
        p = params if fmt == "dense" or masked_params is None \
            else masked_params
        engines[fmt] = ServeEngine(api, p, masks=masks if fmt != "dense"
                                   else None, fmt=fmt, kernel=kernel,
                                   mesh=mesh)
        # compile (and record dispatch) up front
        cold[fmt] = engines[fmt].generate(prompt, n_new)
    # interleave the timed repeats round-robin across engines so clock
    # drift (turbo ramp, background load) biases no single variant —
    # serial per-variant timing systematically favors whichever runs
    # last on a warming machine
    warm: dict = {fmt: [] for fmt in formats}
    for _ in range(repeats):
        for fmt in formats:
            warm[fmt].append(engines[fmt].generate(prompt, n_new))
    rows = []
    for fmt in formats:
        eng = engines[fmt]
        results = [cold[fmt], *warm[fmt]]
        base = {
            "variant": fmt,
            "kernel": kernel if fmt in ("nm24", "gathered") else "dense",
            "weight_bytes": eng.weight_bytes(),
            "pack_s": eng.pack_s,
        }
        prefill_s = min(r.prefill_s for r in results[1:])
        rows.append({
            **base, "phase": "prefill",
            "kernel_used": eng.kernel_used.get("prefill", "dense"),
            "prefill_s": prefill_s,
            "tok_s": B * S / max(prefill_s, 1e-9),
        })
        rows.append({
            **base, "phase": "decode",
            "kernel_used": eng.kernel_used.get("decode", "dense"),
            "cold_tok_s": results[0].tok_s,
            "tok_s": max(r.tok_s for r in results[1:]),
        })
    return rows
