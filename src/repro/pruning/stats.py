"""Streaming, recipe-aware, mesh-sharded calibration statistics.

The refinement needs only G = XXᵀ "accumulated on-the-fly as calibration
samples pass through the layer" (paper §2.1.2) — and different methods
need different statistics: sparseswaps/sparsegpt the full Gram, Wanda/RIA
warmstarts just its diagonal, DSnoT only feature means/variances. This
module plans, accumulates, shards and checkpoints exactly that state:

* ``CalibSpec`` — derived from a resolved plan: per tap, which level of
  statistics to accumulate ("gram" | "moments" | "none"). Skip-rule sites
  accumulate nothing, so tap memory scales with the sites actually
  pruned; dsnot-only sites pay O(d) instead of O(d²).
* ``CalibStats`` — the accumulated state: the model-structured tap tree
  (raw additive moments, fp32, device-resident), convertible per tap to
  ``core.gram.GramState``.
* ``accumulate_stats`` — the donated-carry loop ``state = step(params,
  state, batch)``: the whole tap tree is a single jitted add with the
  carry donated, replacing the per-batch device→host roundtrip of the
  legacy ``jax.tree.map(jnp.add)`` host sum. With ``mesh=``, batches
  shard along the data axis via ``dist.specs`` and per-device partial
  statistics merge through ``core.gram.psum_gram`` inside a
  ``shard_map``; the carried accumulator itself is stored with shardings
  from ``dist.specs.calib_pspecs`` (Gram columns over "model").
* checkpoint/resume through ``repro.ckpt``, keyed by the spec fingerprint
  so a resumed job never mixes statistics from a different recipe.

The statistic *computation* stays in the model code — ``models/common``'s
``TapPolicy`` hook — so the same forward serves the legacy dict path and
this one. ``kernel="pallas"`` routes Gram contributions through the
Pallas ``kernels.ops.gram_xtx`` (interpret fallback off-TPU);
``kernel="auto"`` selects it on TPU only.

Known coarseness: policies key on the *emitted* tap name, which is the
bare projection name — a recipe skipping ``enc_layers.attn.wq`` but
keeping ``dec_layers.attn.wq`` accumulates both (same emission name
"wq"); levels union over same-named taps. This only ever
over-accumulates, never under.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import warnings
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import ckpt
from repro.core import gram as gram_lib
from repro.dist import specs as specs_lib
from repro.models import ModelApi
from repro.models import common as common_lib

from . import sites as sites_lib

LEVELS = ("none", "moments", "gram")
_RANK = {lvl: i for i, lvl in enumerate(LEVELS)}
_FIELDS = {"none": (), "moments": ("d", "s", "n"), "gram": ("g", "s", "n")}


def required_level(rule) -> str:
    """The statistics a resolved site rule needs.

    * skip            -> nothing;
    * dsnot           -> feature moments (mean/variance from d/s/n; the
                         Wanda/RIA warmstart norms come from the same
                         diagonal). Row losses are then reported via the
                         diagonal (Jensen) proxy — see engine;
    * everything else -> the full Gram (exact row objective, swaps, OBS).
    """
    if rule.skip:
        return "none"
    if rule.method == "dsnot":
        return "moments"
    return "gram"


def _max_level(a: str, b: str) -> str:
    return a if _RANK[a] >= _RANK[b] else b


# ---------------------------------------------------------------------------
# spec
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class CalibSpec:
    """Which statistics calibration accumulates, per emitted tap name.

    ``levels`` maps every tap the model emits to a statistics level;
    omitted taps default to "none" (never emitted). ``kernel`` selects
    the Gram contraction: "auto" (Pallas on TPU, plain jnp elsewhere),
    "pallas" (forced, interpret off-TPU — tests), "jnp" (forced plain).
    """

    levels: tuple[tuple[str, str], ...]
    kernel: str = "auto"

    def __post_init__(self):
        if self.kernel not in ("auto", "pallas", "jnp"):
            raise ValueError(f"unknown kernel {self.kernel!r}")
        bad = [l for _, l in self.levels if l not in LEVELS]
        if bad:
            raise ValueError(f"unknown levels {bad}; have {LEVELS}")
        object.__setattr__(self, "levels",
                           tuple(sorted(dict(self.levels).items())))

    # -- construction -------------------------------------------------------

    @classmethod
    def full(cls, cfg, *, kernel: str = "auto") -> "CalibSpec":
        """Every tap at gram level — the legacy ``accumulate`` contract."""
        names = {sites_lib._emission_name(tpath)
                 for _, _, tpath, _ in sites_lib._table(cfg)}
        return cls(levels=tuple((n, "gram") for n in sorted(names)),
                   kernel=kernel)

    @classmethod
    def from_plan(cls, cfg, plan, *, minimal: bool = True,
                  kernel: str = "auto") -> "CalibSpec":
        """Derive the per-tap levels a resolved ``PrunePlan`` needs.

        Per tap: the max level over every site group it feeds (and over
        every tap sharing its emission name). ``minimal=False`` promotes
        all non-skipped taps to gram level — skip-aware memory savings
        with bit-compatible refinement reports (dsnot keeps its exact
        row-loss accounting); ``minimal=True`` additionally drops
        dsnot-only taps to moments level.
        """
        by_site = {g.spec.name: required_level(g.rule) for g in plan.groups}
        if not minimal:
            by_site = {k: ("none" if v == "none" else "gram")
                       for k, v in by_site.items()}
        levels: dict[str, str] = {}
        taps = sites_lib.tap_specs(cfg, [g.spec for g in plan.groups])
        for tap in taps:
            lvl = "none"
            for site in tap.sites:
                lvl = _max_level(lvl, by_site.get(site, "none"))
            levels[tap.name] = _max_level(levels.get(tap.name, "none"), lvl)
        return cls(levels=tuple(levels.items()), kernel=kernel)

    # -- queries ------------------------------------------------------------

    def level(self, name: str) -> str:
        return dict(self.levels).get(name, "none")

    def covers(self, other: "CalibSpec") -> bool:
        """True when stats under this spec satisfy ``other``'s needs."""
        mine = dict(self.levels)
        return all(_RANK[mine.get(n, "none")] >= _RANK[lvl]
                   for n, lvl in other.levels)

    def fingerprint(self) -> str:
        """Content hash for checkpoint keying (kernel choice excluded —
        it changes rounding, not the contract; resume stays valid)."""
        return hashlib.sha256(
            json.dumps(self.levels).encode()).hexdigest()[:16]

    # -- the pluggable accumulator ------------------------------------------

    def policy(self) -> common_lib.TapPolicy:
        """The ``TapPolicy`` models consult while tracing this spec."""
        return _SpecTapPolicy(self)


class _SpecTapPolicy(common_lib.TapPolicy):
    """TapPolicy driven by a CalibSpec: field selection + kernel choice."""

    def __init__(self, spec: CalibSpec):
        self._levels = dict(spec.levels)
        use_pallas = (spec.kernel == "pallas"
                      or (spec.kernel == "auto"
                          and jax.default_backend() == "tpu"))
        self._pallas = use_pallas

    def fields(self, name: str) -> tuple[str, ...]:
        return _FIELDS[self._levels.get(name, "none")]

    def gram(self, x2):
        if not self._pallas:
            return super().gram(x2)
        from repro.kernels import ops as kops
        return kops.gram_xtx(x2, interpret=None)   # interpret off-TPU

    def gram_experts(self, x5):
        if not self._pallas:
            return super().gram_experts(x5)
        from repro.kernels import ops as kops
        # (B, groups, E, cap, d) -> (E, tokens, d): one padded kernel
        # call per expert over that expert's capacity buffer
        return kops.gram_xtx_stacked(
            x5.transpose(2, 0, 1, 3, 4), interpret=None)


# ---------------------------------------------------------------------------
# state
# ---------------------------------------------------------------------------

def _is_entry(node) -> bool:
    return isinstance(node, dict) and "n" in node and not isinstance(
        node["n"], dict)


def _map_entries(tree, fn, path=()):
    """Apply ``fn(path, entry)`` to every {g|d, s, n} entry in a tap tree."""
    if _is_entry(tree):
        return fn(path, tree)
    return {k: _map_entries(v, fn, (*path, k)) for k, v in tree.items()}


@dataclasses.dataclass
class CalibStats:
    """Accumulated calibration statistics (the executor's input).

    ``taps`` is the model-structured tree of raw additive moments —
    exactly what ``calibrate.accumulate`` returns, minus whatever the
    spec skipped (absent keys) or reduced (entries carrying "d" instead
    of "g"). ``batches`` counts calibration batches folded in.
    """

    taps: dict
    spec: CalibSpec
    batches: int = 0

    def tap_bytes(self) -> int:
        """Total accumulator footprint (device bytes, unsharded)."""
        return sum(leaf.size * leaf.dtype.itemsize
                   for leaf in jax.tree.leaves(self.taps))

    def gram_state(self, path: tuple[str, ...]) -> gram_lib.GramState:
        """One tap entry as a ``core.gram.GramState`` (stacked dims kept)."""
        ent = self.taps
        for k in path:
            ent = ent[k]
        g = ent["g"] if "g" in ent else ent["d"]
        return gram_lib.state_from_moments(g, ent["s"], ent["n"])


# ---------------------------------------------------------------------------
# steps
# ---------------------------------------------------------------------------

def make_tap_step(api: ModelApi, spec: CalibSpec):
    """jit'd (params, batch) -> one batch's tap tree under ``spec``."""
    policy = spec.policy()

    @jax.jit
    def step(params, batch):
        with common_lib.use_tap_policy(policy):
            _, aux = api.loss(params, batch, masks=None, want_taps=True)
        return aux["taps"]

    return step


def make_carry_step(api: ModelApi, spec: CalibSpec, *, donate: bool = True,
                    out_shardings=None):
    """jit'd, donated-carry (params, state, batch) -> state.

    The whole ``CalibStats`` tree stays resident on device; donation lets
    XLA update the accumulator buffers in place instead of the legacy
    path's per-batch host-summed tap tree. ``donate=False`` keeps the
    input state alive after the call — for callers that hand the carry to
    user code between steps (the ``calibrate.accumulate`` shim, whose
    ``checkpoint_fn`` may legally retain the tree). ``out_shardings``
    pins the carried state's placement (the model-sharded accumulator on
    meshes whose batches don't data-split).
    """
    policy = spec.policy()

    @partial(jax.jit, donate_argnums=(1,) if donate else (),
             out_shardings=out_shardings)
    def step(params, state, batch):
        with common_lib.use_tap_policy(policy):
            _, aux = api.loss(params, batch, masks=None, want_taps=True)
        return jax.tree.map(jnp.add, state, aux["taps"])

    return step


def _dp_size(mesh: Mesh) -> int:
    dp = specs_lib._dp_axes(mesh.shape)
    n = 1
    for a in dp:
        n *= mesh.shape[a]
    return n


def batch_shardable(batch: dict, mesh: Mesh) -> bool:
    """True iff every batch leaf's leading dim splits over the DP axes
    (and there is more than one data-parallel device to split over)."""
    n = _dp_size(mesh)
    return n > 1 and all(
        leaf.ndim and leaf.shape[0] % n == 0
        for leaf in jax.tree.leaves(batch))


def make_sharded_step(api: ModelApi, spec: CalibSpec, mesh: Mesh,
                      batch: dict, state):
    """Donated-carry step with batches sharded along the data axis.

    Inside a ``shard_map`` over the DP axes each device runs the forward
    on its batch shard, producing *partial* raw moments; the partials are
    bridged to ``core.gram.GramState`` and merged with ``psum_gram``
    (Chan parallel-variance algebra over raw psums), then folded into the
    carried state. Input/accumulator shardings derive from ``dist.specs``
    (``batch_pspecs`` / ``calib_pspecs`` — Gram columns ride the "model"
    axis, everything stays replicated over data).
    """
    policy = spec.policy()
    dp = specs_lib._dp_axes(mesh.shape)
    batch_specs = specs_lib.batch_pspecs(api.cfg, batch, mesh)
    state_specs = specs_lib.calib_pspecs(state, mesh)
    state_shardings = specs_lib.named(mesh, state_specs)

    def local(params, batch_shard):
        with common_lib.use_tap_policy(policy):
            _, aux = api.loss(params, batch_shard, masks=None, want_taps=True)

        def merge(_, ent):
            key = "g" if "g" in ent else "d"
            st = gram_lib.state_from_moments(ent[key], ent["s"], ent["n"])
            st = gram_lib.psum_gram(st, dp)
            g, s, n = gram_lib.moments_from_state(st)
            return {key: g, "s": s, "n": n}

        return _map_entries(aux["taps"], merge)

    local = jax.shard_map(local, mesh=mesh, in_specs=(P(), batch_specs),
                          out_specs=P(), check_vma=False)

    @partial(jax.jit, donate_argnums=(1,), out_shardings=state_shardings)
    def step(params, state, batch):
        return jax.tree.map(jnp.add, state, local(params, batch))

    return step


def init_state(api: ModelApi, spec: CalibSpec, params, batch):
    """Zero accumulator matching the taps the spec emits (eval_shape only)."""
    shapes = jax.eval_shape(make_tap_step(api, spec), params, batch)
    return jax.tree.map(lambda l: jnp.zeros(l.shape, l.dtype), shapes)


# ---------------------------------------------------------------------------
# accumulation driver (+ checkpoint/resume)
# ---------------------------------------------------------------------------

def _calib_target(state):
    return jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype), state)


def _try_resume(ckpt_dir, spec: CalibSpec, state):
    """(start_batch, state) from the newest matching calibration ckpt."""
    step = ckpt.latest_valid(ckpt_dir)
    if step is None:
        return 0, state
    man_path = Path(ckpt_dir) / f"step_{step:08d}" / "MANIFEST.json"
    try:
        man = json.loads(man_path.read_text())
    except (OSError, json.JSONDecodeError):
        return 0, state
    extra = man.get("extra", {})
    if extra.get("calib_spec") != spec.fingerprint():
        return 0, state
    try:
        tree, _ = ckpt.restore(ckpt_dir, step, _calib_target(state))
    except (KeyError, ValueError, OSError):
        return 0, state
    return step, tree


def accumulate_stats(api: ModelApi, params, batches, *,
                     spec: CalibSpec | None = None,
                     mesh: Mesh | None = None,
                     ckpt_dir=None, checkpoint_every: int = 0) -> CalibStats:
    """Stream calibration batches into a ``CalibStats`` accumulator.

    ``mesh``: shard batches along the data axis (see ``make_sharded_step``;
    falls back to the single-device step when the batch doesn't split).
    ``ckpt_dir`` + ``checkpoint_every``: persist the accumulator every k
    batches via ``repro.ckpt`` and resume a matching interrupted run —
    keyed by the spec fingerprint, consistent with the executor's
    group-checkpoint keying (a different recipe recomputes).
    """
    spec = spec if spec is not None else CalibSpec.full(api.cfg)
    it = iter(batches)
    try:
        first = next(it)
    except StopIteration:
        raise ValueError("no calibration batches provided") from None

    state = init_state(api, spec, params, first)
    if mesh is not None:
        # the accumulator always gets its dist.specs shardings on a mesh
        # (Gram columns over "model"); place the zeros up front so every
        # step's donation (including the first) is usable
        state_shardings = specs_lib.named(
            mesh, specs_lib.calib_pspecs(state, mesh))
        state = jax.device_put(state, state_shardings)
        if batch_shardable(first, mesh):
            step = make_sharded_step(api, spec, mesh, first, state)
        else:
            if _dp_size(mesh) > 1:
                # surfaced, not silent: data parallelism was available
                # but the batch doesn't split over it — same policy as
                # the executor's single-device-group warning
                warnings.warn(
                    "calibration batches not sharded: leading dims do "
                    "not divide the data-parallel axes "
                    f"({dict(mesh.shape)}); accumulating each batch "
                    "whole")
            step = make_carry_step(api, spec, out_shardings=state_shardings)
    else:
        step = make_carry_step(api, spec)

    start = 0
    if ckpt_dir is not None:
        start, state = _try_resume(ckpt_dir, spec, state)

    def replay():
        yield first
        yield from it

    done = start
    for i, batch in enumerate(replay()):
        if i < start:
            continue
        state = step(params, state, batch)
        done = i + 1
        if (ckpt_dir is not None and checkpoint_every
                and done % checkpoint_every == 0):
            ckpt.save(ckpt_dir, done, state,
                      extra={"calib_spec": spec.fingerprint()})
            ckpt.gc(ckpt_dir, keep=1)
    return CalibStats(taps=state, spec=spec, batches=done)
