"""Group-batched refinement engine: one jit per SiteGroup, not per matrix.

The paper's refiners are row-parallel, so all N instances of a logical
site (N layers, N experts, ...) batch into ONE vmapped, jit-compiled call
over stacked ``(N, d_out, d_in)`` weights and ``(N, d_in, d_in)`` Grams —
the hot path ``prune_model`` drives. Methods plug in through a small
registry protocol::

    @register("sparseswaps")
    def _refine_sparseswaps(W, gram, pattern, ctx) -> GroupResult: ...

where ``W`` is the stacked weight block, ``gram`` a ``sites.GramBatch``,
and ``ctx`` the immutable per-run knobs (warmstart criterion, t_max, mesh,
...). Every refiner returns per-row losses so reports stay per-instance.

Mesh dispatch (``ctx.mesh``): the sparseswaps refiner routes each instance
through ``distributed.refine_rows_sharded`` (rows over every mesh axis, G
replicated; weights row-padded to the device count and sliced back).
Unstructured sites whose Gram exceeds ``ctx.gram_budget_bytes`` — the
replication budget from ``pruning.distributed`` (granite-34b down-proj:
d_in=24576 is a 2.4 GB fp32 Gram) — fall back to the column-sharded
``refine_g_sharded`` scheme. Both sharded paths match the single-device
chunked search bit-exactly (same deterministic tie-break).

``refine_instance`` / ``refine_group_reference`` keep the original
per-instance Python loop alive as the reference the batched engine is
tested against (bit-identical masks on a fixed seed).
"""
from __future__ import annotations

import dataclasses
import warnings
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.core import masks as masks_lib
from repro.core import sparseswaps
from repro.core import swap_math as sm
from repro.core.dsnot import _dsnot_rows, dsnot as _dsnot
from repro.core.sparsegpt import sparsegpt as _sparsegpt
from repro.core.warmstart import warmstart_mask

from . import distributed
from . import sites as sites_lib

# G replicated per device while refining rows: cap at 1 GiB fp32 by default
# (the refine_rows_sharded regime bound from pruning.distributed).
DEFAULT_GRAM_BUDGET = 1 << 30


@dataclasses.dataclass(frozen=True)
class RefineContext:
    """Immutable per-run knobs every refiner sees (hashable: jit-static).

    ``k_swaps``: candidate swaps committed per search pass (None = auto,
    resolved by ``sparseswaps._pick_k`` — currently 8). ``t_max`` bounds
    search PASSES, so the swap budget is ``t_max · k_swaps``; every pass
    stays exactly monotone and convergence is still certified by the
    1-swap argmin (see ``core.sparseswaps``). ``compact_every``: gather
    converged rows out of the working set every S passes (None/0 = off;
    single-host engine path only — the sharded refiners keep static
    shapes for SPMD).
    """

    warmstart: str = "wanda"
    t_max: int = 100
    eps: float = 0.0
    swap_method: str = "auto"
    chunk: int = 512
    row_block: int | None = None
    mesh: Mesh | None = None
    gram_budget_bytes: int = DEFAULT_GRAM_BUDGET
    k_swaps: int | None = None
    compact_every: int | None = None

    def with_overrides(self, **overrides) -> "RefineContext":
        """Per-group context: replace only the knobs a recipe rule sets.

        ``None`` values mean "inherit" — a rule that only pins ``t_max``
        leaves warmstart/eps/... at the run-wide defaults, so the executor
        builds one context per planned group from one base context.
        """
        kept = {k: v for k, v in overrides.items() if v is not None}
        return dataclasses.replace(self, **kept) if kept else self


@dataclasses.dataclass
class GroupResult:
    """Batched refinement output for one SiteGroup."""

    masks: jnp.ndarray                # (N, d_out, d_in)
    loss_init: jnp.ndarray            # (N, d_out) exact row loss, warmstart
    loss_final: jnp.ndarray           # (N, d_out) after refinement
    swaps: jnp.ndarray                # (N, d_out) accepted swaps per row
    new_weights: jnp.ndarray | None = None   # (N, d_out, d_in), sparsegpt


REFINERS: dict = {}


def register(name: str):
    """Register a group refiner under a method name."""

    def deco(fn):
        REFINERS[name] = fn
        return fn

    return deco


def refine_group(method: str, group: sites_lib.SiteGroup,
                 pattern: masks_lib.Pattern, ctx: RefineContext) -> GroupResult:
    """Refine every instance of ``group`` in one batched call."""
    if method not in REFINERS:
        raise ValueError(f"unknown method {method!r}; have {sorted(REFINERS)}")
    if group.gram.G is None and method != "dsnot":
        raise ValueError(
            f"method {method!r} needs full Gram statistics but group "
            f"{group.name!r} was calibrated at moments level — rebuild the "
            f"CalibSpec from the current plan (pruning.stats)")
    return REFINERS[method](group.weights, group.gram, pattern, ctx)


# ---------------------------------------------------------------------------
# batched building blocks
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("pattern", "criterion"))
def _warmstart_batch(W, G, pattern, criterion):
    """(N, R, d) stacked warmstart masks."""
    return jax.vmap(
        lambda w, g: warmstart_mask(w, g, pattern, criterion=criterion)
    )(W.astype(jnp.float32), G)


@jax.jit
def _row_loss_batch(W, M, G):
    return jax.vmap(sm.row_loss)(W.astype(jnp.float32), M, G)


@jax.jit
def _row_loss_diag_batch(W, M, diag):
    """Diagonal (Jensen) proxy of the row loss: Σ_j c_j² G_jj.

    Used when only moments-level statistics exist (dsnot under a minimal
    ``CalibSpec``): exact for uncorrelated features, an upper bound
    otherwise — reported losses are then proxies, not the exact quadratic
    objective.
    """
    C = W.astype(jnp.float32) * (1.0 - M)
    return jnp.sum(C * C * diag.astype(jnp.float32)[:, None, :], axis=-1)


def _no_swaps(W):
    return jnp.zeros(W.shape[:2], jnp.int32)


# ---------------------------------------------------------------------------
# methods
# ---------------------------------------------------------------------------

@register("none")
def _refine_none(W, gram, pattern, ctx):
    """Warmstart mask only (= Wanda / RIA / magnitude baselines)."""
    m0 = _warmstart_batch(W, gram.G, pattern, ctx.warmstart)
    l0 = _row_loss_batch(W, m0, gram.G)
    return GroupResult(masks=m0, loss_init=l0, loss_final=l0,
                       swaps=_no_swaps(W))


@register("sparseswaps")
def _refine_sparseswaps(W, gram, pattern, ctx):
    """The paper's swap refinement (k-swap), vmapped over instances
    (or sharded via the mesh dispatch below)."""
    if ctx.mesh is not None:
        return _refine_sparseswaps_sharded(W, gram, pattern, ctx)
    N, R, d = W.shape
    m0 = _warmstart_batch(W, gram.G, pattern, ctx.warmstart)
    # auto budgets against the FULL stacked block (all N instances live in
    # one call here); row_block bounds it, as in the per-instance reference
    rb = ctx.row_block or R
    meth = sparseswaps._pick_method(ctx.swap_method, d, N * rb)
    block = pattern.block(d)
    k = sparseswaps._pick_k(ctx.k_swaps, d, block)

    if ctx.compact_every:
        m, l0, l1, swaps, _ = sparseswaps.refine_stacked_compacted(
            W.astype(jnp.float32), m0, gram.G.astype(jnp.float32),
            t_max=ctx.t_max, eps=ctx.eps, method=meth, block=block,
            chunk=ctx.chunk, k_swaps=k, compact_every=ctx.compact_every,
            row_block=ctx.row_block)
        return GroupResult(masks=m, loss_init=l0, loss_final=l1, swaps=swaps)

    run = jax.vmap(
        lambda w, m_, g: sparseswaps._refine_block(
            w, m_, g, t_max=ctx.t_max, eps=ctx.eps, method=meth, block=block,
            chunk=ctx.chunk, track_history=False, k_swaps=k))
    # pad the trailing partial block to ``rb`` rows (zero weights under a
    # keep-all mask: never a feasible candidate) so every block hits one
    # jit cache entry; results are sliced back to the true rows
    pad = (-R) % rb
    W32 = W.astype(jnp.float32)
    if pad:
        W32 = jnp.pad(W32, ((0, 0), (0, pad), (0, 0)))
        m0 = jnp.pad(m0, ((0, 0), (0, pad), (0, 0)), constant_values=1.0)
    outs = []
    for lo in range(0, W32.shape[1], rb):
        out = run(W32[:, lo:lo + rb], m0[:, lo:lo + rb], gram.G)
        sparseswaps.record_search_passes(jnp.max(out[4]), N * rb)
        outs.append(out)
    cat = lambda i: jnp.concatenate([o[i] for o in outs], axis=1)[:, :R]
    return GroupResult(masks=cat(0), loss_init=cat(1), loss_final=cat(2),
                       swaps=cat(3))


@register("dsnot")
def _refine_dsnot(W, gram, pattern, ctx):
    """DSnoT baseline: surrogate-driven swaps from feature mean/variance.

    Runs off moments alone: with a full Gram the warmstart uses G and the
    reported losses are the exact row objective; at moments level the
    warmstart scores from diag(G) (identical masks — Wanda/RIA only ever
    read the diagonal) and losses fall back to the diagonal proxy.
    """
    d = W.shape[2]
    g_or_diag = gram.G if gram.G is not None else gram.gram_diag
    row_loss = (_row_loss_batch if gram.G is not None
                else _row_loss_diag_batch)
    m0 = _warmstart_batch(W, g_or_diag, pattern, ctx.warmstart)
    l0 = row_loss(W, m0, g_or_diag)
    block = pattern.block(d)
    m1 = jax.vmap(
        lambda w, m_, mu, var, ex2: _dsnot_rows(
            w, m_, mu, var, ex2, t_max=ctx.t_max, block=block)
    )(W.astype(jnp.float32), m0, gram.mean, gram.variance, gram.ex2)
    l1 = row_loss(W, m1, g_or_diag)
    return GroupResult(masks=m1, loss_init=l0, loss_final=l1,
                       swaps=_no_swaps(W))


@register("sparsegpt")
def _refine_sparsegpt(W, gram, pattern, ctx):
    """SparseGPT baseline: OBS mask + weight update, batched over instances."""
    m0 = _warmstart_batch(W, gram.G, pattern, ctx.warmstart)
    l0 = _row_loss_batch(W, m0, gram.G)
    W1, m1 = jax.vmap(lambda w, g: _sparsegpt(w, g, pattern))(W, gram.G)
    # loss of the (mask + updated weights) pair w.r.t. the dense output:
    # ||WX - W1X||^2 via G
    diff = W.astype(jnp.float32) - W1
    l1 = jax.vmap(sm.quad_form)(diff, gram.G)
    return GroupResult(masks=m1, loss_init=l0, loss_final=l1,
                       swaps=_no_swaps(W), new_weights=W1)


# ---------------------------------------------------------------------------
# mesh dispatch (sparseswaps only — the distributed refiners implement it)
# ---------------------------------------------------------------------------

def _sharded_regime(pattern, d_in: int, mesh: Mesh, budget: int) -> str:
    """rows-sharded unless G can't replicate (then column-shard G).

    N:M always refines rows-sharded: its swaps are within-block, so only
    the block-diagonal of G is touched and replication is never the bound.
    """
    if pattern.block(d_in) is not None or d_in * d_in * 4 <= budget:
        return "rows"
    if d_in % mesh.size:
        warnings.warn(
            f"Gram ({d_in}x{d_in} fp32) exceeds the per-device replication "
            f"budget but d_in is not divisible by {mesh.size} devices — "
            "column-sharded fallback unavailable, replicating G anyway")
        return "rows"
    return "gram"


def _refine_rows_padded(W, G, m0, pattern, mesh, *, t_max, eps, chunk,
                        k_swaps=1):
    """refine_rows_sharded with row padding to the mesh device count.

    Pad rows are zero weights under a keep-all mask: every candidate swap
    there scores +inf (b is inf on kept entries), so they never accept and
    never NaN; results are sliced back to the true rows.
    """
    R = W.shape[0]
    pad = (-R) % mesh.size
    if pad:
        W = jnp.pad(W, ((0, pad), (0, 0)))
        m0 = jnp.pad(m0, ((0, pad), (0, 0)), constant_values=1.0)
    m, l0, l1 = distributed.refine_rows_sharded(
        W, G, m0, pattern, mesh, t_max=t_max, eps=eps, chunk=chunk,
        k_swaps=k_swaps)
    return m[:R], l0[:R], l1[:R]


def _refine_sparseswaps_sharded(W, gram, pattern, ctx):
    N, R, d = W.shape
    mesh = ctx.mesh
    regime = _sharded_regime(pattern, d, mesh, ctx.gram_budget_bytes)
    k = sparseswaps._pick_k(ctx.k_swaps, d, pattern.block(d))
    masks, m0s, l0s, l1s = [], [], [], []
    for i in range(N):
        Wi = W[i].astype(jnp.float32)
        Gi = gram.G[i]
        m0 = warmstart_mask(Wi, Gi, pattern, criterion=ctx.warmstart)
        if regime == "gram":
            m, l0, l1 = distributed.refine_g_sharded(
                Wi, Gi, m0, pattern, mesh, t_max=ctx.t_max, eps=ctx.eps,
                k_swaps=k)
        else:
            m, l0, l1 = _refine_rows_padded(
                Wi, Gi, m0, pattern, mesh, t_max=ctx.t_max, eps=ctx.eps,
                chunk=ctx.chunk, k_swaps=k)
        sparseswaps.record_search_passes(ctx.t_max, R)
        masks.append(m)
        m0s.append(m0)
        l0s.append(l0)
        l1s.append(l1)
    m = jnp.stack(masks)
    # the sharded loop doesn't count acceptances; each accepted swap flips
    # exactly 2 entries, so net mask distance / 2 is a faithful lower bound
    swaps = (jnp.sum(jnp.abs(m - jnp.stack(m0s)), axis=2) / 2).astype(jnp.int32)
    return GroupResult(masks=m, loss_init=jnp.stack(l0s),
                       loss_final=jnp.stack(l1s), swaps=swaps)


# ---------------------------------------------------------------------------
# per-instance reference path (under test against the batched engine)
# ---------------------------------------------------------------------------

def refine_instance(W, gram: sites_lib.GramStats, pattern, *, method: str,
                    warmstart: str, t_max: int, eps: float,
                    swap_method: str, row_block, k_swaps=None,
                    compact_every=None):
    """Prune one (d_out, d_in) instance. Returns (mask, l0, l1, swaps, W').

    The original pipeline hot loop, one jit per matrix — kept as the
    reference implementation the group-batched engine is verified against.
    """
    G = gram.G
    if G is None:
        if method != "dsnot":
            raise ValueError(f"method {method!r} needs full Gram statistics")
        diag = gram.gram_diag
        m0 = warmstart_mask(W, diag, pattern, criterion=warmstart)
        l0 = _row_loss_diag_batch(W[None], m0[None], diag[None])[0]
        m1 = _dsnot(W, m0, gram.mean, gram.variance, gram.ex2,
                    pattern, t_max=t_max, row_block=row_block)
        l1 = _row_loss_diag_batch(W[None], m1[None], diag[None])[0]
        return m1, l0, l1, jnp.zeros(W.shape[0], jnp.int32), None
    m0 = warmstart_mask(W, G, pattern, criterion=warmstart)
    l0 = sm.row_loss(W.astype(jnp.float32), m0, G)

    if method == "none":
        return m0, l0, l0, jnp.zeros(W.shape[0], jnp.int32), None

    if method == "sparseswaps":
        k = sparseswaps._pick_k(k_swaps, W.shape[1],
                                pattern.block(W.shape[1]))
        res = sparseswaps.refine(W, G, m0, pattern, t_max=t_max, eps=eps,
                                 method=swap_method, row_block=row_block,
                                 k_swaps=k,
                                 compact_every=compact_every or 0)
        return res.mask, res.loss_init, res.loss_final, res.swaps, None

    if method == "dsnot":
        m1 = _dsnot(W, m0, gram.mean, gram.variance, gram.ex2,
                    pattern, t_max=t_max, row_block=row_block)
        l1 = sm.row_loss(W.astype(jnp.float32), m1, G)
        return m1, l0, l1, jnp.zeros(W.shape[0], jnp.int32), None

    if method == "sparsegpt":
        W1, m1 = _sparsegpt(W, G, pattern)
        diff = (W.astype(jnp.float32) - W1)
        l1 = sm.quad_form(diff, G)
        return m1, l0, l1, jnp.zeros(W.shape[0], jnp.int32), W1

    raise ValueError(f"unknown method {method!r}")


def refine_group_reference(method: str, group: sites_lib.SiteGroup,
                           pattern: masks_lib.Pattern,
                           ctx: RefineContext) -> GroupResult:
    """The per-instance Python loop, reshaped into a GroupResult."""
    ms, l0s, l1s, sws, w1s = [], [], [], [], []
    for i in range(group.n_instances):
        m, l0, l1, sw, w1 = refine_instance(
            group.weights[i], group.gram.instance(i), pattern, method=method,
            warmstart=ctx.warmstart, t_max=ctx.t_max, eps=ctx.eps,
            swap_method=ctx.swap_method, row_block=ctx.row_block,
            k_swaps=ctx.k_swaps, compact_every=ctx.compact_every)
        ms.append(m)
        l0s.append(l0)
        l1s.append(l1)
        sws.append(sw)
        if w1 is not None:
            w1s.append(w1)
    return GroupResult(
        masks=jnp.stack(ms), loss_init=jnp.stack(l0s),
        loss_final=jnp.stack(l1s), swaps=jnp.stack(sws),
        new_weights=jnp.stack(w1s) if w1s else None)
