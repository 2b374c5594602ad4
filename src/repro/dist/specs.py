"""PartitionSpec derivation for whole pytrees (params / state / cache / batch).

Where ``sharding`` answers "how is THIS activation laid out" inside a traced
step, this module answers "what ``in_shardings``/``out_shardings`` does the
launcher pass to jit" — one spec per pytree leaf, derived from shapes:

* weights — ("model", "data") on the two largest dims (tensor parallel +
  FSDP), each guarded by divisibility against the mesh axis size; anything
  that does not divide stays replicated (the granite-moe vocab 49155 case);
* packed weights — output rows over every mesh axis (``packed_pspec``),
  the slice each device's sparse kernel serves;
* optimizer state — same rule as the matching param (AdamW m/v mirror the
  param tree), scalars replicated;
* decode caches — batch dim over the data-parallel axes, KV-head dim over
  "model" when the head count divides it;
* batches — leading (batch) dim over the data-parallel axes.

Every function takes shape pytrees (``jax.eval_shape`` outputs or concrete
arrays) and only reads ``mesh.shape``, so the dry-run can derive specs for
a 512-chip mesh without touching device state.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Mapping, Sequence

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.core.packed import PackedWeight


def _dp_axes(mesh_shape: Mapping[str, int]) -> tuple[str, ...]:
    """The data-parallel axes, outermost first ("pod" crosses DCN)."""
    return tuple(a for a in ("pod", "data") if a in mesh_shape)


def _axes_size(mesh_shape: Mapping[str, int], axes: Sequence[str]) -> int:
    return math.prod(mesh_shape[a] for a in axes)


def _path_names(path) -> list[str]:
    names = []
    for k in path:
        if hasattr(k, "key"):
            names.append(str(k.key))
        elif hasattr(k, "name"):
            names.append(str(k.name))
        elif hasattr(k, "idx"):
            names.append(str(k.idx))
    return names


# ---------------------------------------------------------------------------
# weights / train state
# ---------------------------------------------------------------------------

def leaf_pspec(path: Sequence[str], shape: Sequence[int], cfg: ArchConfig,
               mesh, *, fsdp: bool = True) -> P:
    """Weight-leaf spec: "model" on the largest dim, "data" on the second.

    Divisibility-guarded per dim (non-dividing dims replicate rather than
    pad), stable under ties (equal dims keep their original order, so a
    square (d, d) weight gets (model, data) — out-dim TP, in-dim FSDP).
    ``path`` is accepted for rule overrides by name; the base rule is
    shape-only.
    """
    del path  # shape-driven; name-keyed overrides slot in here if needed
    ms = mesh.shape
    if len(shape) < 2:
        return P()  # scalars / norm vectors / gates: replicate
    order = sorted(range(len(shape)), key=lambda i: shape[i], reverse=True)
    assign: list[str | None] = [None] * len(shape)
    i_tp, i_dp = order[0], order[1]
    if "model" in ms and shape[i_tp] % ms["model"] == 0:
        assign[i_tp] = "model"
    if fsdp and "data" in ms and shape[i_dp] % ms["data"] == 0:
        assign[i_dp] = "data"
    return P(*assign)


def packed_pspec(shape: Sequence[int], mesh) -> P:
    """Packed-leaf spec: output rows (dim -2) over every mesh axis.

    ``kernels.spmm`` runs the Pallas kernel per device on exactly this
    row slice, so the packed values/idx never move between calls. Rows
    that do not divide stay replicated (spmm then refuses the mesh).
    """
    rows = tuple(mesh.axis_names)
    if shape[-2] % math.prod(mesh.shape.values()):
        rows = None
    return P(*([None] * (len(shape) - 2)), rows, None)


def param_pspecs(cfg: ArchConfig, params: Any, mesh, *,
                 fsdp: bool = True) -> Any:
    """Spec tree matching ``params`` leaf-for-leaf."""
    def leaf(p, x):
        if isinstance(x, PackedWeight):
            spec = packed_pspec(x.values.shape, mesh)
            return dataclasses.replace(x, values=spec, idx=spec)
        return leaf_pspec(_path_names(p), x.shape, cfg, mesh, fsdp=fsdp)

    return jax.tree_util.tree_map_with_path(
        leaf, params, is_leaf=lambda x: isinstance(x, PackedWeight))


def state_pspecs(cfg: ArchConfig, state: Any, mesh, *,
                 fsdp: bool = True) -> Any:
    """Spec tree for a TrainState (params + AdamW m/v + step).

    The optimizer moments mirror the param shapes, so the weight rule
    applies uniformly; the step counter (and any other scalar) replicates.
    """
    return param_pspecs(cfg, state, mesh, fsdp=fsdp)


# ---------------------------------------------------------------------------
# caches / batches
# ---------------------------------------------------------------------------

def cache_pspecs(cfg: ArchConfig, cache: Any, mesh, *,
                 batch: int | None = None) -> Any:
    """Decode-cache specs: batch dim over DP axes, KV heads over "model".

    Dims are identified by size (the cache layout is (L, B, S, kv_heads,
    head_dim)-shaped per family, with family-specific leading stacks), so
    pass the cell's global ``batch``. First match wins per role.
    """
    ms = mesh.shape
    dp = _dp_axes(ms)
    dp_ok = dp and batch and batch % _axes_size(ms, dp) == 0
    kv_ok = ("model" in ms and cfg.n_kv_heads
             and cfg.n_kv_heads % ms["model"] == 0)

    def leaf(l) -> P:
        shape = tuple(l.shape)
        if not shape:
            return P()
        assign: list[Any] = [None] * len(shape)
        b_done = kv_done = False
        for i, s in enumerate(shape):
            if dp_ok and not b_done and s == batch:
                assign[i] = dp if len(dp) > 1 else dp[0]
                b_done = True
            elif kv_ok and not kv_done and s == cfg.n_kv_heads:
                assign[i] = "model"
                kv_done = True
        return P(*assign)

    return jax.tree.map(leaf, cache)


def page_pspecs(cfg: ArchConfig, pages: Any, mesh) -> Any:
    """Paged-KV-pool specs: kv-head dim over "model", pages replicated.

    Pool leaves are (L, n_pages, page, kv_heads, head_dim) — positionally
    fixed, so the kv-head dim is identified by *position* (-2) rather
    than by size (a tiny config can have page == kv_heads, which would
    fool the first-match-by-size rule ``cache_pspecs`` uses). The page
    dim never shards: pages are addressed by id from host-side tables,
    and a session's pages must gather on every device.
    """
    ms = mesh.shape
    kv_ok = ("model" in ms and cfg.n_kv_heads
             and cfg.n_kv_heads % ms["model"] == 0)

    def leaf(l) -> P:
        shape = tuple(l.shape)
        if kv_ok and len(shape) == 5 and shape[-2] == cfg.n_kv_heads:
            return P(None, None, None, "model", None)
        return P(*([None] * len(shape)))

    return jax.tree.map(leaf, pages)


def mesh_slices(mesh, *, axis: str = "data",
                first: int | None = None) -> tuple:
    """Carve a mesh into two disjoint submeshes along a named axis.

    The disaggregated-serving placement primitive: prefill
    (compute-bound) and decode (bytes-bound) pools live on separate
    device slices of one physical mesh, and KV pages ship between them
    (``serve.kvcache.ship_pages``). Splitting along a *data-parallel*
    axis keeps the "model" axis intact in both slices, so each pool
    still shards its kv-head dim over "model" exactly as before —
    ``page_pspecs`` applies unchanged on either slice.

    Returns ``(first_slice, second_slice)`` — ``first`` devices along
    ``axis`` vs the rest (default an even split). Both slices keep the
    parent's axis names; axis sizes shrink accordingly.
    """
    from jax.sharding import Mesh

    names = tuple(mesh.axis_names)
    if axis not in names:
        raise ValueError(f"mesh has no axis {axis!r} (axes: {names})")
    n = mesh.shape[axis]
    if n < 2:
        raise ValueError(f"cannot slice axis {axis!r} of size {n} in two")
    first = n // 2 if first is None else int(first)
    if not 0 < first < n:
        raise ValueError(f"need 0 < first < {n} along {axis!r}, "
                         f"got {first}")
    ax = names.index(axis)
    devs = mesh.devices
    take = lambda lo, hi: devs.take(range(lo, hi), axis=ax)
    return Mesh(take(0, first), names), Mesh(take(first, n), names)


def batch_pspecs(cfg: ArchConfig, batch: Any, mesh) -> Any:
    """Input-batch specs: leading dim over the DP axes, rest replicated."""
    ms = mesh.shape
    dp = _dp_axes(ms)
    dp_size = _axes_size(ms, dp) if dp else 0

    def leaf(l) -> P:
        shape = tuple(l.shape)
        if not shape or not dp or shape[0] % dp_size:
            return P(*([None] * len(shape)))
        lead = dp if len(dp) > 1 else dp[0]
        return P(lead, *([None] * (len(shape) - 1)))

    return jax.tree.map(leaf, batch)


# ---------------------------------------------------------------------------
# calibration accumulators
# ---------------------------------------------------------------------------

def calib_pspecs(state: Any, mesh) -> Any:
    """Specs for a calibration accumulator tree (``pruning.stats``).

    The accumulator is replicated over the data-parallel axes (every
    device folds in its own batch shard and the partials psum-merge), but
    the O(d²) Gram leaves — square trailing dims — column-shard over
    "model" when divisible, so the carried state costs 1/TP of its full
    footprint per device. G is symmetric, so a column shard is as good as
    a row shard for every consumer. Vector/scalar moments replicate.
    """
    ms = mesh.shape
    model = ms.get("model", 1)

    def leaf(l) -> P:
        shape = tuple(l.shape)
        if (model > 1 and len(shape) >= 2 and shape[-1] == shape[-2]
                and shape[-1] % model == 0):
            return P(*([None] * (len(shape) - 1)), "model")
        return P(*([None] * len(shape)))

    return jax.tree.map(leaf, state)


# ---------------------------------------------------------------------------
# materialization
# ---------------------------------------------------------------------------

def named(mesh, tree: Any) -> Any:
    """PartitionSpec pytree -> NamedSharding pytree on ``mesh``."""
    is_spec = lambda x: isinstance(x, P)
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s) if is_spec(s) else s,
        tree, is_leaf=is_spec)
