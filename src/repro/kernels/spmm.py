"""Pallas TPU kernels: fused packed sparse-weight matmul for serving.

``y = act(x @ (mask ⊙ W)ᵀ + b)`` evaluated from the *packed*
representations of ``repro.core.packed`` — the dense (d_out, d_in)
weight never exists in HBM, and the activation/bias epilogue runs in
the same kernel so serving matmuls never round-trip the pre-activation
through HBM. Both formats reduce to one kernel scheme because an
``nm24`` slot's absolute column is computable from its slot index
(``(s // n) * m + idx``), making it a ``gathered`` row with arithmetic
metadata.

Fused design (replaces the old expand-then-dot kernel, which
materialized a full (TO, d_in) fp32 scratch per output tile and capped
``d_in`` at the VMEM bound):

* grid ``(T/TT, d_out/TO, K/TS, d_in/TD)`` — token stripes outermost,
  then output tiles, with the packed-slot x reduction axes innermost;
* each (slot-tile, d-tile) step expands its slot block into a small
  (TD, TO) fp32 sub-tile (the weight tile transposed) in VMEM
  (slot-indexed one-hot accumulation — out-of-tile columns fall out of
  the iota match) and feeds the MXU directly: ``acc += x_tile @
  sub_tile`` with a persistent (TT, TO) fp32 accumulator. The slot
  block is first copied slot-major, so the per-slot loop reads one
  sublane row at a dynamic offset; a width-1 dynamic *lane* slice is
  refused by the TPU compiler. No (TO, d_in) scratch ever exists, so
  there is no ``d_in`` cap — wide layers tile;
* ``nm24`` slots are column-sorted by construction, so the slot block
  for d-tile ``di`` is the *static* slice ``[di·TD·n/m, (di+1)·TD·n/m)``
  — the slot grid axis collapses to 1 and expansion work drops from
  O(K·d_in) to O(K·TD) per output tile. ``gathered`` columns are
  arbitrary, so every slot tile is scanned against every d-tile
  (O(K·d_in) — the price of unstructured sparsity without hardware
  gather), but VMEM stays O(TO·TS): tiling along d_in replaced the old
  hard ``d_in`` cap;
* the epilogue (bias add + activation) applies once on the fp32
  accumulator at the last reduction step, in-kernel.

Pallas pipelines (double-buffers) the x / values / column blocks across
grid steps, so on real hardware the next tile's DMA overlaps the
current expand+dot. HBM traffic per output stripe is the packed bytes
(n/m of dense for 2:4 bf16 + metadata) — the decode-regime win — and
expanded sub-tiles amortize across the whole token stripe during
prefill instead of being re-paid per 128-token tile.

Off-TPU the wrappers run ``interpret=True`` or the pure-jnp fallback
(``kernel="jnp"``), which is phase-aware: decode-sized T gathers the
kept x columns per output row (O(T·d_out·K), no densification); prefill
-sized T scatters the packed rows into dense chunks once and runs one
BLAS matmul, amortizing the O(d_out·d_in) expansion over all T tokens —
the same amortization the Pallas kernel gets from its token stripes.

Kernel selection is logged at trace time (``record_dispatch``) so the
serving engine can report which path actually ran. Tiles whose VMEM
estimate passes ``_VMEM_BOUND`` raise: the kernel never silently hands
a call to the jnp path.
"""
from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from repro.core.packed import PackedWeight

# VMEM budget per grid step (double-buffered operands + scratch). Mosaic's
# default scoped-VMEM limit on v5e is 16 MiB; leave headroom for the
# compiler's own buffers.
_VMEM_BOUND = 12 * 2**20

# default tile shapes (see _plan): token stripe, output rows, d_in columns,
# gathered slot tile. TILE_D=256 keeps the 2:4 slot block at 128 lanes.
TILE_T = 256
TILE_O = 128
TILE_D = 256
TILE_S = 512

# gathered-intermediate budget for the jnp paths: the decode gather's
# (T, chunk, K) and the prefill scatter's (chunk, d_in) stay bounded
_JNP_GATHER_ELEMS = 1 << 24

# token count at/above which the jnp fallback switches from the decode
# gather to the prefill expand-to-dense + BLAS path (the expansion is
# O(d_out·d_in) once vs O(T·d_out·K) gathered elements)
_JNP_EXPAND_T = 16


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


# ---------------------------------------------------------------------------
# epilogue
# ---------------------------------------------------------------------------

def _relu2(x):
    r = jax.nn.relu(x)
    return r * r


# activations servable as a fused epilogue; keys match models.common.ACTS
EPILOGUES = {
    "silu": jax.nn.silu,
    "gelu": jax.nn.gelu,
    "relu": jax.nn.relu,
    "relu2": _relu2,
    "sigmoid": jax.nn.sigmoid,
}


def apply_epilogue(y, bias=None, act: str | None = None):
    """``act(y + bias)`` — the reference (unfused) epilogue."""
    if bias is not None:
        y = y + bias.astype(y.dtype)
    if act is not None:
        y = EPILOGUES[act](y)
    return y


# ---------------------------------------------------------------------------
# dispatch bookkeeping: trace-time records
# ---------------------------------------------------------------------------

_DISPATCH_LOG: list | None = None


@contextlib.contextmanager
def record_dispatch():
    """Collect the kernel decisions made while tracing inside the block.

    Kernel selection is static (shapes are trace-time constants), so a
    list appended to during tracing is exact. Yields the list; each
    entry: {"kernel", "fmt", "T", "d_out", "d_in", "reason"}. Blocks
    nest: an enclosing block also receives the inner block's records.
    """
    global _DISPATCH_LOG
    prev, _DISPATCH_LOG = _DISPATCH_LOG, []
    rec = _DISPATCH_LOG
    try:
        yield rec
    finally:
        _DISPATCH_LOG = prev
        if prev is not None:
            prev.extend(rec)


def _record(kernel: str, fmt: str, T: int, d_out: int, d_in: int,
            reason: str) -> None:
    if _DISPATCH_LOG is not None:
        _DISPATCH_LOG.append({"kernel": kernel, "fmt": fmt, "T": T,
                              "d_out": d_out, "d_in": d_in,
                              "reason": reason})


# ---------------------------------------------------------------------------
# tiling plan
# ---------------------------------------------------------------------------

def _plan(T: int, d_in: int, K: int, nm: tuple[int, int] | None, *,
          tile_t: int, tile_o: int, tile_d: int,
          tile_s: int) -> dict:
    """Resolve tile sizes for one spmm call (all static).

    ``nm`` is (n, m) for the aligned nm24 slot blocking, None for
    gathered. Shrinks tiles to the (padded) problem so tiny layers don't
    pad 2x, and keeps the nm24 slot block = tile_d·n/m exact.
    """
    tile_t = min(tile_t, _round_up(T, 8))
    tile_d = min(tile_d, _round_up(d_in, 128))
    if nm is not None:
        n, m = nm
        # slot blocks must cover whole m-blocks (tile_d multiple of m)
        # and keep a full 128-lane slot block even for narrow layers
        tile_d = _round_up(max(tile_d, 128 * m // n), m)
        tile_s = tile_d * n // m
        n_s = 1
        Dp = _round_up(d_in, tile_d)
        Kp = Dp * n // m
    else:
        tile_s = min(tile_s, _round_up(K, 128))
        Dp = _round_up(d_in, tile_d)
        Kp = _round_up(K, tile_s)
        n_s = Kp // tile_s
    return {"tile_t": tile_t, "tile_o": tile_o, "tile_d": tile_d,
            "tile_s": tile_s, "n_s": n_s, "Dp": Dp, "Kp": Kp}


def _vmem_bytes(plan: dict, x_itemsize: int, v_itemsize: int) -> int:
    """Estimated VMEM per grid step: double-buffered operand blocks plus
    the fp32 accumulator, the expansion sub-tile and the slot-major
    copies (the quantity the boundary test pins at ``_VMEM_BOUND``)."""
    tt, to = plan["tile_t"], plan["tile_o"]
    td, ts = plan["tile_d"], plan["tile_s"]
    x_blk = tt * td * x_itemsize
    v_blk = to * ts * v_itemsize
    c_blk = to * ts * 4
    o_blk = tt * to * 4
    b_blk = to * 4
    scratch = tt * to * 4 + td * to * 4 + 2 * ts * to * 4
    return 2 * (x_blk + v_blk + c_blk + o_blk + b_blk) + scratch


# ---------------------------------------------------------------------------
# kernel
# ---------------------------------------------------------------------------

def _spmm_kernel(x_ref, v_ref, c_ref, b_ref, o_ref, acc_ref, sub_ref,
                 vt_ref, ct_ref, *, n_slots: int, tile_d: int,
                 act: str | None):
    """One fused reduction step of ``y = act(x @ Wᵀ + b)``.

    x_ref: (TT, TD) token stripe x d-tile; v_ref/c_ref: (TO, TS) packed
    values + absolute columns for this slot tile; b_ref: (1, TO) fp32
    bias; o_ref: (TT, TO); acc_ref: persistent fp32 accumulator;
    sub_ref: (TD, TO) fp32 expansion scratch (the weight tile
    transposed), rebuilt per step; vt_ref/ct_ref: (TS, TO) slot-major
    copies of the slot block.
    """
    si, di = pl.program_id(2), pl.program_id(3)
    first = jnp.logical_and(si == 0, di == 0)
    last = jnp.logical_and(si == pl.num_programs(2) - 1,
                           di == pl.num_programs(3) - 1)

    @pl.when(first)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # slot-major copies: the slot loop below then reads one sublane row
    # per slot. A width-1 dynamic *lane* slice of the (TO, TS) block is
    # what the TPU compiler refuses.
    ct_ref[...] = (c_ref[...] - di * tile_d).T
    vt_ref[...] = v_ref[...].astype(jnp.float32).T

    # expand the slot block into the (TD, TO) sub-tile: a slot whose
    # column lies outside [di·TD, (di+1)·TD) never matches the iota, so
    # the same masked accumulation serves aligned nm24 blocks, arbitrary
    # gathered slots, and zero-padding alike. Kept columns are unique
    # per row -> the add is an exact scatter.
    sub_ref[...] = jnp.zeros_like(sub_ref)
    iota = jax.lax.broadcasted_iota(jnp.int32, sub_ref.shape, 0)

    def body(s, carry):
        local = ct_ref[pl.ds(s, 1), :]                      # (1, TO)
        val = vt_ref[pl.ds(s, 1), :]
        sub_ref[...] += jnp.where(iota == local, val, 0.0)
        return carry

    jax.lax.fori_loop(0, n_slots, body, 0)

    x = x_ref[...].astype(jnp.float32)
    acc_ref[...] += jnp.dot(x, sub_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(last)
    def _epilogue():
        y = acc_ref[...] + b_ref[...]
        if act is not None:
            y = EPILOGUES[act](y)
        o_ref[...] = y


@functools.partial(
    jax.jit,
    static_argnames=("nm_aligned", "tile_t", "tile_o", "tile_d", "tile_s",
                     "act", "interpret"))
def _spmm_padded(x, vals, cols, bias, *, nm_aligned: bool, tile_t: int,
                 tile_o: int, tile_d: int, tile_s: int, act: str | None,
                 interpret: bool):
    """Core pallas_call. x: (Tp, Dp); vals/cols: (Op, Kp); bias: (1, Op)
    fp32; all padded to their tile multiples."""
    Tp, Dp = x.shape
    Op, Kp = vals.shape
    n_s = 1 if nm_aligned else Kp // tile_s
    grid = (Tp // tile_t, Op // tile_o, n_s, Dp // tile_d)
    # nm24 slots are column-aligned: d-tile di owns slot block di. The
    # gathered slot axis is its own grid dim, swept against every d-tile.
    slot_ix = ((lambda t, o, s, d: (o, d)) if nm_aligned
               else (lambda t, o, s, d: (o, s)))
    out = pl.pallas_call(
        functools.partial(_spmm_kernel, n_slots=tile_s, tile_d=tile_d,
                          act=act),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_t, tile_d), lambda t, o, s, d: (t, d)),  # x
            pl.BlockSpec((tile_o, tile_s), slot_ix),    # values
            pl.BlockSpec((tile_o, tile_s), slot_ix),    # abs columns
            pl.BlockSpec((1, tile_o), lambda t, o, s, d: (0, o)),       # bias
        ],
        out_specs=pl.BlockSpec((tile_t, tile_o), lambda t, o, s, d: (t, o)),
        out_shape=jax.ShapeDtypeStruct((Tp, Op), jnp.float32),
        scratch_shapes=[pltpu.VMEM((tile_t, tile_o), jnp.float32),
                        pltpu.VMEM((tile_d, tile_o), jnp.float32),
                        pltpu.VMEM((tile_s, tile_o), jnp.float32),
                        pltpu.VMEM((tile_s, tile_o), jnp.int32)],
        interpret=interpret,
    )(x, vals, cols, bias)
    return out


def _spmm_pallas(x2, vals, cols, *, d_in, plan, bias, act, interpret):
    T, _ = x2.shape
    d_out, K = vals.shape
    tt, to = plan["tile_t"], plan["tile_o"]
    Tp, Op = _round_up(T, tt), _round_up(d_out, to)
    Dp, Kp = plan["Dp"], plan["Kp"]
    xp = jnp.pad(x2, ((0, Tp - T), (0, Dp - d_in)))
    # padded slots: value 0 at column 0 — they match (at most) iota 0 of
    # d-tile 0 and contribute exactly nothing
    vp = jnp.pad(vals, ((0, Op - d_out), (0, Kp - K)))
    cp = jnp.pad(cols, ((0, Op - d_out), (0, Kp - K)))
    b = jnp.zeros((1, Op), jnp.float32) if bias is None else \
        jnp.pad(bias.astype(jnp.float32).reshape(1, -1),
                ((0, 0), (0, Op - d_out)))
    y = _spmm_padded(xp, vp, cp, b, nm_aligned=plan["nm_aligned"],
                     tile_t=tt, tile_o=to, tile_d=plan["tile_d"],
                     tile_s=plan["tile_s"], act=act, interpret=interpret)
    return y[:T, :d_out]


def _per_device_rows(run, x2, vals, cols, bias, mesh):
    """Run the kernel whole, or on each device of ``mesh``.

    XLA cannot partition a Mosaic kernel, so on a mesh each device runs
    it inside a ``shard_map`` on its own slice of output rows (split over
    every mesh axis, the placement ``dist.specs`` gives packed leaves),
    with x replicated.
    """
    if mesh is None or mesh.size == 1:
        return run(x2, vals, cols, bias=bias)
    if vals.shape[0] % mesh.size:
        raise ValueError(
            f"spmm: {vals.shape[0]} output rows do not split over the "
            f"{mesh.size} devices of the mesh")
    rows = tuple(mesh.axis_names)
    if bias is None:
        bias = jnp.zeros(vals.shape[0], jnp.float32)
    return jax.shard_map(
        lambda x, v, c, b: run(x, v, c, bias=b), mesh=mesh,
        in_specs=(P(), P(rows, None), P(rows, None), P(rows)),
        out_specs=P(None, rows), check_vma=False)(x2, vals, cols, bias)


# ---------------------------------------------------------------------------
# jnp fallback — phase-aware: gather for decode, expand+BLAS for prefill
# ---------------------------------------------------------------------------

def _spmm_jnp(x2: jnp.ndarray, vals: jnp.ndarray, abs_idx: jnp.ndarray,
              d_in: int, *, nm: tuple[int, int] | None = None, bias=None,
              act: str | None = None,
              expand_t: int | None = None) -> jnp.ndarray:
    """y[t, o] = act(Σ_s x[t, cols[o, s]] · vals[o, s] + b[o]) — fp32.

    Two regimes, switched on the (static) token count:

    * decode (T < ``_JNP_EXPAND_T``): gather the kept x columns per
      output row and contract over slots — O(T·d_out·K), no
      densification, chunked over d_out to bound the (T, chunk, K)
      intermediate;
    * prefill: densify each packed row into a (chunk, d_in) fp32 tile
      ONCE and run one BLAS matmul over all T tokens — the
      O(d_out·d_in) expansion amortizes over the token axis exactly
      like the Pallas kernel's stripe-resident sub-tiles (this is what
      closes the packed-prefill gap off-TPU). nm24 rows densify via a
      vectorized within-block one-hot einsum (slot s lives in m-block
      s//n — no scatter on the hot path); gathered rows need the
      general scatter-add.
    """
    T = x2.shape[0]
    d_out, K = vals.shape
    x32 = x2.astype(jnp.float32)
    v32 = vals.astype(jnp.float32)
    threshold = _JNP_EXPAND_T if expand_t is None else expand_t
    outs = []
    if T >= threshold and nm is not None:
        n, m = nm
        nb = K // n
        blk = (jnp.arange(K, dtype=jnp.int32) // n) * m
        chunk = max(1, min(d_out, _JNP_GATHER_ELEMS // max(d_in * n, 1)))
        for lo in range(0, d_out, chunk):
            c = min(chunk, d_out - lo)
            loc = abs_idx[lo:lo + c] - blk                     # (c, K) < m
            oh = jax.nn.one_hot(loc.reshape(c, nb, n), m,
                                dtype=jnp.float32)             # (c,nb,n,m)
            wd = jnp.einsum("cbn,cbnm->cbm",
                            v32[lo:lo + c].reshape(c, nb, n), oh)
            outs.append(x32 @ wd.reshape(c, d_in).T)
    elif T >= threshold:
        chunk = max(1, min(d_out, _JNP_GATHER_ELEMS // max(d_in, 1)))
        rows = jnp.arange(chunk)[:, None]
        for lo in range(0, d_out, chunk):
            c = min(chunk, d_out - lo)
            wd = jnp.zeros((c, d_in), jnp.float32)
            wd = wd.at[rows[:c], abs_idx[lo:lo + c]].add(v32[lo:lo + c])
            outs.append(x32 @ wd.T)
    else:
        chunk = max(1, min(d_out, _JNP_GATHER_ELEMS // max(T * K, 1)))
        for lo in range(0, d_out, chunk):
            xg = jnp.take(x32, abs_idx[lo:lo + chunk], axis=1)  # (T, c, K)
            outs.append(jnp.einsum("tok,ok->to", xg, v32[lo:lo + chunk]))
    y = outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)
    return apply_epilogue(y, bias, act)


# ---------------------------------------------------------------------------
# public wrappers
# ---------------------------------------------------------------------------

def _abs_columns_nm(idx: jnp.ndarray, n: int, m: int) -> jnp.ndarray:
    """Within-block uint8 metadata -> absolute int32 columns."""
    slots = jnp.arange(idx.shape[-1], dtype=jnp.int32)
    base = (slots // n) * m
    return idx.astype(jnp.int32) + jnp.broadcast_to(base, idx.shape)


def abs_columns(pw: PackedWeight) -> jnp.ndarray:
    """Absolute kept-column indices (..., d_out, k) for either format."""
    if pw.fmt == "nm24":
        return _abs_columns_nm(pw.idx, pw.n, pw.m)
    return pw.idx.astype(jnp.int32)


def _dispatch(x, vals, cols, d_in: int, *, nm: tuple[int, int] | None,
              kernel: str, interpret: bool | None, tile_t: int,
              tile_o: int, tile_d: int, tile_s: int, bias=None,
              act: str | None = None, mesh=None):
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    T = x2.shape[0]
    d_out, K = vals.shape
    fmt = "nm24" if nm is not None else "gathered"
    if kernel == "auto":
        kernel = "pallas" if _on_tpu() else "jnp"
        reason = "auto"
    else:
        reason = "forced"
    if kernel == "pallas":
        plan = _plan(T, d_in, K, nm, tile_t=tile_t, tile_o=tile_o,
                     tile_d=tile_d, tile_s=tile_s)
        plan["nm_aligned"] = nm is not None
        est = _vmem_bytes(plan, x.dtype.itemsize, vals.dtype.itemsize)
        if est > _VMEM_BOUND:
            tiles = (plan["tile_t"], plan["tile_o"], plan["tile_d"],
                     plan["tile_s"])
            raise ValueError(
                f"spmm: tiles (tile_t, tile_o, tile_d, tile_s)={tiles} "
                f"need ~{est / 2**20:.1f} MiB VMEM per grid step for "
                f"d_in={d_in} (bound {_VMEM_BOUND / 2**20:.0f} MiB); "
                f"shrink the tiles")
    if kernel == "jnp":
        y = _spmm_jnp(x2, vals, cols, d_in, nm=nm, bias=bias, act=act)
    elif kernel == "pallas":
        if interpret is None:
            interpret = not _on_tpu()
        run = functools.partial(_spmm_pallas, d_in=d_in, plan=plan, act=act,
                                interpret=interpret)
        y = _per_device_rows(run, x2, vals, cols, bias, mesh)
    else:
        raise ValueError(f"unknown spmm kernel {kernel!r}")
    _record(kernel, fmt, T, d_out, d_in, reason)
    return y.reshape(*lead, d_out).astype(x.dtype)


def spmm_nm24(x, values, idx, *, n: int = 2, m: int = 4,
              d_in: int | None = None, kernel: str = "auto",
              interpret: bool | None = None, tile_t: int = TILE_T,
              tile_o: int = TILE_O, tile_d: int = TILE_D,
              bias=None, act: str | None = None, mesh=None):
    """x: (..., d_in) @ packed-N:M weightᵀ -> (..., d_out), epilogue fused.

    ``values``: (d_out, nb·n) kept weights; ``idx``: matching uint8
    within-block positions. ``bias`` ((d_out,) or None) and ``act`` (an
    ``EPILOGUES`` key or None) run inside the kernel on the fp32
    accumulator. With a ``mesh`` the Pallas kernel runs on each device
    over its slice of output rows.
    """
    if d_in is None:
        d_in = values.shape[-1] * m // n
    cols = _abs_columns_nm(idx, n, m)
    return _dispatch(x, values, cols, d_in, nm=(n, m), kernel=kernel,
                     interpret=interpret, tile_t=tile_t, tile_o=tile_o,
                     tile_d=tile_d, tile_s=TILE_S, bias=bias, act=act,
                     mesh=mesh)


def spmm_gather(x, values, idx, *, d_in: int, kernel: str = "auto",
                interpret: bool | None = None, tile_t: int = TILE_T,
                tile_o: int = TILE_O, tile_d: int = TILE_D,
                tile_s: int = TILE_S, bias=None, act: str | None = None,
                mesh=None):
    """x: (..., d_in) @ gathered weightᵀ -> (..., d_out), epilogue fused.

    ``values``: (d_out, k) kept weights; ``idx``: int32 absolute kept
    columns per row (any order; packing emits them ascending).
    """
    return _dispatch(x, values, idx.astype(jnp.int32), d_in, nm=None,
                     kernel=kernel, interpret=interpret, tile_t=tile_t,
                     tile_o=tile_o, tile_d=tile_d, tile_s=tile_s,
                     bias=bias, act=act, mesh=mesh)


def spmm(x, pw: PackedWeight, *, kernel: str = "auto",
         interpret: bool | None = None, bias=None, act: str | None = None,
         mesh=None):
    """Dispatch on a 2-D (d_out, k) ``PackedWeight`` leaf."""
    if pw.values.ndim != 2:
        raise ValueError(
            f"spmm wants an unstacked (d_out, k) PackedWeight; got "
            f"values of shape {pw.values.shape} — vmap via spmm_stacked")
    if pw.fmt == "nm24":
        return spmm_nm24(x, pw.values, pw.idx, n=pw.n, m=pw.m,
                         d_in=pw.d_in, kernel=kernel, interpret=interpret,
                         bias=bias, act=act, mesh=mesh)
    return spmm_gather(x, pw.values, pw.idx, d_in=pw.d_in, kernel=kernel,
                       interpret=interpret, bias=bias, act=act, mesh=mesh)


def spmm_stacked(x, pw: PackedWeight, *, kernel: str = "auto",
                 interpret: bool | None = None, bias=None,
                 act: str | None = None, mesh=None):
    """Per-instance spmm over one stacked leading dim (MoE experts).

    x: (N, ..., d_in); pw values/idx: (N, d_out, k) -> (N, ..., d_out).
    """
    import dataclasses as _dc

    def one(xi, vi, ii):
        return spmm(xi, _dc.replace(pw, values=vi, idx=ii),
                    kernel=kernel, interpret=interpret, bias=bias, act=act,
                    mesh=mesh)

    return jax.vmap(one)(x, pw.values, pw.idx)
