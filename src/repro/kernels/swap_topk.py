"""Pallas TPU kernels: fused top-k swap search + in-kernel commit loop.

The k-swap hot path. ``swap_argmin`` (the k = 1 reference kernel this one
is tested against) re-streams the whole Gram matrix from HBM for every
single accepted swap; here ONE pass over G yields up to k committable
candidates per row, so HBM traffic per accepted swap drops by ~k.

``swap_topk_padded`` — fused candidate search:

* Grid ``(rows/RB, d/TU, d/TP)`` with the p-tiles INNERMOST. Each step
  scores ΔL[r, u, p] = (a_u + b_p) + (c_u·w_p)·G[u, p] for its RB rows,
  TU kept-side columns u and TP pruned-side columns p, with c = -2·w
  handed in by the wrapper (doubling and negation are exact, so this is
  the reference's a + b - 2·(w_u·w_p)·G bit for bit in float32).
* The u-indexed operands arrive with u on lanes. Once per (row block,
  u-tile), at its first p-tile, they are spread to u on sublanes across
  all 128 lanes in VMEM scratch; every p-tile of that u-tile reuses the
  spread, so the cross-lane work is O(R·d), not O(R·d²).
* Per row, one sweep over the u-tile in groups of 8 (one u per sublane)
  and over the whole p-tile (at most 512 lanes) keeps a running (min,
  argmin group) per (sublane, p) in registers: 7 vector ops per 8×128 ΔL
  vreg (2 adds, 2 multiplies, a compare, 2 selects), nothing
  materialised, nothing reduced twice. A strict < as u rises keeps the lowest u among equals.
  The 8 sublanes then fold to one (min, lowest u) per p, which merges
  into a per-p VMEM scratch across u-tiles (ascending, so an equal ΔL
  keeps the earlier u).
* At the last u-tile the TP completed columns fold into per-row top-k
  lists that live in the OUTPUT refs. Candidates are the k best pruned
  columns p by ``min_u ΔL[u, p]`` with deterministic (ΔL, p, u)
  lexicographic tie-break — bit-identical to
  ``swap_math.topk_swaps_dense/chunked`` on feasible entries (the +inf
  tail of rows with fewer than k feasible pairs carries index sentinels).
  Each G tile is read from HBM once per row block.
* Top-k maintenance is an insertion network: each extracted candidate is
  ranked against the running sorted list (count-of-predecessors), then the
  list shift-inserts in registers — no sort primitive needed.

``swap_commit_padded`` — the greedy commit decision loop, in-kernel:

* One grid step per row block, everything in VMEM. The body executes
  ``swap_math.commit_decisions`` VERBATIM (the function is written in
  2-D-slice form for exactly this reason) over the gathered k×k candidate
  sub-Grams, so kernel and jnp commits are bit-identical by construction.
* O(R·k²) state instead of O(R·d): the sequential re-scoring of later
  candidates against earlier accepted swaps never touches a full-width
  vector; the full-width Eq. 6 rank-1 updates happen once per accepted
  swap outside (``swap_math.apply_commits``), amortized against the
  O(R·d²) search.

VMEM per search step (RB=8, TU=1024, TP=512, the widest tiles
``ops.topk_tiles`` picks): G tile 2MB (x2 buffers) + u spreads (RB,TU,128)
fp32 2x4MB + per-p (min, u) 64B·d (0.9MB at d=13824) + this tile's (min,
u) 32KB + lists ~1KB < 16MB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import swap_math as sm

_BIG_I32 = 2**30  # python int: jnp constants may not be captured by kernels
_SUB, _LANES = 8, 128     # float32 vreg: sublanes x lanes
_SWEEP_LANES = 512        # most p lanes one u-sweep keeps in registers
_UNROLL = 16              # u-groups of 8 per sweep iteration; divides 128/8


def _shift_right(x):
    """[x0, x0, x1, ..., x_{k-2}]: the insert-at-pos shift (slot 0 unused
    by construction — it is only selected where sidx > pos >= 0)."""
    return jnp.concatenate([x[:, :1], x[:, :-1]], axis=1)


def _insert_sorted(vals, ps, us, mv, gp, uv):
    """Insert one (ΔL, p, u) candidate per row into sorted top-k lists.

    Lists are ascending by (ΔL, p); ``mv, gp, uv`` are (RB, 1). Returns the
    updated lists. A candidate ranking past the end (pos == k) is dropped.
    """
    prec = (vals < mv) | ((vals == mv) & (ps < gp))
    pos = jnp.sum(prec.astype(jnp.int32), axis=1, keepdims=True)
    sidx = jax.lax.broadcasted_iota(jnp.int32, vals.shape, 1)
    vals = jnp.where(sidx < pos, vals,
                     jnp.where(sidx == pos, mv, _shift_right(vals)))
    ps = jnp.where(sidx < pos, ps,
                   jnp.where(sidx == pos, gp, _shift_right(ps)))
    us = jnp.where(sidx < pos, us,
                   jnp.where(sidx == pos, uv, _shift_right(us)))
    return vals, ps, us


def _topk_kernel(a_ref, b_ref, c_ref, wp_ref, g_ref, vals_ref, u_ref,
                 p_ref, abc_ref, cbc_ref, cur_min_ref, cur_u_ref, pmin_ref,
                 pu_ref, *, tu: int, tp: int, k: int):
    ui = pl.program_id(1)
    pi = pl.program_id(2)
    rb = a_ref.shape[0]

    @pl.when((ui == 0) & (pi == 0))
    def _init_lists():
        vals_ref[...] = jnp.full_like(vals_ref, jnp.inf)
        u_ref[...] = jnp.full_like(u_ref, _BIG_I32)
        p_ref[...] = jnp.full_like(p_ref, _BIG_I32)

    @pl.when(pi == 0)
    def _spread_u():
        # a and c arrive with u on lanes; the ΔL vregs want u on sublanes,
        # spread over all 128 lanes. Done once per (row block, u-tile) and
        # reused by every p-tile, so the cross-lane work is O(R·d).
        abc_ref[...] = jnp.broadcast_to(a_ref[...][:, :, None],
                                        abc_ref.shape)
        cbc_ref[...] = jnp.broadcast_to(c_ref[...][:, :, None],
                                        cbc_ref.shape)

    reps = tp // _LANES
    sub = jax.lax.broadcasted_iota(jnp.int32, (_SUB, tp), 0)

    # rows in a loop, not unrolled: each process traces and lowers this
    # body again for every refinement program (one per row count), and
    # unrolled rows made that several times the cost of the old kernel
    def row(r, carry):
        b_r = jnp.broadcast_to(b_ref[pl.ds(r, 1), :], (_SUB, tp))
        w_r = jnp.broadcast_to(wp_ref[pl.ds(r, 1), :], (_SUB, tp))

        def sweep(i, carry):
            # eight u per group, one per sublane: a running (min, group)
            # per (sublane, p); strict < keeps the lowest u
            best, grp = carry
            for t in range(_UNROLL):
                j = i * _UNROLL + t
                u8 = pl.multiple_of(j * _SUB, _SUB)
                av = jnp.tile(abc_ref[r, pl.ds(u8, _SUB), :], (1, reps))
                cv = jnp.tile(cbc_ref[r, pl.ds(u8, _SUB), :], (1, reps))
                dl = (av + b_r) + (cv * w_r) * g_ref[pl.ds(u8, _SUB), :]
                lt = dl < best
                best = jnp.where(lt, dl, best)
                grp = jnp.where(lt, j, grp)
            return best, grp

        best, grp = jax.lax.fori_loop(
            0, tu // (_SUB * _UNROLL), sweep,
            (jnp.full((_SUB, tp), jnp.inf, jnp.float32),
             jnp.zeros((_SUB, tp), jnp.int32)))
        # fold the eight sublanes: least ΔL, then least u among equals (an
        # all-inf column reads u = 0, as argmin does)
        m = jnp.min(best, axis=0, keepdims=True)
        cur_min_ref[pl.ds(r, 1), :] = m
        cur_u_ref[pl.ds(r, 1), :] = jnp.min(
            jnp.where(best == m, grp * _SUB + sub, _BIG_I32), axis=0,
            keepdims=True)
        return carry

    jax.lax.fori_loop(0, rb, row, 0)
    tmin = cur_min_ref[...]                                 # (RB, TP)
    gu = ui * tu + cur_u_ref[...]

    @pl.when(ui == 0)
    def _first_tile():
        pmin_ref[pi] = tmin
        pu_ref[pi] = gu

    @pl.when(ui > 0)
    def _merge_tile():
        # u-tiles arrive in ascending order, so an equal ΔL keeps the
        # earlier (lower) u
        prev = pmin_ref[pi]
        better = tmin < prev
        pmin_ref[pi] = jnp.where(better, tmin, prev)
        pu_ref[pi] = jnp.where(better, gu, pu_ref[pi])

    @pl.when(ui == pl.num_programs(1) - 1)
    def _fold_tile():
        # all u-tiles seen for this p-tile: fold its TP completed columns
        # into the running top-k lists (k masked-min extractions, each
        # shift-inserted; any global top-k member is in its tile's top-k)
        cv = pmin_ref[pi]
        cu = pu_ref[pi]
        iota_p = jax.lax.broadcasted_iota(jnp.int32, cv.shape, 1)
        vals, us, ps = vals_ref[...], u_ref[...], p_ref[...]
        for _ in range(k):
            mv = jnp.min(cv, axis=1, keepdims=True)
            sel_p = jnp.where(cv == mv, iota_p, _BIG_I32)
            loc = jnp.min(sel_p, axis=1, keepdims=True)     # ties -> low p
            sel = iota_p == loc
            uv = jnp.min(jnp.where(sel, cu, _BIG_I32), axis=1, keepdims=True)
            gp = pi * tp + loc
            cv = jnp.where(sel, jnp.inf, cv)
            vals, ps, us = _insert_sorted(vals, ps, us, mv, gp, uv)
        vals_ref[...] = vals
        u_ref[...] = us
        p_ref[...] = ps


@functools.partial(
    jax.jit, static_argnames=("k", "row_block", "tile_u", "tile_p",
                              "interpret")
)
def swap_topk_padded(
    a: jnp.ndarray,
    b: jnp.ndarray,
    c: jnp.ndarray,
    w: jnp.ndarray,
    G: jnp.ndarray,
    *,
    k: int,
    row_block: int = 8,
    tile_u: int = 256,
    tile_p: int = 256,
    interpret: bool = False,
):
    """Core pallas_call. Requires R % row_block == 0 and d % tile == 0.

    a, b: (R, d) fp32 with +inf at infeasible entries; c = -2·w and w:
    (R, d) fp32; G: (d, d) fp32. Returns (vals (R, k), u (R, k), p (R, k))
    sorted ascending by (ΔL, p); +inf vals carry _BIG index sentinels.
    """
    R, d = a.shape
    assert R % row_block == 0 and d % tile_u == 0 and d % tile_p == 0
    # a p-tile is one sweep: its running (min, group) stays in registers
    assert tile_u % _LANES == 0 and tile_p % _LANES == 0, (tile_u, tile_p)
    assert tile_p <= _SWEEP_LANES, tile_p
    n_p = d // tile_p
    grid = (R // row_block, d // tile_u, n_p)

    row_u = lambda ri, ui, pi: (ri, ui)
    row_p = lambda ri, ui, pi: (ri, pi)
    out_map = lambda ri, ui, pi: (ri, 0)

    vals, u_idx, p_idx = pl.pallas_call(
        functools.partial(_topk_kernel, tu=tile_u, tp=tile_p, k=k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((row_block, tile_u), row_u),   # a
            pl.BlockSpec((row_block, tile_p), row_p),   # b
            pl.BlockSpec((row_block, tile_u), row_u),   # c = -2·w (u view)
            pl.BlockSpec((row_block, tile_p), row_p),   # w (p view)
            pl.BlockSpec((tile_u, tile_p), lambda ri, ui, pi: (ui, pi)),  # G
        ],
        out_specs=[
            pl.BlockSpec((row_block, k), out_map),
            pl.BlockSpec((row_block, k), out_map),
            pl.BlockSpec((row_block, k), out_map),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((R, k), jnp.float32),
            jax.ShapeDtypeStruct((R, k), jnp.int32),
            jax.ShapeDtypeStruct((R, k), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((row_block, tile_u, _LANES), jnp.float32),  # a spread
            pltpu.VMEM((row_block, tile_u, _LANES), jnp.float32),  # c spread
            pltpu.VMEM((row_block, tile_p), jnp.float32),  # this u-tile's min
            pltpu.VMEM((row_block, tile_p), jnp.int32),    # and its argmin u
            pltpu.VMEM((n_p, row_block, tile_p), jnp.float32),  # per-p min
            pltpu.VMEM((n_p, row_block, tile_p), jnp.int32),    # its argmin u
        ],
        interpret=interpret,
    )(a, b, c, w, G)
    return vals, u_idx, p_idx


def _commit_kernel(wu_ref, wp_ref, cu_ref, cp_ref, suu_ref, sup_ref,
                   spp_ref, u_ref, p_ref, valid_ref, acc_ref, dl_ref, *,
                   eps: float, k: int):
    acc, dls = sm.commit_decisions(
        wu_ref[...], wp_ref[...], cu_ref[...], cp_ref[...], suu_ref[...],
        sup_ref[...], spp_ref[...], u_ref[...], p_ref[...], valid_ref[...],
        eps=eps, k=k)
    acc_ref[...] = acc
    dl_ref[...] = dls


@functools.partial(jax.jit,
                   static_argnames=("eps", "k", "row_block", "interpret"))
def swap_commit_padded(wu, wp, cu, cp, Suu, Sup, Spp, u, p, valid, *,
                       eps: float, k: int, row_block: int = 8,
                       interpret: bool = False):
    """In-kernel greedy commit decisions over a gathered candidate batch.

    All (R, k) / (R, k, k) inputs; requires R % row_block == 0. Returns
    (acc (R, k) 0/1 fp32, dl (R, k) exact re-scored ΔL, 0 where rejected).
    """
    R = wu.shape[0]
    assert R % row_block == 0, (R, row_block)
    grid = (R // row_block,)
    mat = pl.BlockSpec((row_block, k), lambda ri: (ri, 0))
    cube = pl.BlockSpec((row_block, k, k), lambda ri: (ri, 0, 0))
    acc, dls = pl.pallas_call(
        functools.partial(_commit_kernel, eps=eps, k=k),
        grid=grid,
        in_specs=[mat, mat, mat, mat, cube, cube, cube, mat, mat, mat],
        out_specs=[mat, mat],
        out_shape=[
            jax.ShapeDtypeStruct((R, k), jnp.float32),
            jax.ShapeDtypeStruct((R, k), jnp.float32),
        ],
        interpret=interpret,
    )(wu, wp, cu, cp, Suu, Sup, Spp, u, p, valid)
    return acc, dls
