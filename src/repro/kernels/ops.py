"""Jit'd public wrappers around the Pallas kernels.

Each op handles padding/layout and falls back to the pure-jnp reference
path on non-TPU backends (the kernels themselves are validated on CPU via
``interpret=True`` in tests; production CPU paths use the chunked jnp
implementations which XLA fuses well).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import swap_math as sm

from . import ref as ref_lib
from .gram import gram_xtx_padded
from .swap_argmin import swap_argmin_padded
from .swap_topk import swap_commit_padded, swap_topk_padded


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def swap_argmin(
    w: jnp.ndarray,
    m: jnp.ndarray,
    c: jnp.ndarray,
    G: jnp.ndarray,
    *,
    row_block: int = 16,
    tile: int = 256,
    interpret: bool | None = None,
):
    """Jointly-best 1-swap per row: (ΔL*, u*, p*) each (R,).

    Computes the per-index half-costs a/b in jnp (O(R·d)), then runs the
    fused tiled argmin kernel over G. Pads R to the row block and d to the
    tile size (padded entries are +inf-masked so they never win).
    """
    if interpret is None:
        interpret = not _on_tpu()
    R, d = w.shape
    a, b, w32, G32, tile = _pad_swap_inputs(w, m, c, G, row_block, tile)
    best, u, p = swap_argmin_padded(
        a, b, w32, G32, row_block=row_block, tile_u=tile, tile_p=tile,
        interpret=interpret,
    )
    return best[:R], u[:R], p[:R]


def _pad_swap_inputs(w, m, c, G, row_block: int, tile: int):
    """Shared a/b scoring + padding for the swap-search kernels."""
    R, d = w.shape
    g_diag = jnp.diagonal(G)
    a, b = sm.swap_scores(w, m, c, g_diag)
    tile = min(tile, _round_up(d, 128))
    Rp = _round_up(R, row_block)
    dp = _round_up(d, tile)
    w32 = w.astype(jnp.float32)
    G32 = G.astype(jnp.float32)
    if (Rp, dp) != (R, d):
        a = jnp.pad(a, ((0, Rp - R), (0, dp - d)), constant_values=jnp.inf)
        b = jnp.pad(b, ((0, Rp - R), (0, dp - d)), constant_values=jnp.inf)
        w32 = jnp.pad(w32, ((0, Rp - R), (0, dp - d)))
        G32 = jnp.pad(G32, ((0, dp - d), (0, dp - d)))
    return a, b, w32, G32, tile


def _lane_tile(dp: int, cap: int) -> int:
    """Largest multiple of 128 lanes that divides ``dp``, at most ``cap``."""
    return max(t for t in range(128, min(cap, dp) + 1, 128) if dp % t == 0)


def topk_tiles(d: int) -> tuple[int, int]:
    """(u, p) tiles of the fused top-k search for inputs of width d.

    d pads as for the argmin kernel (to 256, or 128 below that) and never
    further: the tiles are the widest lane multiples that divide the
    padded width, so fewer grid steps never cost padded work. A long
    u-tile amortises each row's sublane fold; 1024 bounds its spreads in
    VMEM. A p-tile is one sweep a row, its running state in registers up
    to 512 lanes; on a v5e that beat wider p-tiles swept in chunks.
    """
    dp = _round_up(d, min(256, _round_up(d, 128)))
    return _lane_tile(dp, 1024), _lane_tile(dp, 512)


def swap_topk(
    w: jnp.ndarray,
    m: jnp.ndarray,
    c: jnp.ndarray,
    G: jnp.ndarray,
    *,
    k: int,
    row_block: int = 8,
    interpret: bool | None = None,
):
    """k best candidate swaps per row: (ΔL, u, p) each (R, k), fused.

    One tiled pass over G (VMEM-resident per-row top-k lists, see
    ``kernels.swap_topk``) instead of k argmin launches. Candidate order
    and tie-break match ``swap_math.topk_swaps_*`` bit-for-bit on feasible
    entries; +inf-padded tail entries are clamped into range (and rejected
    by ``commit_swaps`` via the +inf ΔL).
    """
    if interpret is None:
        interpret = not _on_tpu()
    R, d = w.shape
    tile_u, tile_p = topk_tiles(d)
    a, b, w32, G32, _ = _pad_swap_inputs(w, m, c, G, row_block, 256)
    # the kernel scores (a + b) + (c·w_p)·g with c = -2·w: doubling and
    # negation are exact, so this is a + b - 2·(w_u·w_p)·g bit for bit
    vals, u, p = swap_topk_padded(
        a, b, -2.0 * w32, w32, G32, k=k, row_block=row_block, tile_u=tile_u,
        tile_p=tile_p, interpret=interpret,
    )
    return (vals[:R], jnp.minimum(u[:R], d - 1), jnp.minimum(p[:R], d - 1))


def swap_topk_commit(
    w: jnp.ndarray,
    m: jnp.ndarray,
    c: jnp.ndarray,
    G: jnp.ndarray,
    *,
    k: int,
    eps: float = 0.0,
    row_block: int = 8,
    interpret: bool | None = None,
):
    """One fused k-swap refinement step on the Pallas path.

    Search (``swap_topk`` kernel) -> candidate sub-Gram gather (O(R·k²))
    -> in-kernel greedy commit decisions (``swap_commit_padded``, runs
    ``swap_math.commit_decisions`` verbatim) -> full-width Eq. 6 apply.
    Returns (m', c', dl_sum (R,), n_accepted (R,)) exactly like
    ``swap_math.commit_swaps``, and bit-identical to it given the same
    candidates.
    """
    if interpret is None:
        interpret = not _on_tpu()
    R = w.shape[0]
    dl, u, p = swap_topk(w, m, c, G, k=k, row_block=row_block,
                         interpret=interpret)
    c32 = c.astype(jnp.float32)
    valid = jnp.isfinite(dl).astype(jnp.float32)
    wu, wp, cu, cp, Suu, Sup, Spp = sm.gather_candidate_stats(
        w, c32, G, u, p)
    Rp = _round_up(R, row_block)
    if Rp != R:
        padk = ((0, Rp - R), (0, 0))
        padc = ((0, Rp - R), (0, 0), (0, 0))
        wu, wp, cu, cp = (jnp.pad(x, padk) for x in (wu, wp, cu, cp))
        Suu, Sup, Spp = (jnp.pad(x, padc) for x in (Suu, Sup, Spp))
        u, p = (jnp.pad(x, padk) for x in (u, p))
        valid = jnp.pad(valid, padk)         # 0 = pad rows never accept
    acc, dls = swap_commit_padded(wu, wp, cu, cp, Suu, Sup, Spp, u, p,
                                  valid, eps=eps, k=k, row_block=row_block,
                                  interpret=interpret)
    return sm.apply_commits(w, m, c32, G, acc[:R], dls[:R], u[:R], p[:R])


def gram_xtx(
    x: jnp.ndarray,
    *,
    tile: int = 256,
    tile_k: int = 512,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Xᵀ X (fp32) for activations x: (..., tokens, d)."""
    if interpret is None:
        interpret = not _on_tpu()
    x2 = x.reshape(-1, x.shape[-1])
    T, d = x2.shape
    tile = min(tile, _round_up(d, 128))
    tk = min(tile_k, _round_up(T, 128))
    Tp, dp = _round_up(T, tk), _round_up(d, tile)
    if (Tp, dp) != (T, d):
        x2 = jnp.pad(x2, ((0, Tp - T), (0, dp - d)))
    out = gram_xtx_padded(x2, tile_i=tile, tile_j=tile, tile_k=tk, interpret=interpret)
    return out[:d, :d]


def gram_update(G: jnp.ndarray, x: jnp.ndarray, **kw) -> jnp.ndarray:
    """Streaming G += Xᵀ X using the kernel for the chunk product."""
    return G.astype(jnp.float32) + gram_xtx(x, **kw)


def gram_xtx_stacked(x: jnp.ndarray, **kw) -> jnp.ndarray:
    """Per-slice XᵀX for x: (N, ..., tokens, d) -> (N, d, d) fp32.

    The MoE calibration path: one Gram per expert over that expert's
    capacity buffer (zero-padded slots contribute zero). vmapping the
    padded Pallas kernel keeps each slice's tiling identical, so the grid
    is compiled once and batched.
    """
    N = x.shape[0]
    return jax.vmap(lambda xi: gram_xtx(xi, **kw))(
        x.reshape(N, -1, x.shape[-1]))
