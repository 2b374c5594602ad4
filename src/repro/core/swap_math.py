"""Exact swap cost algebra from the paper (§2.1.3), 1-swap and k-swap.

Everything here is pure jnp and row-batched: a "row block" is `w, m, c` of
shape (R, d_in) plus the shared Gram matrix G (d_in, d_in). These functions
are the single source of truth for the swap formulas; the Pallas kernels in
``repro.kernels`` and the distributed paths reuse them (or are tested
against them).

Notation (paper Eq. 5/6):
    a_u = 2 w_u c_u + w_u^2 G_uu          ΔL contribution of *pruning* the
                                          currently-kept index u
    b_p = -2 w_p c_p + w_p^2 G_pp         ΔL contribution of *unpruning* the
                                          currently-pruned index p
    dL[u, p] = a_u + b_p - 2 w_u w_p G_up

A mask entry m_j == 1 means the weight is KEPT (unpruned), m_j == 0 pruned,
matching the paper. A swap (u, p) prunes kept index u and keeps pruned
index p, preserving the per-row sparsity level.

Two search families share those formulas:

* ``best_swap_*``  — the jointly-best single swap per row (k = 1).
* ``topk_swaps_*`` — the k best candidate pairs per row from ONE ΔL
  evaluation, amortizing the O(R·d_in²) Gram stream over up to k accepted
  swaps. Candidates are the k best *pruned* indices p by score
  ``min_u ΔL[u, p]`` (each paired with its own argmin u), sorted ascending
  with deterministic (ΔL, p, u) lexicographic tie-break — identical across
  the dense / chunked / N:M / Pallas / gram-sharded implementations, so
  every path commits the same swaps bit-for-bit.
* ``commit_swaps`` / ``commit_swaps_columns`` — greedily apply a
  candidate batch in score order, re-scoring each candidate against the
  *updated* correlation state (the true ΔL after earlier accepted swaps
  in the batch) and rejecting any that went non-improving or infeasible,
  so monotonicity and the incremental loss bookkeeping stay exact. The
  ``columns`` flavor (unstructured default) additionally re-searches the
  best u for each candidate column — O(R·d) per candidate — which is
  what sustains ~k/2 accepts per pass on correlated Grams; the
  candidate-space flavor is O(R·k²) (and runs in-kernel on TPU) and
  serves N:M, whose block search is already cheap.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

INVALID = jnp.float32(jnp.inf)  # +inf sentinel for masked-out candidates
# float32 contractions of the objective (c, row losses) run at full float32
# precision: the TPU's default computes them in one bfloat16 pass, and
# errors that size swamp the ΔL of a single swap (the loss bookkeeping
# L + ΣΔL then drifts below zero)
HIGHEST = jax.lax.Precision.HIGHEST
_BIG_I32 = jnp.int32(2**30)     # index sentinel that loses every tie-break


def correlation_vector(w: jnp.ndarray, m: jnp.ndarray, G: jnp.ndarray) -> jnp.ndarray:
    """c = G ((1 - m) ⊙ w), row-batched.

    w, m: (R, d_in); G: (d_in, d_in) -> c: (R, d_in), fp32.
    """
    wp = ((1.0 - m) * w).astype(jnp.float32)
    # G symmetric; .T keeps layout intent
    return jnp.matmul(wp, G.astype(jnp.float32).T, precision=HIGHEST)


def row_loss(w: jnp.ndarray, m: jnp.ndarray, G: jnp.ndarray) -> jnp.ndarray:
    """Exact per-row loss L = (w - m⊙w)^T G (w - m⊙w). (R,)."""
    return quad_form((1.0 - m) * w, G)


def quad_form(x: jnp.ndarray, G: jnp.ndarray) -> jnp.ndarray:
    """Per-row x_r^T G x_r for x: (R, d), G: (d, d) symmetric. (R,).

    Summed as Σ_j x_j (x G)_j from a materialized HIGHEST matmul. The
    barrier keeps the TPU compiler from fusing the multiply-reduce into
    the matmul: fused, a v5e returned -1281 for a row whose float64 loss
    is 660 (jitted einsum or matmul alike, at any precision setting),
    while the unfused sum is within 5e-7."""
    x = x.astype(jnp.float32)
    xg = jax.lax.optimization_barrier(
        jnp.matmul(x, G.astype(jnp.float32), precision=HIGHEST))
    return jnp.sum(x * xg, axis=-1)


def swap_scores(w: jnp.ndarray, m: jnp.ndarray, c: jnp.ndarray, g_diag: jnp.ndarray):
    """Per-index swap half-costs (a, b) with infeasible entries pushed to +inf.

    a[r, u]: cost term for pruning currently-kept u   (valid where m==1)
    b[r, p]: cost term for unpruning currently-pruned p (valid where m==0)
    """
    w = w.astype(jnp.float32)
    c = c.astype(jnp.float32)
    quad = (w * w) * g_diag.astype(jnp.float32)
    a = 2.0 * w * c + quad
    b = -2.0 * w * c + quad
    a = jnp.where(m > 0.5, a, INVALID)
    b = jnp.where(m > 0.5, INVALID, b)
    return a, b


def delta_matrix(w, m, c, G):
    """Full ΔL[r, u, p] matrix (reference path — O(R d_in²) memory).

    Infeasible pairs (u not kept, p not pruned) are +inf.
    """
    g_diag = jnp.diagonal(G)
    a, b = swap_scores(w, m, c, g_diag)
    w32 = w.astype(jnp.float32)
    inter = 2.0 * jnp.einsum("ru,rp,up->rup", w32, w32, G.astype(jnp.float32))
    return a[:, :, None] + b[:, None, :] - inter


def best_swap_dense(w, m, c, G):
    """Jointly-best (ΔL*, u*, p*) per row via the dense ΔL matrix.

    Returns (dl, u_idx, p_idx) with shapes (R,), (R,), (R,).
    Reference implementation; production uses the chunked/Pallas paths.
    """
    dl = delta_matrix(w, m, c, G)
    R, d, _ = dl.shape
    flat = dl.reshape(R, d * d)
    idx = jnp.argmin(flat, axis=1)
    best = jnp.take_along_axis(flat, idx[:, None], axis=1)[:, 0]
    return best, idx // d, idx % d


def best_swap_chunked(w, m, c, G, *, chunk: int = 512):
    """Memory-lean jointly-best swap: stream over p-column chunks of G.

    For each chunk of pruned candidates p, reduce over all u on the fly:
    memory O(R * chunk) instead of O(R * d_in²). Pure jnp (works on any
    backend); the Pallas kernel implements the same contraction tiled for
    VMEM.
    """
    d_in = G.shape[0]
    g_diag = jnp.diagonal(G)
    a, b = swap_scores(w, m, c, g_diag)  # (R, d)
    w32 = w.astype(jnp.float32)
    nchunks = (d_in + chunk - 1) // chunk
    pad = nchunks * chunk - d_in
    if pad:
        b = jnp.pad(b, ((0, 0), (0, pad)), constant_values=jnp.inf)
        Gp = jnp.pad(G.astype(jnp.float32), ((0, 0), (0, pad)))
        wp = jnp.pad(w32, ((0, 0), (0, pad)))
    else:
        Gp, wp = G.astype(jnp.float32), w32

    best = jnp.full((w.shape[0],), jnp.inf, jnp.float32)
    best_u = jnp.zeros((w.shape[0],), jnp.int32)
    best_p = jnp.zeros((w.shape[0],), jnp.int32)
    # fori-style python loop: nchunks is static, so this unrolls in jit.
    for ci in range(nchunks):
        sl = slice(ci * chunk, (ci + 1) * chunk)
        Gc = Gp[:, sl]                       # (d, chunk)
        # ΔL[r, u, p] for this chunk = a[r,u] + b[r,p] - 2 w_u w_p G_up
        inter = 2.0 * jnp.einsum("ru,rp,up->rup", w32, wp[:, sl], Gc)
        dl = a[:, :, None] + b[:, sl][:, None, :] - inter  # (R, d, chunk)
        flat = dl.reshape(dl.shape[0], -1)
        idx = jnp.argmin(flat, axis=1)
        val = jnp.take_along_axis(flat, idx[:, None], axis=1)[:, 0]
        u_i = (idx // chunk).astype(jnp.int32)
        p_i = (idx % chunk + ci * chunk).astype(jnp.int32)
        upd = val < best
        best = jnp.where(upd, val, best)
        best_u = jnp.where(upd, u_i, best_u)
        best_p = jnp.where(upd, p_i, best_p)
    return best, best_u, best_p


def _nm_pairs(w, m, c, G, block: int):
    """Within-block ΔL as a (block, block, R, nb) stack.

    Entry [i, j, r, b] is ΔL of the swap u = b·block + i, p = b·block + j
    in row r. Only the block-diagonal of G is read: O(d_in · block) per
    row. The block count stays on the minor axis — the TPU tiles the
    minor axis to 128 lanes, so a (..., block)-minor array costs
    128/block times its size in HBM.
    """
    R, d_in = w.shape
    nb = d_in // block
    a, b = swap_scores(w, m, c, jnp.diagonal(G))
    # (block, R, nb): row i holds in-block offset i of every block
    split = lambda x: x.reshape(R, nb, block).transpose(2, 0, 1)
    a, b, w32 = split(a), split(b), split(w.astype(jnp.float32))
    G32 = G.astype(jnp.float32)
    base = jnp.arange(nb) * block
    ii = jnp.arange(block)
    g = G32[base + ii[:, None, None], base + ii[None, :, None]]
    g = g[:, :, None, :]                                  # (i, j, 1, nb)
    return ((a[:, None] + b[None, :])
            - 2.0 * (w32[:, None] * w32[None, :]) * g)


def _interleave(x):
    """(block, R, nb) -> (R, nb · block): back to column order."""
    block, R, nb = x.shape
    return x.transpose(1, 2, 0).reshape(R, nb * block)


def best_swap_nm(w, m, c, G, *, block: int):
    """Best within-block swap for N:M sparsity (paper §2.2).

    Swaps are restricted to the same M-block, so only the block-diagonal of
    G is needed: O(d_in · block) per row instead of O(d_in²). Ties go to
    the lowest u, then the lowest p.
    """
    dl = _nm_pairs(w, m, c, G, block)
    # best p per u; argmin keeps the first index, so ties keep low p
    best = _interleave(jnp.min(dl, axis=1))              # (R, d_in) by u
    p_off = _interleave(jnp.argmin(dl, axis=1).astype(jnp.int32))
    u_i = jnp.argmin(best, axis=1).astype(jnp.int32)
    val = jnp.take_along_axis(best, u_i[:, None], axis=1)[:, 0]
    p_i = u_i - u_i % block + jnp.take_along_axis(
        p_off, u_i[:, None], axis=1)[:, 0]
    return val, u_i, p_i


# ---------------------------------------------------------------------------
# k-swap candidate search
# ---------------------------------------------------------------------------
#
# A "candidate batch" is the k best (u, p) pairs per row extracted from one
# ΔL evaluation: for every pruned index p the best kept u is found
# (``min_u ΔL[u, p]``, ties to the lowest u), then the k best p columns are
# kept (ties to the lowest p). Distinct-p candidates maximize the number of
# independently-committable swaps per batch — two candidates sharing p can
# never both be accepted. All implementations (dense / chunked / N:M /
# Pallas kernel / gram-sharded) return bit-identical candidate lists.


def _merge_topk(vals, ps, us, new_vals, new_ps, new_us, k: int):
    """Merge two per-row candidate lists, keep the k best by (ΔL, p) lex."""
    v = jnp.concatenate([vals, new_vals], axis=1)
    p = jnp.concatenate([ps, new_ps], axis=1)
    u = jnp.concatenate([us, new_us], axis=1)
    v, p, u = jax.lax.sort((v, p, u), dimension=1, num_keys=2, is_stable=True)
    return v[:, :k], p[:, :k], u[:, :k]


def topk_swaps_dense(w, m, c, G, *, k: int):
    """k best candidate swaps per row via the dense ΔL matrix.

    Returns (dl, u, p) each (R, k), sorted ascending by ΔL; rows with fewer
    than k feasible pairs pad with +inf entries (rejected at commit time).
    Reference path — O(R d_in²) memory, small d only.
    """
    g_diag = jnp.diagonal(G)
    a, b = swap_scores(w, m, c, g_diag)
    w32 = w.astype(jnp.float32)
    # explicit broadcast (not einsum): the exact multiply order the Pallas
    # kernel uses, so candidate ΔL values are bit-identical across paths
    inter = 2.0 * (w32[:, :, None] * w32[:, None, :]) * (
        G.astype(jnp.float32)[None, :, :])
    dl = a[:, :, None] + b[:, None, :] - inter  # (R, d, d) +inf infeasible
    d = dl.shape[2]
    vals_p = jnp.min(dl, axis=1)                # (R, d) best over u, per p
    u_p = jnp.argmin(dl, axis=1).astype(jnp.int32)   # ties -> lowest u
    neg, p_idx = jax.lax.top_k(-vals_p, min(k, d))   # ties -> lowest p
    u_idx = jnp.take_along_axis(u_p, p_idx, axis=1)
    return -neg, u_idx, p_idx.astype(jnp.int32)


def topk_swaps_chunked(w, m, c, G, *, k: int, chunk: int = 512):
    """k best candidate swaps per row, streaming over p-column chunks of G.

    Memory O(R·chunk) like ``best_swap_chunked``; one full G stream yields
    up to k committable swaps instead of one. Bit-identical candidate
    lists to ``topk_swaps_dense`` (same (ΔL, p, u) tie-break).
    """
    R, d_in = w.shape
    k = min(k, d_in)
    g_diag = jnp.diagonal(G)
    a, b = swap_scores(w, m, c, g_diag)         # (R, d)
    w32 = w.astype(jnp.float32)
    nchunks = (d_in + chunk - 1) // chunk
    pad = nchunks * chunk - d_in
    if pad:
        b = jnp.pad(b, ((0, 0), (0, pad)), constant_values=jnp.inf)
        Gp = jnp.pad(G.astype(jnp.float32), ((0, 0), (0, pad)))
        wp = jnp.pad(w32, ((0, 0), (0, pad)))
    else:
        Gp, wp = G.astype(jnp.float32), w32

    best_v = jnp.full((R, k), jnp.inf, jnp.float32)
    best_p = jnp.full((R, k), _BIG_I32, jnp.int32)
    best_u = jnp.zeros((R, k), jnp.int32)
    for ci in range(nchunks):                   # static: unrolls in jit
        sl = slice(ci * chunk, (ci + 1) * chunk)
        inter = 2.0 * (w32[:, :, None] * wp[:, sl][:, None, :]) * (
            Gp[:, sl][None, :, :])              # kernel multiply order
        dl = a[:, :, None] + b[:, sl][:, None, :] - inter   # (R, d, chunk)
        vals_p = jnp.min(dl, axis=1)                        # (R, chunk)
        u_p = jnp.argmin(dl, axis=1).astype(jnp.int32)
        kk = min(k, chunk)
        neg, p_loc = jax.lax.top_k(-vals_p, kk)
        u_c = jnp.take_along_axis(u_p, p_loc, axis=1)
        p_c = p_loc.astype(jnp.int32) + ci * chunk
        best_v, best_p, best_u = _merge_topk(
            best_v, best_p, best_u, -neg, p_c, u_c, k)
    return best_v, best_u, best_p


def topk_swaps_nm(w, m, c, G, *, block: int, k: int):
    """k best within-block candidate swaps for N:M sparsity.

    Same block-diagonal bands as ``best_swap_nm`` — only O(d_in·block) of
    G is touched per row. Per p the best u is the lowest on ties; the k
    best p follow ``lax.top_k`` order.
    """
    R, d_in = w.shape
    k = min(k, d_in)
    dl = _nm_pairs(w, m, c, G, block)
    # best u per p; argmin keeps the first index, so ties keep low u
    vals_p = _interleave(jnp.min(dl, axis=0))            # (R, d_in) by p
    pos = jnp.arange(d_in, dtype=jnp.int32)
    u_glob = pos - pos % block + _interleave(
        jnp.argmin(dl, axis=0).astype(jnp.int32))
    neg, p_idx = jax.lax.top_k(-vals_p, k)
    u_idx = jnp.take_along_axis(u_glob, p_idx, axis=1)
    return -neg, u_idx, p_idx.astype(jnp.int32)


# ---------------------------------------------------------------------------
# k-swap commit: greedy apply with exact re-scoring in candidate space
# ---------------------------------------------------------------------------


def gather_candidate_stats(w, c, G, u, p):
    """Gather the per-candidate inputs the commit decision loop needs.

    w, c: (R, d); G: (d, d); u, p: (R, k) int32. Returns
    (wu, wp, cu, cp, Suu, Sup, Spp) where S** are the (R, k, k) candidate
    sub-Grams  Suu[i, j] = G[u_i, u_j],  Sup[i, j] = G[u_i, p_j],
    Spp[i, j] = G[p_i, p_j] — everything the sequential re-scoring touches,
    O(R·k²) instead of O(R·d²).
    """
    w32 = w.astype(jnp.float32)
    G32 = G.astype(jnp.float32)
    wu = jnp.take_along_axis(w32, u, axis=1)
    wp = jnp.take_along_axis(w32, p, axis=1)
    cu = jnp.take_along_axis(c, u, axis=1)
    cp = jnp.take_along_axis(c, p, axis=1)
    Suu = G32[u[:, :, None], u[:, None, :]]
    Sup = G32[u[:, :, None], p[:, None, :]]
    Spp = G32[p[:, :, None], p[:, None, :]]
    return wu, wp, cu, cp, Suu, Sup, Spp


def commit_decisions(wu, wp, cu, cp, Suu, Sup, Spp, u, p, valid, *,
                     eps: float, k: int):
    """Sequential greedy accept/reject over a candidate batch, in candidate
    space only (no O(d) state touched).

    Candidates are visited in list order (ascending searched ΔL). Each is
    re-scored against the correlation values updated by every *earlier
    accepted* swap in the batch — the true ΔL of applying it now — and
    accepted iff it is still feasible (its u not yet pruned, its p not yet
    unpruned by this batch) and still improving (ΔL < -eps). Because the u
    candidates come from the originally-kept set and the p candidates from
    the originally-pruned set, feasibility reduces to index-collision
    checks within the batch.

    Pure jnp on (R, k)-shaped values — shared verbatim by the single-device
    commit, the gram-sharded commit (on a psum-built sub-Gram) and the
    Pallas commit kernel, which keeps every path bit-identical.

    Returns (acc, dls): acc (R, k) float 0/1 accept flags, dls (R, k)
    exact re-scored ΔL (0 where rejected).
    """
    u_dead = jnp.zeros_like(wu)
    p_dead = jnp.zeros_like(wp)
    accs, dls = [], []
    # every op below keeps a (R, 1) or (R, k) shape — the loop body is
    # executed verbatim inside the Pallas commit kernel (kernels/swap_topk)
    for t in range(k):                           # k static: unrolled
        wu_t, wp_t = wu[:, t:t + 1], wp[:, t:t + 1]
        suu_t = Suu[:, :, t]                     # (R, k) column t
        sup_col_t = Sup[:, :, t]
        sup_row_t = Sup[:, t, :]
        spp_t = Spp[:, :, t]
        a_t = 2.0 * wu_t * cu[:, t:t + 1] + (wu_t * wu_t) * suu_t[:, t:t + 1]
        b_t = (-2.0 * wp_t * cp[:, t:t + 1]
               + (wp_t * wp_t) * spp_t[:, t:t + 1])
        dl_t = a_t + b_t - 2.0 * (wu_t * wp_t) * sup_col_t[:, t:t + 1]
        ok = ((valid[:, t:t + 1] > 0.5) & (u_dead[:, t:t + 1] < 0.5)
              & (p_dead[:, t:t + 1] < 0.5) & (dl_t < -eps))
        okf = ok.astype(jnp.float32)             # (R, 1)
        # Eq. 6 restricted to candidate positions:
        #   c[u_j] += w_u G[u_j, u_t] - w_p G[u_j, p_t]
        #   c[p_j] += w_u G[u_t, p_j] - w_p G[p_j, p_t]
        cu = cu + okf * (wu_t * suu_t - wp_t * sup_col_t)
        cp = cp + okf * (wu_t * sup_row_t - wp_t * spp_t)
        u_dead = jnp.maximum(
            u_dead, okf * (u == u[:, t:t + 1]).astype(jnp.float32))
        p_dead = jnp.maximum(
            p_dead, okf * (p == p[:, t:t + 1]).astype(jnp.float32))
        accs.append(okf)
        dls.append(jnp.where(ok, dl_t, 0.0))
    return jnp.concatenate(accs, axis=1), jnp.concatenate(dls, axis=1)


def apply_commits(w, m, c, G, acc, dls, u, p):
    """Apply a decided candidate batch: mask flips + full-width Eq. 6.

    acc, dls: ``commit_decisions`` output. One rank-1 c-update per accepted
    swap — O(accepted·R·d) gather bytes, amortized against the O(R·d²)
    search that produced the batch. Returns (m', c', dl_sum, n_accepted).
    """
    R, k = acc.shape
    w32 = w.astype(jnp.float32)
    G32 = G.astype(jnp.float32)

    # a loop, not an unroll: each step's two gathered (R, d) columns die
    # before the next step's are built
    def body(t, mc):
        m, c = mc
        sel = acc[:, t][:, None]
        u_t, p_t = u[:, t], p[:, t]
        wu_t = jnp.take_along_axis(w32, u_t[:, None], axis=1)
        wp_t = jnp.take_along_axis(w32, p_t[:, None], axis=1)
        gu = G32[:, u_t].T                       # (R, d) columns G_{:, u_t}
        gp = G32[:, p_t].T
        c = c + sel * (wu_t * gu - wp_t * gp)
        flip = (jax.nn.one_hot(p_t, m.shape[1], dtype=m.dtype)
                - jax.nn.one_hot(u_t, m.shape[1], dtype=m.dtype))
        return m + sel.astype(m.dtype) * flip, c

    m, c = jax.lax.fori_loop(0, k, body, (m, c))
    return m, c, jnp.sum(dls, axis=1), jnp.sum(acc, axis=1).astype(jnp.int32)


def commit_swaps_columns(w, m, c, G, dl, p_idx, *, eps: float = 0.0):
    """Greedily commit the k best candidate COLUMNS per row, re-pairing u.

    The production unstructured commit. ``p_idx`` (R, k): the stale
    search's top-k pruned columns (ascending stale ΔL; ``dl`` is only
    consulted for validity of the +inf tail). For each column in order,
    the best kept u is re-searched EXACTLY against the current (m, c) —
    an O(R·d) column-restricted argmin, d/k× cheaper than the full
    search — so a candidate whose stale pairing died from an earlier
    accept in the batch re-pairs instead of being discarded. Accepted iff
    the column is still pruned and the re-scored ΔL < -eps; every accept
    applies the exact Eq. 6 rank-1 update before the next candidate.

    Deeper per-pass chains than the candidate-space ``commit_swaps``
    (whose re-scoring can only reject): on correlated Grams this is the
    difference between ~1.5 and ~k/2 accepted swaps per O(R·d²) search.

    If a pass accepts nothing, candidate 0 — the stale global argmin,
    re-scored against an unchanged state — was non-improving, so the row
    is a certified 1-swap fixed point; convergence detection is exactly
    the 1-swap loop's.

    Returns (m', c', dl_sum (R,), n_accepted (R,) int32).
    """
    R, k = p_idx.shape
    d_in = w.shape[1]
    w32 = w.astype(jnp.float32)
    G32 = G.astype(jnp.float32)
    c = c.astype(jnp.float32)
    g_diag = jnp.diagonal(G32)
    valid = jnp.isfinite(dl)
    p_idx = jnp.clip(p_idx, 0, d_in - 1)
    rows = jnp.arange(R)
    dsum = jnp.zeros(R, jnp.float32)
    nacc = jnp.zeros(R, jnp.int32)
    for t in range(k):                           # static unroll, k small
        pt = p_idx[:, t]
        gcol = G32[:, pt].T                                  # (R, d)
        wpt = jnp.take_along_axis(w32, pt[:, None], 1)[:, 0]
        cpt = jnp.take_along_axis(c, pt[:, None], 1)[:, 0]
        b_t = -2.0 * wpt * cpt + (wpt * wpt) * g_diag[pt]    # (R,)
        a = 2.0 * w32 * c + (w32 * w32) * g_diag[None, :]
        a = jnp.where(m > 0.5, a, INVALID)
        dl_u = a + b_t[:, None] - 2.0 * (w32 * wpt[:, None]) * gcol
        ui = jnp.argmin(dl_u, axis=1)                        # ties -> low u
        dl_t = jnp.take_along_axis(dl_u, ui[:, None], 1)[:, 0]
        still_pruned = jnp.take_along_axis(m, pt[:, None], 1)[:, 0] < 0.5
        ok = (dl_t < -eps) & still_pruned & valid[:, t] & jnp.isfinite(dl_t)
        okf = ok.astype(jnp.float32)[:, None]
        wut = jnp.take_along_axis(w32, ui[:, None], 1)
        gu = G32[:, ui].T
        c = c + okf * (wut * gu - wpt[:, None] * gcol)
        m = m.at[rows, ui].set(jnp.where(ok, 0.0, m[rows, ui]))
        m = m.at[rows, pt].set(jnp.where(ok, 1.0, m[rows, pt]))
        dsum = dsum + jnp.where(ok, dl_t, 0.0)
        nacc = nacc + ok.astype(jnp.int32)
    return m, c, dsum, nacc


def commit_swaps(w, m, c, G, dl, u_idx, p_idx, *, eps: float = 0.0):
    """Greedily commit a k-candidate batch per row in candidate space.

    dl, u_idx, p_idx: a ``topk_swaps_*`` candidate list, ascending by ΔL
    (+inf = no candidate). Candidates are re-scored in order against the
    correlation state updated by earlier accepts in the batch (the true
    ΔL of each swap as applied), and any that turned non-improving or
    infeasible are rejected — the loss decrease is exact and monotone,
    up to k swaps per O(R·d²) search. The sequential loop runs entirely
    in O(R·k²) candidate space (``commit_decisions`` — also available
    in-kernel, ``kernels.swap_topk.swap_commit_padded``); this is the
    N:M commit and the cheap unstructured variant, while
    ``commit_swaps_columns`` (which re-pairs u per candidate) is the
    unstructured default.

    Returns (m', c', dl_sum (R,), n_accepted (R,) int32).
    """
    k = dl.shape[1]
    c = c.astype(jnp.float32)
    valid = jnp.isfinite(dl).astype(jnp.float32)
    # +inf-padded candidates carry an out-of-range index sentinel from the
    # kernel path; clamp for the gathers (they are masked out by `valid`)
    d_in = w.shape[1]
    u_idx = jnp.clip(u_idx, 0, d_in - 1)
    p_idx = jnp.clip(p_idx, 0, d_in - 1)
    wu, wp, cu, cp, Suu, Sup, Spp = gather_candidate_stats(w, c, G, u_idx,
                                                           p_idx)
    acc, dls = commit_decisions(wu, wp, cu, cp, Suu, Sup, Spp, u_idx, p_idx,
                                valid, eps=eps, k=k)
    return apply_commits(w, m, c, G, acc, dls, u_idx, p_idx)


def apply_swap(w, m, c, G, dl, u_idx, p_idx, *, eps: float = 0.0):
    """Apply accepted swaps row-batched; rows with dl >= -eps are no-ops.

    Returns (m', c', accepted) — Eq. 6 correlation update:
        c ← c + w_u G_{:,u} − w_p G_{:,p}
    """
    accepted = dl < -eps
    R, d_in = m.shape
    rows = jnp.arange(R)
    G32 = G.astype(jnp.float32)
    gu = G32[:, u_idx].T  # (R, d_in) columns G_{:, u*}
    gp = G32[:, p_idx].T
    wu = jnp.take_along_axis(w, u_idx[:, None], axis=1)[:, 0].astype(jnp.float32)
    wp = jnp.take_along_axis(w, p_idx[:, None], axis=1)[:, 0].astype(jnp.float32)
    c_new = c + wu[:, None] * gu - wp[:, None] * gp
    m_new = m.at[rows, u_idx].set(0.0).at[rows, p_idx].set(1.0)
    acc = accepted[:, None]
    return (
        jnp.where(acc, m_new, m),
        jnp.where(acc, c_new, c),
        accepted,
    )
