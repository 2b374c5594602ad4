"""Distributed SparseSwaps: the paper's row parallelism on the mesh.

Two regimes (DESIGN §2):

* ``refine_rows_sharded`` — rows of W sharded over the flattened mesh
  axes, G REPLICATED. Zero communication inside the swap loop (rows are
  independent, paper §2.2); the refined masks come back sharded exactly
  like the weights. Default whenever ``d_in²·4B`` fits per-device HBM.

* ``refine_g_sharded`` — for layers whose Gram can't be replicated
  (granite-34b down-proj d_in=24576: G is 2.4GB fp32). G is column-
  sharded (G symmetric, so column shard == row shard); the correlation
  vector c lives SHARDED (R, cols-per-device). Each iteration:
    1. all-gather c (the only O(R·d_in) exchange) -> full a_u scores;
    2. each device scores (all u × its owned p) with its G columns;
    3. all-gather of per-device (ΔL*, u*, p*) + deterministic min-combine
       picks the global winner (O(R) scalars);
    4. Eq. 6 update touches only LOCAL slices: c_own += w_u·G[own, u*]
       − w_p·G[own, p*], and G[own, j] = g_cols[j, :] by symmetry.
  Per-iteration comm O(R·d_in) vs compute O(R·d_in²/P): the exchange is
  1/d_in of the math — ICI-negligible at LLM widths.

Both paths match the single-device reference bit-exactly (same
deterministic tie-break); tested in tests/test_distributed.py. Both
regimes also run the amortized k-swap step (``k_swaps > 1``): rows-sharded
trivially (rows are independent), gram-sharded via a distributed top-k
merge + the column-rescored commit with O(R)-scalar exchanges per
candidate — see ``refine_g_sharded``.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import masks as masks_lib
from repro.core import swap_math as sm


def _flat_axes(mesh: Mesh) -> tuple[str, ...]:
    return tuple(mesh.axis_names)


def refine_rows_sharded(W, G, mask_init, pattern: masks_lib.Pattern, mesh,
                        *, t_max: int = 50, eps: float = 0.0,
                        chunk: int = 512, use_kernel: bool = False,
                        k_swaps: int = 1):
    """Row-sharded refinement: W rows over every mesh axis, G replicated.

    ``k_swaps > 1`` runs the k-swap step (top-k search + greedy exact
    commit, ``core.sparseswaps._swap_step``) per device — rows are
    independent, so the sharded masks stay bit-identical to the
    single-device loop at the same k. Zero communication inside the loop
    either way. Returns (mask, loss_init, loss_final); rows must divide
    the device count (pad upstream if needed).
    """
    from repro.core import sparseswaps as ss

    axes = _flat_axes(mesh)
    block = pattern.block(W.shape[1])
    method = "pallas" if use_kernel else "chunked"

    @partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P(axes, None), P(None, None), P(axes, None)),
        out_specs=(P(axes, None), P(axes), P(axes)),
        check_vma=False,
    )
    def run(w, g, m0):
        c0 = sm.correlation_vector(w, m0, g)
        l0 = sm.row_loss(w, m0, g)
        swaps0 = jnp.zeros(w.shape[0], jnp.int32)

        def body(state, _):
            m, c, loss, swaps = state
            m, c, loss, swaps, _ = ss._swap_step(
                w, m, c, loss, swaps, g, eps=eps, method=method,
                block=block, chunk=chunk, k_swaps=k_swaps)
            return (m, c, loss, swaps), None

        (m, _, loss, _), _ = jax.lax.scan(body, (m0, c0, l0, swaps0), None,
                                          length=t_max)
        return m, l0, loss

    return run(W.astype(jnp.float32), G.astype(jnp.float32),
               mask_init.astype(jnp.float32))


def refine_g_sharded(W, G, mask_init, pattern: masks_lib.Pattern, mesh,
                     *, t_max: int = 50, eps: float = 0.0,
                     unroll: bool = False, row_axes: tuple = (),
                     col_axes: tuple | None = None, k_swaps: int = 1):
    """Column-sharded-G refinement for d_in too large to replicate.

    ``col_axes`` shard G's columns (and the correlation state); the
    optional ``row_axes`` ADDITIONALLY shard W's rows — the 2-D prune
    mesh (rows x gram-columns), a beyond-paper scheme that removes the
    row-redundant scoring of plain G-sharding: with rows over "data" and
    columns over "model", per-device work drops by the full device count
    while comm stays O(R_loc * d_in) on the column axis only (§Perf
    cell C, iteration 3).

    ``k_swaps > 1`` distributes the k-swap step: each device extracts its
    local top-k candidate columns (ΔL keyed, same tie-break as
    ``swap_math.topk_swaps_chunked``), an all-gather + lexicographic sort
    merges them into the global top-k, and the column-rescored commit
    (``swap_math.commit_swaps_columns`` semantics) runs with O(R)-scalar
    exchanges per candidate: one psum for c[p_t], one all-gather for the
    (ΔL*, u*) min-combine. All O(R·d_in) state stays sharded; masks are
    bit-identical to the single-device k-swap loop (G symmetric, so
    ``g_cols[j, :]`` IS the j-th column slice every update needs).
    """
    axes = tuple(col_axes) if col_axes is not None else _flat_axes(mesh)
    n_dev = 1
    for a in axes:
        n_dev *= mesh.shape[a]
    d_in = G.shape[0]
    assert d_in % n_dev == 0, (d_in, n_dev)
    cols = d_in // n_dev
    if pattern.block(d_in) is not None:
        raise NotImplementedError("N:M swaps are within-block (block-diag G "
                                  "path) — G-sharding targets unstructured")

    row_spec = tuple(row_axes) if row_axes else None

    @partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P(row_spec, None), P(None, axes), P(row_spec, None),
                  P(None)),
        out_specs=(P(row_spec, None), P(row_spec), P(row_spec)),
        check_vma=False,
    )
    def run(w, g_cols, m0, g_diag):
        R = w.shape[0]
        idx = 0
        for ax in axes:                     # flattened linear device index
            idx = idx * mesh.shape[ax] + jax.lax.axis_index(ax)
        start = idx * cols
        # c = G @ wp  =>  c_own = G[own, :] @ wp; by symmetry
        # G[own, j] = G[j, own] = g_cols[j, :], so c_own = wp @ g_cols.
        c_own0 = jnp.matmul((1.0 - m0) * w, g_cols,
                            precision=sm.HIGHEST)              # (R, cols)
        c_full0 = _gather_cols(c_own0, axes)                   # (R, d)
        l0 = jnp.sum(((1.0 - m0) * w) * c_full0, axis=1)

        def own_gather(x_own, pos):
            """x_own (R, cols) at global column ``pos`` (R,) -> (R,),
            psum-combined (exactly one device owns each position)."""
            loc = jnp.clip(pos - start, 0, cols - 1)
            val = jnp.take_along_axis(x_own, loc[:, None], 1)[:, 0]
            mine = (pos >= start) & (pos < start + cols)
            return jax.lax.psum(jnp.where(mine, val, 0.0), axes)

        def kswap_body(state, _):
            m, c_own, loss = state
            g_diag_own = jax.lax.dynamic_slice(g_diag, (start,), (cols,))
            # -- search: local per-p best-u scores over owned columns ----
            c_full = _gather_cols(c_own, axes)                  # (R, d)
            a, b = sm.swap_scores(w, m, c_full, g_diag)
            b_own = jax.lax.dynamic_slice(b, (0, start), (R, cols))
            w_own = jax.lax.dynamic_slice(w, (0, start), (R, cols))
            inter = 2.0 * (w[:, :, None] * w_own[:, None, :]) * (
                g_cols[None, :, :])
            dl = a[:, :, None] + b_own[:, None, :] - inter      # (R, d, cols)
            vals_p = jnp.min(dl, axis=1)                        # (R, cols)
            kk = min(k_swaps, cols)
            neg, p_loc = jax.lax.top_k(-vals_p, kk)             # ties: low p
            cand_v = -neg
            cand_p = p_loc.astype(jnp.int32) + start
            # -- merge to the global top-k by (ΔL, p) — p's are unique ---
            all_v = _gather_cols(cand_v, axes)                  # (R, P*kk)
            all_p = _gather_cols(cand_p, axes)
            all_v, all_p = jax.lax.sort((all_v, all_p), dimension=1,
                                        num_keys=2)
            top_v, top_p = all_v[:, :k_swaps], all_p[:, :k_swaps]
            # -- column-rescored greedy commit (k static, unrolled) ------
            rows_i = jnp.arange(R)
            for t in range(k_swaps):
                pt = top_p[:, t]
                gcol_own = jnp.take(g_cols, pt, axis=0)         # G[pt, own]
                wpt = jnp.take_along_axis(w, pt[:, None], 1)[:, 0]
                cpt = own_gather(c_own, pt)
                b_t = -2.0 * wpt * cpt + (wpt * wpt) * g_diag[pt]
                m_own = jax.lax.dynamic_slice(m, (0, start), (R, cols))
                a_own = (2.0 * w_own * c_own
                         + (w_own * w_own) * g_diag_own[None, :])
                a_own = jnp.where(m_own > 0.5, a_own, jnp.inf)
                dl_u = (a_own + b_t[:, None]
                        - 2.0 * (w_own * wpt[:, None]) * gcol_own)
                u_loc = jnp.argmin(dl_u, axis=1)
                dl_t = jnp.take_along_axis(dl_u, u_loc[:, None], 1)[:, 0]
                u_glob = u_loc.astype(jnp.int32) + start
                # global (ΔL, u) lexicographic min-combine
                av = jax.lax.all_gather(dl_t, axes)
                au = jax.lax.all_gather(u_glob, axes)
                av = jnp.moveaxis(av.reshape(-1, R), 0, 1)      # (R, P)
                au = jnp.moveaxis(au.reshape(-1, R), 0, 1)
                vmin = jnp.min(av, axis=1)
                big = jnp.int32(2**30)
                u_w = jnp.min(jnp.where(av == vmin[:, None], au, big),
                              axis=1)
                still_pruned = jnp.take_along_axis(
                    m, pt[:, None], 1)[:, 0] < 0.5
                ok = ((vmin < -eps) & still_pruned
                      & jnp.isfinite(top_v[:, t]) & jnp.isfinite(vmin))
                okf = ok.astype(jnp.float32)[:, None]
                wut = jnp.take_along_axis(w, u_w[:, None], 1)
                gu_own = jnp.take(g_cols, u_w, axis=0)          # G[u*, own]
                c_own = c_own + okf * (wut * gu_own
                                       - wpt[:, None] * gcol_own)
                m = m.at[rows_i, u_w].set(jnp.where(ok, 0.0,
                                                    m[rows_i, u_w]))
                m = m.at[rows_i, pt].set(jnp.where(ok, 1.0,
                                                   m[rows_i, pt]))
                loss = loss + jnp.where(ok, vmin, 0.0)
            return (m, c_own, loss), None

        def body(state, _):
            m, c_own, loss = state
            c_full = _gather_cols(c_own, axes)                  # (R, d)
            a, b = sm.swap_scores(w, m, c_full, g_diag)
            b_own = jax.lax.dynamic_slice(b, (0, start), (R, cols))
            w_own = jax.lax.dynamic_slice(w, (0, start), (R, cols))
            inter = 2.0 * jnp.einsum("ru,rp,up->rup", w, w_own, g_cols)
            dl = a[:, :, None] + b_own[:, None, :] - inter      # (R, d, cols)
            flat = dl.reshape(R, -1)
            loc = jnp.argmin(flat, axis=1)
            val = jnp.take_along_axis(flat, loc[:, None], 1)[:, 0]
            u_i = (loc // cols).astype(jnp.int32)
            p_i = (loc % cols).astype(jnp.int32) + start
            # deterministic global min combine (value, then flat index)
            all_val = jax.lax.all_gather(val, axes)             # (P, R)
            all_u = jax.lax.all_gather(u_i, axes)
            all_p = jax.lax.all_gather(p_i, axes)
            # lexicographic (val, u, p) min — int32-exact at any d_in
            big = jnp.int32(2**30)
            vmin = jnp.min(all_val, 0, keepdims=True)
            tie_u = jnp.where(all_val == vmin, all_u, big)
            umin = jnp.min(tie_u, 0, keepdims=True)
            tie_p = jnp.where((all_val == vmin) & (all_u == umin), all_p, big)
            win = jnp.argmin(tie_p, axis=0)
            dl_w = jnp.take_along_axis(all_val, win[None], 0)[0]
            u_w = jnp.take_along_axis(all_u, win[None], 0)[0]
            p_w = jnp.take_along_axis(all_p, win[None], 0)[0]
            # Eq. 6 on the local slice: G[own, j] = g_cols[j, :]
            gu_own = jnp.take(g_cols, u_w, axis=0)              # (R, cols)
            gp_own = jnp.take(g_cols, p_w, axis=0)
            wu = jnp.take_along_axis(w, u_w[:, None], 1)[:, 0]
            wp = jnp.take_along_axis(w, p_w[:, None], 1)[:, 0]
            acc = dl_w < -eps
            rows = jnp.arange(R)
            m_new = m.at[rows, u_w].set(0.0).at[rows, p_w].set(1.0)
            c_new = c_own + wu[:, None] * gu_own - wp[:, None] * gp_own
            m = jnp.where(acc[:, None], m_new, m)
            c_own = jnp.where(acc[:, None], c_new, c_own)
            loss = jnp.where(acc, loss + dl_w, loss)
            return (m, c_own, loss), None

        (m, _, loss), _ = jax.lax.scan(
            kswap_body if k_swaps > 1 else body, (m0, c_own0, l0), None,
            length=t_max, unroll=True if unroll else 1)
        return m, l0, loss

    g_diag = jnp.diagonal(G).astype(jnp.float32)
    return run(W.astype(jnp.float32), G.astype(jnp.float32),
               mask_init.astype(jnp.float32), g_diag)


def _gather_cols(x_own, axes):
    """(R, cols) per-device -> (R, d) replicated, preserving column order."""
    g = jax.lax.all_gather(x_own, axes, tiled=False)   # (P, R, cols)
    if g.ndim == 3:
        return jnp.moveaxis(g, 0, 1).reshape(x_own.shape[0], -1)
    # nested gather over multiple axes: leading dims are per-axis
    lead = int(jnp.prod(jnp.array(g.shape[:-2])))
    g = g.reshape(lead, *x_own.shape)
    return jnp.moveaxis(g, 0, 1).reshape(x_own.shape[0], -1)


def prune_refine_step_fn(pattern, mesh, *, t_max: int = 10):
    """Dry-run lowering unit for the paper's technique (§Perf):
    (W, G, M0) -> (M, l0, l1), rows sharded across the whole mesh."""

    def step(W, G, M0):
        return refine_rows_sharded(W, G, M0, pattern, mesh, t_max=t_max)

    return step
