"""k-swap refinement: candidate search, exact commit, compaction, guard.

The amortized-search engine (core.swap_math ``topk_swaps_*`` +
``commit_swaps``/``commit_swaps_columns``, threaded through
``core.sparseswaps``): one O(R·d²) ΔL evaluation commits up to k exact,
monotone swaps. These tests pin the contract:

* candidate lists are bit-identical across the dense / chunked / Pallas
  (interpret) searches, and k = 1 degenerates to the jointly-best swap;
* both commit flavors are exact — the tracked ΔL equals the directly
  recomputed loss delta and the incremental c matches recomputation;
* at the same search-pass budget, k-swap never ends above the 1-swap
  loss, and every converged k-swap mask is a certified 1-swap fixed
  point (brute force, all backends including N:M);
* active-row compaction is bit-identical to the uncompacted loop;
* the counted-search-pass perf guard: on the weakly-correlated smoke
  config, k-swap reaches the brute-force fixed point within
  ceil(max-row-swaps / k) + 2 passes — the ≥2× amortization claim, as a
  deterministic count, not wall-clock.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from test_swap_optimal import _brute_force, _problem, _row_loss_np

from repro.core import masks as masks_lib
from repro.core import sparseswaps
from repro.core import swap_math as sm
from repro.kernels import ops as kops


def _cands(seed=0, R=8, d_in=24, keep=12, corr=0.5):
    W, G, m = _problem(seed, R, d_in, keep, corr=corr)
    W, G, m = jnp.asarray(W), jnp.asarray(G), jnp.asarray(m)
    c = sm.correlation_vector(W, m, G)
    return W, G, m, c


# ---------------------------------------------------------------------------
# candidate search
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("k", [1, 3, 8])
def test_topk_dense_chunked_kernel_agree(k):
    W, G, m, c = _cands(seed=3, R=8, d_in=24, keep=12)
    vd, ud, pd = sm.topk_swaps_dense(W, m, c, G, k=k)
    for chunk in (5, 8, 24):
        vc, uc, pc = sm.topk_swaps_chunked(W, m, c, G, k=k, chunk=chunk)
        assert np.array_equal(np.asarray(vd), np.asarray(vc)), chunk
        assert np.array_equal(np.asarray(ud), np.asarray(uc)), chunk
        assert np.array_equal(np.asarray(pd), np.asarray(pc)), chunk
    vk, uk, pk = kops.swap_topk(W, m, c, G, k=k, interpret=True)
    fin = np.isfinite(np.asarray(vd))
    np.testing.assert_allclose(np.asarray(vk)[fin], np.asarray(vd)[fin],
                               rtol=1e-5, atol=1e-4)
    assert np.array_equal(np.asarray(uk)[fin], np.asarray(ud)[fin])
    assert np.array_equal(np.asarray(pk)[fin], np.asarray(pd)[fin])


def test_topk_k1_is_jointly_best():
    """The first candidate achieves the brute-force minimum ΔL."""
    W, G, m = _problem(5, 6, 10, 5)
    want_dl, _, _ = _brute_force(W, G, m)
    c = sm.correlation_vector(jnp.asarray(W), jnp.asarray(m), jnp.asarray(G))
    v, u, p = sm.topk_swaps_dense(jnp.asarray(W), jnp.asarray(m), c,
                                  jnp.asarray(G), k=1)
    scale = np.maximum(np.abs(want_dl), 1.0)
    assert np.all(np.abs(np.asarray(v[:, 0]) - want_dl) <= 1e-3 * scale)
    for r in range(W.shape[0]):
        assert m[r, int(u[r, 0])] == 1.0 and m[r, int(p[r, 0])] == 0.0


def test_topk_candidates_feasible_and_sorted():
    W, G, m, c = _cands(seed=7, R=6, d_in=20, keep=9)
    v, u, p = sm.topk_swaps_chunked(W, m, c, G, k=6, chunk=7)
    v, u, p = np.asarray(v), np.asarray(u), np.asarray(p)
    m_np = np.asarray(m)
    for r in range(v.shape[0]):
        fin = np.isfinite(v[r])
        assert np.all(np.diff(v[r][fin]) >= 0)           # ascending
        assert len(set(p[r][fin])) == fin.sum()          # distinct p
        for j in np.where(fin)[0]:
            assert m_np[r, u[r, j]] == 1.0 and m_np[r, p[r, j]] == 0.0


def _tiled_problem(R, d, *, planted, seed=0):
    """Rows, Gram and mask at a width the search splits into many tiles.

    With ``planted``, two kept columns in different u-tiles and two pruned
    columns in different p-tiles are exact duplicates (same Gram row and
    column, same w and c), so both pairs tie on every ΔL they enter. The
    pruned pair carries large weights, so it heads every row's list, and
    is anti-correlated with the kept pair, so pruning that pair is its
    best swap. Row 0 prunes only three columns: fewer than k finite
    candidates.
    """
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(d, 64)).astype(np.float32)
    G = X @ X.T / 64 + np.eye(d, dtype=np.float32)
    W = rng.normal(size=(R, d)).astype(np.float32)
    m = (rng.random((R, d)) > 0.6).astype(np.float32)
    c = None
    if planted:
        tu, tp = kops.topk_tiles(d)
        tie_u, tie_p = (3, 3 + 2 * tu), (tu + 5, tu + 5 + tp)
        m[0] = 1.0
        m[0, 7] = 0.0
        for (j1, j2), keep in ((tie_u, 1.0), (tie_p, 0.0)):
            G[:, j2] = G[:, j1]
            G[j2, :] = G[j1, :]
            W[:, j2] = W[:, j1]
            m[:, (j1, j2)] = keep
        for jp in tie_p:
            G[tie_u, jp] = G[jp, tie_u] = -4.0
        W[:, tie_p] = 8.0
        W[:, tie_u] = 1.0
        c = np.array(sm.correlation_vector(W, m, G))
        for j1, j2 in (tie_u, tie_p):
            c[:, j2] = c[:, j1]
    W, G, m = jnp.asarray(W), jnp.asarray(G), jnp.asarray(m)
    c = sm.correlation_vector(W, m, G) if c is None else jnp.asarray(c)
    return W, G, m, c


@pytest.mark.parametrize("R,d,planted", [(3, 3072, False), (3, 4608, True),
                                         (11, 2304, True), (13, 600, False)])
def test_topk_kernel_multi_tile_bitwise(R, d, planted):
    """The Pallas search across many (u, p) tiles, a partial row block and
    padded columns: u and p equal the dense reference exactly on feasible
    entries, ties and short rows included. ΔL compares within the
    single-tile test's rtol=1e-5: in interpret mode the CPU backend
    contracts the kernel's ``(a + b) + (c·w)·g`` into a fused
    multiply-add (one ulp off the reference at d = 600), which the TPU's
    vector unit does not."""
    k = 8
    W, G, m, c = _tiled_problem(R, d, planted=planted)
    tu, tp = kops.topk_tiles(d)
    dp = -(-d // 256) * 256
    if d >= 3072:                     # at least 3 u-tiles and 3 p-tiles
        assert dp // tu >= 3 and dp // tp >= 3, (tu, tp)
    # the dense reference row by row: its (1, d, d) ΔL stays small
    ref = [sm.topk_swaps_dense(W[r:r + 1], m[r:r + 1], c[r:r + 1], G, k=k)
           for r in range(R)]
    vd, ud, pd = (np.concatenate([np.asarray(x[i]) for x in ref])
                  for i in range(3))
    vk, uk, pk = (np.asarray(x) for x in
                  kops.swap_topk(W, m, c, G, k=k, interpret=True))
    fin = np.isfinite(vd)
    assert np.array_equal(np.isfinite(vk), fin)
    np.testing.assert_allclose(vk[fin], vd[fin], rtol=1e-5, atol=1e-4)
    assert np.array_equal(uk[fin], ud[fin])
    assert np.array_equal(pk[fin], pd[fin])
    if planted:
        assert fin[0].sum() == 3                     # the short row
        tie_u, tie_p = (3, 3 + 2 * tu), (tu + 5, tu + 5 + tp)
        # the tied pruned pair heads each list, lower p first; equal ΔL
        assert np.all(pk[:, :2] == np.array(tie_p))
        assert np.array_equal(vk[:, 0], vk[:, 1])
        # a tie in u resolves to the lower u, in the earlier u-tile
        assert np.any(uk[fin] == tie_u[0])
        assert not np.any(uk[fin] == tie_u[1])


@pytest.mark.parametrize("d", [24, 128, 130, 300, 600, 2304, 3072, 4096,
                               9216, 11008, 13696, 14336, 16384, 28672])
def test_topk_tiles_divide_the_padded_width(d, monkeypatch):
    """The search's tiles are lane multiples that divide the width padded
    to 256 (128 below that), so they never pad it further; the wrapper
    hands the kernel exactly that width."""
    R = 20
    dp = -(-d // 256) * 256 if d > 128 else 128
    tu, tp = kops.topk_tiles(d)
    for t in (tu, tp):
        assert t % 128 == 0 and dp % t == 0
    seen = {}

    def spy(a, b, cc, w, g, *, k, row_block, tile_u, tile_p, interpret):
        seen.update(shape=a.shape, g=g.shape, tiles=(row_block, tile_u,
                                                     tile_p))
        z = jnp.zeros((a.shape[0], k))
        return z, z.astype(jnp.int32), z.astype(jnp.int32)

    monkeypatch.setattr(kops, "swap_topk_padded", spy)
    x = jax.ShapeDtypeStruct((R, d), jnp.float32)
    jax.eval_shape(lambda w, mm, cc, g: kops.swap_topk(w, mm, cc, g, k=8),
                   x, x, x, jax.ShapeDtypeStruct((d, d), jnp.float32))
    assert seen == dict(shape=(24, dp), g=(dp, dp), tiles=(8, tu, tp))


# ---------------------------------------------------------------------------
# commit exactness (both flavors)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("flavor", ["candidates", "columns"])
def test_commit_exact_and_monotone(flavor):
    W, G, m, c = _cands(seed=11, R=10, d_in=24, keep=12)
    v, u, p = sm.topk_swaps_chunked(W, m, c, G, k=5, chunk=8)
    if flavor == "candidates":
        m2, c2, dsum, nacc = sm.commit_swaps(W, m, c, G, v, u, p, eps=0.0)
    else:
        m2, c2, dsum, nacc = sm.commit_swaps_columns(W, m, c, G, v, p,
                                                     eps=0.0)
    l0 = sm.row_loss(W, m, G)
    l1 = sm.row_loss(W, m2, G)
    scale = float(jnp.mean(l0)) + 1.0
    # tracked ΔL == directly recomputed loss delta (exact bookkeeping)
    assert np.allclose(np.asarray(dsum), np.asarray(l1 - l0),
                       atol=1e-4 * scale)
    assert np.all(np.asarray(dsum) <= 1e-6)              # monotone
    assert np.any(np.asarray(nacc) > 1)                  # actually batched
    # incremental c == recomputation after the batch
    c_re = sm.correlation_vector(W, m2, G)
    np.testing.assert_allclose(np.asarray(c2), np.asarray(c_re),
                               rtol=1e-4, atol=1e-2 * scale)
    # sparsity level preserved, entries exactly 0/1
    assert np.array_equal(np.asarray(jnp.sum(m2, 1)),
                          np.asarray(jnp.sum(m, 1)))
    assert set(np.unique(np.asarray(m2))) <= {0.0, 1.0}


def test_commit_kernel_matches_jnp():
    """The in-kernel commit loop (interpret) is bit-identical to the jnp
    candidate-space commit on masks, c, and accept counts."""
    W, G, m, c = _cands(seed=13, R=9, d_in=24, keep=12)
    k = 5
    v, u, p = sm.topk_swaps_chunked(W, m, c, G, k=k, chunk=8)
    m1, c1, s1, n1 = sm.commit_swaps(W, m, c, G, v, u, p, eps=0.0)
    m2, c2, s2, n2 = kops.swap_topk_commit(W, m, c, G, k=k, interpret=True)
    assert np.array_equal(np.asarray(m1), np.asarray(m2))
    assert np.array_equal(np.asarray(c1), np.asarray(c2))
    assert np.array_equal(np.asarray(n1), np.asarray(n2))
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                               rtol=1e-5, atol=1e-4)


# ---------------------------------------------------------------------------
# refinement-level properties
# ---------------------------------------------------------------------------


def test_kswap_beats_one_swap_at_equal_pass_budget():
    """With the same t_max search passes, k-swap ends at or below the
    1-swap loss (it commits up to k times more swaps per pass)."""
    W, G, m = _problem(17, 10, 24, 12)
    pat = masks_lib.PerRow(0.5)
    for t in (2, 5):
        r1 = sparseswaps.refine(jnp.asarray(W), jnp.asarray(G),
                                jnp.asarray(m), pat, t_max=t, k_swaps=1,
                                method="chunked", chunk=8)
        rk = sparseswaps.refine(jnp.asarray(W), jnp.asarray(G),
                                jnp.asarray(m), pat, t_max=t, k_swaps=6,
                                method="chunked", chunk=8)
        l1 = float(jnp.sum(r1.loss_final))
        lk = float(jnp.sum(rk.loss_final))
        assert lk <= l1 * (1 + 1e-5) + 1e-4, (t, lk, l1)


def test_kswap_monotone_history():
    W, G, m = _problem(19, 8, 24, 12)
    pat = masks_lib.PerRow(0.5)
    res = sparseswaps.refine(jnp.asarray(W), jnp.asarray(G), jnp.asarray(m),
                             pat, t_max=20, k_swaps=4, track_history=True)
    hist = np.asarray(res.history)
    assert np.all(np.diff(hist) <= 1e-3)


@pytest.mark.parametrize("method", ["dense", "chunked", "pallas"])
def test_kswap_fixed_point_certified(method):
    """Converged k-swap masks are 1-swap fixed points on every backend
    (brute-force: no feasible swap improves the loss)."""
    W, G, m = _problem(23, 5, 12, 6)
    pat = masks_lib.PerRow(0.5)
    res = sparseswaps.refine(jnp.asarray(W), jnp.asarray(G), jnp.asarray(m),
                             pat, t_max=300, k_swaps=4, method=method,
                             chunk=5)
    mf = np.asarray(res.mask)
    assert masks_lib.validate_mask(jnp.asarray(mf), pat)
    want_dl, _, _ = _brute_force(W, G, mf)
    assert np.all(want_dl >= -1e-4), want_dl
    # exact bookkeeping held all the way to the fixed point
    exact = np.array([_row_loss_np(W[r], mf[r], G)
                      for r in range(W.shape[0])])
    np.testing.assert_allclose(np.asarray(res.loss_final), exact,
                               rtol=1e-3, atol=1e-2)


def test_kswap_fixed_point_certified_nm():
    W, G, mask = _problem(29, 5, 16, 0)
    scores = np.random.default_rng(31).normal(size=W.shape)
    pat = masks_lib.NM(2, 4)
    mask = np.asarray(masks_lib.make_mask(jnp.asarray(scores), pat))
    res = sparseswaps.refine(jnp.asarray(W), jnp.asarray(G),
                             jnp.asarray(mask), pat, t_max=300, k_swaps=4)
    mf = np.asarray(res.mask)
    assert masks_lib.validate_mask(jnp.asarray(mf), pat)
    want_dl, _, _ = _brute_force(W, G, mf, block=4)
    assert np.all(want_dl >= -1e-4), want_dl


@pytest.mark.parametrize("method", ["chunked", "pallas"])
def test_kswap_candidate_commit_mode(method):
    """The O(R·k²) candidate-space commit (in-kernel on the Pallas path)
    is reachable via refine(commit_mode=\"candidates\") and reaches a
    certified fixed point with exact bookkeeping, like the default."""
    W, G, m = _problem(61, 5, 12, 6)
    pat = masks_lib.PerRow(0.5)
    res = sparseswaps.refine(jnp.asarray(W), jnp.asarray(G), jnp.asarray(m),
                             pat, t_max=300, k_swaps=4, method=method,
                             chunk=5, commit_mode="candidates")
    mf = np.asarray(res.mask)
    want_dl, _, _ = _brute_force(W, G, mf)
    assert np.all(want_dl >= -1e-4), want_dl
    exact = np.array([_row_loss_np(W[r], mf[r], G)
                      for r in range(W.shape[0])])
    np.testing.assert_allclose(np.asarray(res.loss_final), exact,
                               rtol=1e-3, atol=1e-2)


def test_compaction_bit_identical():
    """Compaction on/off produce identical masks, swaps, and losses —
    converged rows leaving the working set changes nothing."""
    W, G, m = _problem(37, 24, 32, 16)
    pat = masks_lib.PerRow(0.5)
    base = sparseswaps.refine(jnp.asarray(W), jnp.asarray(G),
                              jnp.asarray(m), pat, t_max=400, k_swaps=4,
                              method="chunked", chunk=8)
    for every in (1, 3, 7):
        comp = sparseswaps.refine(jnp.asarray(W), jnp.asarray(G),
                                  jnp.asarray(m), pat, t_max=400, k_swaps=4,
                                  method="chunked", chunk=8,
                                  compact_every=every)
        assert bool(jnp.all(base.mask == comp.mask)), every
        assert np.array_equal(np.asarray(base.swaps),
                              np.asarray(comp.swaps)), every
        np.testing.assert_array_equal(np.asarray(base.loss_final),
                                      np.asarray(comp.loss_final))


def test_compaction_truncated_budget_bit_identical():
    """Bit-identity also holds when t_max truncates mid-refinement."""
    W, G, m = _problem(41, 16, 32, 16)
    pat = masks_lib.PerRow(0.5)
    base = sparseswaps.refine(jnp.asarray(W), jnp.asarray(G),
                              jnp.asarray(m), pat, t_max=5, k_swaps=4,
                              method="chunked", chunk=8)
    comp = sparseswaps.refine(jnp.asarray(W), jnp.asarray(G),
                              jnp.asarray(m), pat, t_max=5, k_swaps=4,
                              method="chunked", chunk=8, compact_every=2)
    assert bool(jnp.all(base.mask == comp.mask))


def test_compaction_rejects_history():
    W, G, m = _problem(43, 4, 12, 6)
    with pytest.raises(ValueError, match="compact_every"):
        sparseswaps.refine(jnp.asarray(W), jnp.asarray(G), jnp.asarray(m),
                           masks_lib.PerRow(0.5), t_max=5,
                           compact_every=2, track_history=True)


def test_row_block_padding_single_jit_entry():
    """A partial trailing row block is padded, not recompiled: results
    match the unblocked run and the padded rows never leak."""
    W, G, m = _problem(47, 13, 24, 12)     # 13 rows: 2 blocks of 8 w/ pad
    pat = masks_lib.PerRow(0.5)
    a = sparseswaps.refine(jnp.asarray(W), jnp.asarray(G), jnp.asarray(m),
                           pat, t_max=12, k_swaps=4, method="chunked",
                           chunk=8)
    b = sparseswaps.refine(jnp.asarray(W), jnp.asarray(G), jnp.asarray(m),
                           pat, t_max=12, k_swaps=4, method="chunked",
                           chunk=8, row_block=8)
    assert a.mask.shape == (13, 24)
    assert bool(jnp.all(a.mask == b.mask))
    cache = sparseswaps._refine_carry._cache_size()
    c = sparseswaps.refine(jnp.asarray(W[:5]), jnp.asarray(G),
                           jnp.asarray(m[:5]), pat, t_max=12, k_swaps=4,
                           method="chunked", chunk=8, row_block=8)
    assert c.mask.shape == (5, 24)
    # 5-row call padded to the same (8, d) block: no new jit entry
    assert sparseswaps._refine_carry._cache_size() == cache


# ---------------------------------------------------------------------------
# the counted-search-pass perf guard (CI)
# ---------------------------------------------------------------------------


def test_search_pass_counter_hook():
    W, G, m = _problem(53, 6, 16, 8)
    pat = masks_lib.PerRow(0.5)
    with sparseswaps.count_search_passes() as cnt:
        res = sparseswaps.refine(jnp.asarray(W), jnp.asarray(G),
                                 jnp.asarray(m), pat, t_max=50, k_swaps=1,
                                 method="chunked", chunk=8)
    assert cnt.passes == int(res.iters)
    assert cnt.rows_scored == cnt.passes * 6
    # hook no longer active: further work is not counted
    sparseswaps.refine(jnp.asarray(W), jnp.asarray(G), jnp.asarray(m), pat,
                       t_max=5, method="chunked", chunk=8)
    assert cnt.passes == int(res.iters)


def test_search_pass_counter_nests():
    """Nested hooks tally independently and unwind by identity."""
    with sparseswaps.count_search_passes() as outer:
        with sparseswaps.count_search_passes() as inner:
            sparseswaps.record_search_passes(3, 4)
        sparseswaps.record_search_passes(2, 4)
    assert (inner.passes, inner.rows_scored) == (3, 12)
    assert (outer.passes, outer.rows_scored) == (5, 20)


def test_stacked_compaction_pads_partial_blocks():
    """The stacked driver (the engine's compact_every path) pads a
    partial trailing row block like the uncompacted paths, so per-row
    results match refine() at the same row_block."""
    rng = np.random.default_rng(67)
    X = rng.normal(size=(32, 200)).astype(np.float32)
    Gs = jnp.stack([jnp.asarray(X @ X.T), jnp.asarray(X @ X.T) * 1.1])
    W = jnp.asarray(rng.normal(size=(2, 13, 32)).astype(np.float32))
    pat = masks_lib.PerRow(0.5)
    from repro.core.warmstart import warmstart_mask
    m0 = jnp.stack([warmstart_mask(W[i], Gs[i], pat, "wanda")
                    for i in range(2)])
    m, l0, l1, sw, _ = sparseswaps.refine_stacked_compacted(
        W, m0, Gs, t_max=200, eps=0.0, method="chunked", block=None,
        chunk=16, k_swaps=4, compact_every=3, row_block=8)
    assert m.shape == (2, 13, 32)
    for i in range(2):
        r = sparseswaps.refine(W[i], Gs[i], m0[i], pat, t_max=200,
                               k_swaps=4, method="chunked", chunk=16,
                               row_block=8)
        assert bool(jnp.all(r.mask == m[i])), i
        np.testing.assert_array_equal(np.asarray(r.swaps), np.asarray(sw[i]))


def test_kswap_pass_budget_guard():
    """Deterministic amortization guard: on the weakly-correlated smoke
    config, k-swap reaches the brute-force fixed point in no more than
    ceil(max-row-swaps / k) + 2 search passes, and in at most half the
    1-swap passes. Counted via the search-pass hook — wall-clock-free,
    so it cannot flake on machine load."""
    k = 8
    W, G, m = _problem(59, 8, 48, 24, corr=0.05)
    pat = masks_lib.PerRow(0.5)
    with sparseswaps.count_search_passes() as c1:
        r1 = sparseswaps.refine(jnp.asarray(W), jnp.asarray(G),
                                jnp.asarray(m), pat, t_max=500, k_swaps=1,
                                method="chunked", chunk=16)
    with sparseswaps.count_search_passes() as ck:
        rk = sparseswaps.refine(jnp.asarray(W), jnp.asarray(G),
                                jnp.asarray(m), pat, t_max=500, k_swaps=k,
                                method="chunked", chunk=16)
    # the k-swap result is a true fixed point (same certification suite)
    want_dl, _, _ = _brute_force(W, G, np.asarray(rk.mask))
    assert np.all(want_dl >= -1e-4)
    budget = int(np.ceil(int(jnp.max(rk.swaps)) / k)) + 2
    assert ck.passes <= budget, (ck.passes, budget)
    assert 2 * ck.passes <= c1.passes, (ck.passes, c1.passes)
