"""SparseSwaps algorithm properties: monotonicity, convergence, exactness."""
import numpy as np
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from conftest import make_problem
from repro.core import masks as masks_lib
from repro.core import objective, sparseswaps
from repro.core import swap_math as sm
from repro.core.warmstart import warmstart_mask


def test_monotone_history(rng):
    W, _, G = make_problem(rng, d_out=12, d_in=48)
    pat = masks_lib.PerRow(0.6)
    m0 = warmstart_mask(W, G, pat, "wanda")
    res = sparseswaps.refine(W, G, m0, pat, t_max=25, track_history=True)
    hist = np.asarray(res.history)
    assert np.all(np.diff(hist) <= 1e-3)      # monotone non-increasing


def test_loss_bookkeeping_exact(rng):
    W, _, G = make_problem(rng, d_out=10, d_in=64)
    pat = masks_lib.PerRow(0.5)
    m0 = warmstart_mask(W, G, pat, "wanda")
    res = sparseswaps.refine(W, G, m0, pat, t_max=40)
    exact = sm.row_loss(W, res.mask, G)
    scale = float(jnp.mean(res.loss_init)) + 1.0
    assert float(jnp.max(jnp.abs(exact - res.loss_final))) < 1e-4 * scale


def test_early_exit_at_local_optimum(rng):
    """Once no swap improves, iterations stop (while_loop early exit)."""
    W, _, G = make_problem(rng, d_out=4, d_in=16)
    pat = masks_lib.PerRow(0.5)
    m0 = warmstart_mask(W, G, pat, "wanda")
    res1 = sparseswaps.refine(W, G, m0, pat, t_max=1000)
    assert int(res1.iters) < 1000
    # re-running from the converged mask performs zero swaps
    res2 = sparseswaps.refine(W, G, res1.mask, pat, t_max=1000)
    assert int(jnp.sum(res2.swaps)) == 0


def test_convergence_bound_prop_a2(rng):
    """Prop A.2: with tolerance eps, swaps <= ceil(L0 / eps)."""
    W, _, G = make_problem(rng, d_out=6, d_in=32)
    pat = masks_lib.PerRow(0.5)
    m0 = warmstart_mask(W, G, pat, "magnitude")
    eps = 1.0
    res = sparseswaps.refine(W, G, m0, pat, t_max=10_000, eps=eps)
    bound = np.ceil(np.asarray(res.loss_init) / eps)
    assert np.all(np.asarray(res.swaps) <= bound)


def test_pattern_preserved_per_row(rng):
    W, _, G = make_problem(rng, d_out=8, d_in=40)
    pat = masks_lib.PerRow(0.6)
    m0 = warmstart_mask(W, G, pat, "wanda")
    res = sparseswaps.refine(W, G, m0, pat, t_max=30)
    assert masks_lib.validate_mask(res.mask, pat)


def test_pattern_preserved_nm(rng):
    W, _, G = make_problem(rng, d_out=8, d_in=32)
    pat = masks_lib.NM(2, 4)
    m0 = warmstart_mask(W, G, pat, "wanda")
    res = sparseswaps.refine(W, G, m0, pat, t_max=30)
    assert masks_lib.validate_mask(res.mask, pat)
    assert float(jnp.sum(res.loss_final)) <= float(jnp.sum(res.loss_init)) + 1e-4


def test_weaker_warmstart_larger_reduction(rng):
    """Paper Table 4: magnitude warmstart yields larger error reductions."""
    W, _, G = make_problem(rng, d_out=16, d_in=64)
    pat = masks_lib.PerRow(0.6)
    reds = {}
    for crit in ("magnitude", "wanda"):
        m0 = warmstart_mask(W, G, pat, crit)
        res = sparseswaps.refine(W, G, m0, pat, t_max=60)
        reds[crit] = float(jnp.mean(res.error_reduction))
    assert reds["magnitude"] > reds["wanda"]


def test_refined_never_worse_than_warmstart(rng):
    for crit in ("magnitude", "wanda", "ria"):
        W, _, G = make_problem(rng, d_out=8, d_in=48)
        pat = masks_lib.PerRow(0.5)
        m0 = warmstart_mask(W, G, pat, crit)
        res = sparseswaps.refine(W, G, m0, pat, t_max=20)
        assert np.all(np.asarray(res.loss_final)
                      <= np.asarray(res.loss_init) * (1 + 1e-5))


def test_row_block_independence(rng):
    """Row-blocked execution gives identical masks (rows independent)."""
    W, _, G = make_problem(rng, d_out=12, d_in=40)
    pat = masks_lib.PerRow(0.5)
    m0 = warmstart_mask(W, G, pat, "wanda")
    r1 = sparseswaps.refine(W, G, m0, pat, t_max=15, method="chunked")
    r2 = sparseswaps.refine(W, G, m0, pat, t_max=15, method="chunked",
                            row_block=5)
    assert bool(jnp.all(r1.mask == r2.mask))


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 10_000),
       sparsity=st.sampled_from([0.25, 0.5, 0.75]),
       d_in=st.sampled_from([16, 24, 40]))
def test_property_monotone_and_feasible(seed, sparsity, d_in):
    """Property: for any problem, refinement is monotone + feasible."""
    rng = np.random.default_rng(seed)
    W, _, G = make_problem(rng, d_out=4, d_in=d_in, seed=seed)
    pat = masks_lib.PerRow(sparsity)
    m0 = warmstart_mask(W, G, pat, "magnitude")
    res = sparseswaps.refine(W, G, m0, pat, t_max=10)
    assert masks_lib.validate_mask(res.mask, pat)
    assert np.all(np.asarray(res.loss_final)
                  <= np.asarray(res.loss_init) * (1 + 1e-5) + 1e-5)
    # exact objective agrees with Gram-tracked loss
    direct = objective.layer_loss(W, res.mask, G)
    assert np.isclose(float(direct), float(jnp.sum(res.loss_final)),
                      rtol=1e-3, atol=1.0)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10_000), n=st.sampled_from([1, 2]),
       m=st.sampled_from([4, 8]))
def test_property_nm_feasible(seed, n, m):
    rng = np.random.default_rng(seed)
    W, _, G = make_problem(rng, d_out=4, d_in=32, seed=seed)
    pat = masks_lib.NM(n, m)
    m0 = warmstart_mask(W, G, pat, "wanda")
    res = sparseswaps.refine(W, G, m0, pat, t_max=8)
    assert masks_lib.validate_mask(res.mask, pat)
    assert np.all(np.asarray(res.loss_final)
                  <= np.asarray(res.loss_init) * (1 + 1e-5) + 1e-5)
