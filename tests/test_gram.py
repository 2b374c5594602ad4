"""Gram accumulation (paper §2.1.2): streaming, stats, loss equivalence."""
import numpy as np
import jax
import jax.numpy as jnp

from hypothesis import given, settings, strategies as st

from conftest import make_problem
from repro.core import gram as gram_lib
from repro.core import objective


def test_streaming_matches_direct(rng):
    X = rng.normal(size=(24, 333)).astype(np.float32)
    G = gram_lib.init_gram(24)
    for lo in range(0, 333, 50):
        G = gram_lib.update(G, jnp.asarray(X[:, lo:lo + 50]))
    np.testing.assert_allclose(np.asarray(G), X @ X.T, rtol=1e-4, atol=1e-2)


def test_update_from_acts_layout(rng):
    acts = rng.normal(size=(4, 7, 12)).astype(np.float32)   # (B, T, d)
    G = gram_lib.update_from_acts(gram_lib.init_gram(12), jnp.asarray(acts))
    x = acts.reshape(-1, 12)
    np.testing.assert_allclose(np.asarray(G), x.T @ x, rtol=1e-4, atol=1e-2)


def test_feature_norms_are_wanda_scale(rng):
    X = rng.normal(size=(16, 100)).astype(np.float32)
    G = jnp.asarray(X @ X.T)
    np.testing.assert_allclose(np.asarray(gram_lib.feature_norms(G)),
                               np.linalg.norm(X, axis=1), rtol=1e-4)


def test_gramstate_mean_variance(rng):
    st = gram_lib.GramState.create(8)
    chunks = [rng.normal(size=(30, 8)).astype(np.float32) * (i + 1)
              for i in range(4)]
    for ch in chunks:
        st = st.update(jnp.asarray(ch))
    allx = np.concatenate(chunks, 0)
    np.testing.assert_allclose(np.asarray(st.mean), allx.mean(0),
                               rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.asarray(st.variance), allx.var(0),
                               rtol=1e-3, atol=1e-3)


def test_psum_gram_merges_hosts(rng):
    """psum_gram math check via explicit merge (single device: identity +
    algebraic re-derivation)."""
    a = gram_lib.GramState.create(6).update(
        jnp.asarray(rng.normal(size=(20, 6)).astype(np.float32)))
    # identity psum (axis of size 1 — vmap provides the axis)
    import jax
    merged = jax.vmap(lambda s: gram_lib.psum_gram(s, "i"), axis_name="i")(
        jax.tree.map(lambda x: x[None], a))
    np.testing.assert_allclose(np.asarray(merged.mean[0]), np.asarray(a.mean),
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(merged.m2[0]), np.asarray(a.m2),
                               rtol=1e-4, atol=1e-4)


@settings(max_examples=15, deadline=None)
@given(n_dev=st.integers(2, 8), seed=st.integers(0, 10**6),
       d=st.sampled_from([4, 8, 13]))
def test_psum_gram_uneven_splits(n_dev, seed, d):
    """Chan parallel-variance merge across UNEVEN per-device token splits
    == one single-device ``GramState.update`` over all tokens (G, count,
    mean, variance). The vmap axis stands in for the mesh data axis."""
    rng = np.random.default_rng(seed)
    # uneven: every device gets a different token count (>=1)
    counts = rng.integers(1, 40, size=n_dev)
    chunks = [rng.normal(size=(int(c), d)).astype(np.float32) * (i + 1)
              for i, c in enumerate(counts)]
    partials = [gram_lib.GramState.create(d).update(jnp.asarray(ch))
                for ch in chunks]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *partials)
    merged = jax.vmap(lambda s: gram_lib.psum_gram(s, "dev"),
                      axis_name="dev")(stacked)
    ref = gram_lib.GramState.create(d).update(
        jnp.asarray(np.concatenate(chunks, 0)))
    for i in range(n_dev):   # psum leaves the merged state on every device
        got = jax.tree.map(lambda x: x[i], merged)
        np.testing.assert_allclose(np.asarray(got.G), np.asarray(ref.G),
                                   rtol=1e-4, atol=1e-3)
        assert float(got.count) == float(ref.count) == float(sum(counts))
        np.testing.assert_allclose(np.asarray(got.mean),
                                   np.asarray(ref.mean), rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(got.variance),
                                   np.asarray(ref.variance),
                                   rtol=1e-3, atol=1e-3)


def test_state_moments_roundtrip(rng):
    """state_from_moments/moments_from_state bridge the raw tap sums to
    GramState exactly (the shard_map merge path relies on this)."""
    x = rng.normal(size=(37, 6)).astype(np.float32)
    g = jnp.asarray(x.T @ x)
    s = jnp.asarray(x.sum(0))
    n = jnp.float32(x.shape[0])
    st_ = gram_lib.state_from_moments(g, s, n)
    ref = gram_lib.GramState.create(6).update(jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(st_.mean), np.asarray(ref.mean),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(st_.m2), np.asarray(ref.m2),
                               rtol=1e-3, atol=1e-3)
    g2, s2, n2 = gram_lib.moments_from_state(st_)
    np.testing.assert_allclose(np.asarray(g2), np.asarray(g), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s),
                               rtol=1e-4, atol=1e-4)
    assert float(n2) == 37.0


def test_feature_norms_accepts_diag(rng):
    X = rng.normal(size=(9, 50)).astype(np.float32)
    G = jnp.asarray(X @ X.T)
    np.testing.assert_allclose(
        np.asarray(gram_lib.feature_norms(jnp.diagonal(G))),
        np.asarray(gram_lib.feature_norms(G)), rtol=1e-6)


def test_layer_loss_gram_equals_direct(rng):
    W, X, G = make_problem(rng, d_out=5, d_in=20)
    m = (rng.random((5, 20)) > 0.4).astype(np.float32)
    lg = objective.layer_loss(W, jnp.asarray(m), G)
    ld = objective.layer_loss_direct(W, jnp.asarray(m), X)
    assert np.isclose(float(lg), float(ld), rtol=1e-3)
