"""Sparsity patterns and top-k mask construction."""
import numpy as np
import jax.numpy as jnp
from hypothesis import given, settings, strategies as st

from repro.core import masks as masks_lib


def test_topk_exact_count():
    rng = np.random.default_rng(0)
    s = jnp.asarray(rng.normal(size=(7, 33)).astype(np.float32))
    for keep in (1, 5, 16, 32, 33):
        m = masks_lib.topk_mask_per_row(s, keep)
        assert np.all(np.asarray(jnp.sum(m, axis=1)) == min(keep, 33))


def test_topk_with_ties():
    """Duplicate scores must not inflate the keep count."""
    s = jnp.asarray([[1.0, 2.0, 2.0, 2.0, 0.5, 2.0]])
    m = masks_lib.topk_mask_per_row(s, 3)
    assert float(jnp.sum(m)) == 3
    assert float(m[0, 4]) == 0.0       # the clear loser is dropped


def test_topk_all_equal():
    s = jnp.ones((3, 8))
    m = masks_lib.topk_mask_per_row(s, 5)
    assert np.all(np.asarray(jnp.sum(m, axis=1)) == 5)


def test_nm_block_counts():
    rng = np.random.default_rng(1)
    s = jnp.asarray(rng.normal(size=(4, 24)).astype(np.float32))
    m = masks_lib.topk_mask_nm(s, 2, 4)
    blocks = np.asarray(m).reshape(4, 6, 4).sum(-1)
    assert np.all(blocks == 2)


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 9999), keep=st.integers(1, 20))
def test_property_topk_count(seed, keep):
    rng = np.random.default_rng(seed)
    # adversarial: quantized scores force ties
    s = jnp.asarray(np.round(rng.normal(size=(3, 20)) * 2) / 2)
    m = masks_lib.topk_mask_per_row(s, keep)
    assert np.all(np.asarray(jnp.sum(m, axis=1)) == keep)
    # kept scores always >= dropped scores
    s_np, m_np = np.asarray(s), np.asarray(m)
    for r in range(3):
        if keep < 20:
            assert s_np[r][m_np[r] > 0.5].min() >= s_np[r][m_np[r] < 0.5].max() - 1e-6


def test_pattern_api():
    p = masks_lib.PerRow(0.6)
    assert p.keep_per_row(100) == 40
    assert p.block(100) is None
    nm = masks_lib.NM(2, 4)
    assert nm.keep_per_row(32) == 16
    assert nm.block(32) == 4
    assert nm.sparsity == 0.5
    assert "2:4" in nm.describe()


def test_validate_mask_rejects_bad():
    p = masks_lib.PerRow(0.5)
    good = jnp.asarray([[1.0, 0, 1, 0], [0, 1, 0, 1]])
    bad = jnp.asarray([[1.0, 1, 1, 0], [0, 1, 0, 1]])
    assert masks_lib.validate_mask(good, p)
    assert not masks_lib.validate_mask(bad, p)
    nm = masks_lib.NM(1, 2)
    assert masks_lib.validate_mask(good, nm)
    assert not masks_lib.validate_mask(jnp.asarray([[1.0, 1, 0, 0]]), nm)
