"""Packed weight formats (core.packed): round-trips, error paths, spmm.

Property tests (``hypothesis``) across
shapes, sparsities and dtypes; bit-exact ``pack``/``unpack`` inversion;
the loud failure modes for masks a format cannot represent; and the
spmm kernels (jnp fallback + Pallas interpret) against the dense
reference.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import masks as masks_lib
from repro.core import packed
from repro.kernels import spmm


def _rand(seed, shape, dtype):
    w = np.random.default_rng(seed).normal(size=shape).astype(np.float32)
    return jnp.asarray(w).astype(dtype)


def _scores(seed, shape):
    return jnp.asarray(np.random.default_rng(seed + 999).normal(size=shape)
                       .astype(np.float32))


# ---------------------------------------------------------------------------
# round-trips
# ---------------------------------------------------------------------------

@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 10_000),
       d_out=st.integers(1, 9),
       nb=st.integers(1, 5),
       nm=st.sampled_from([(2, 4), (1, 4), (4, 8), (2, 8)]),
       dtype=st.sampled_from(["float32", "bfloat16"]))
def test_nm_pack_unpack_roundtrip(seed, d_out, nb, nm, dtype):
    """unpack(pack_nm(w, m)) == w ⊙ m bit-exactly, any shape/dtype."""
    n, m = nm
    w = _rand(seed, (d_out, nb * m), dtype)
    mask = masks_lib.make_mask(_scores(seed, (d_out, nb * m)),
                               masks_lib.NM(n, m))
    pw = packed.pack(w, mask, "nm24", n=n, m=m)
    assert pw.idx.dtype == jnp.uint8 and pw.k == nb * n
    np.testing.assert_array_equal(
        np.asarray(packed.unpack(pw)),
        np.asarray(w * mask.astype(w.dtype)))


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 10_000),
       d_out=st.integers(1, 9),
       d_in=st.integers(4, 24),
       sparsity=st.sampled_from([0.25, 0.5, 0.75]),
       dtype=st.sampled_from(["float32", "bfloat16"]))
def test_gathered_pack_unpack_roundtrip(seed, d_out, d_in, sparsity, dtype):
    """Equal-R unstructured rows round-trip bit-exactly via the gather
    format (SparseSwaps' PerRow masks are equal-R by construction)."""
    w = _rand(seed, (d_out, d_in), dtype)
    pat = masks_lib.PerRow(sparsity)
    if pat.keep_per_row(d_in) == 0:
        return
    mask = masks_lib.make_mask(_scores(seed, (d_out, d_in)), pat)
    pw = packed.pack(w, mask, "gathered")
    assert pw.idx.dtype == jnp.int32
    # metadata is sorted ascending per row — the DMA-friendly layout
    assert bool(jnp.all(jnp.diff(pw.idx, axis=-1) > 0))
    np.testing.assert_array_equal(
        np.asarray(packed.unpack(pw)),
        np.asarray(w * mask.astype(w.dtype)))


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10_000), stack=st.integers(1, 4))
def test_stacked_leading_dims_roundtrip(seed, stack):
    """Leading stack dims (layers, experts) pack/unpack symmetrically."""
    w = _rand(seed, (stack, 3, 5, 16), "float32")
    mask = masks_lib.make_mask(_scores(seed, w.shape), masks_lib.NM(2, 4))
    pw = packed.pack(w, mask, "nm24")
    assert pw.shape == w.shape and pw.values.shape == (stack, 3, 5, 8)
    np.testing.assert_array_equal(np.asarray(packed.unpack(pw)),
                                  np.asarray(w * mask))
    # pytree: values/idx are data leaves, format fields are static
    sliced = jax.tree.map(lambda x: x[0], pw)
    assert isinstance(sliced, packed.PackedWeight)
    np.testing.assert_array_equal(np.asarray(packed.unpack(sliced)),
                                  np.asarray((w * mask)[0]))


# ---------------------------------------------------------------------------
# error paths
# ---------------------------------------------------------------------------

def test_nm_rejects_non_nm_mask():
    w = _rand(0, (4, 16), "float32")
    mask = masks_lib.make_mask(_scores(0, (4, 16)), masks_lib.PerRow(0.5))
    with pytest.raises(ValueError, match="not 2:4"):
        packed.pack(w, mask, "nm24")


def test_gathered_rejects_unequal_row_support():
    w = _rand(1, (4, 12), "float32")
    mask = np.asarray(masks_lib.make_mask(_scores(1, (4, 12)),
                                          masks_lib.PerRow(0.5))).copy()
    mask[0, np.argmin(mask[0])] = 1.0      # one row keeps an extra entry
    with pytest.raises(ValueError, match="equal per-row support"):
        packed.pack(w, jnp.asarray(mask), "gathered")


def test_gathered_rejects_all_pruned_rows():
    w = _rand(2, (3, 8), "float32")
    with pytest.raises(ValueError, match="all-pruned"):
        packed.pack(w, jnp.zeros_like(w), "gathered")


def test_unknown_format_and_bad_mask():
    w = _rand(3, (3, 8), "float32")
    mask = masks_lib.make_mask(_scores(3, (3, 8)), masks_lib.NM(2, 4))
    with pytest.raises(ValueError, match="unknown packed format"):
        packed.pack(w, mask, "csr")
    with pytest.raises(ValueError, match="exactly 0/1"):
        packed.pack(w, mask * 0.5, "nm24")


def test_pack_tree_names_offending_site():
    import repro.configs as configs
    import repro.models as models
    cfg = configs.get_tiny("llama31-8b")
    api = models.build(cfg)
    params = api.init(jax.random.key(0))
    masks = jax.tree.map(
        lambda w: masks_lib.make_mask(
            jnp.abs(w.astype(jnp.float32)), masks_lib.PerRow(0.5)),
        {"layers": {"attn": {"wq": params["layers"]["attn"]["wq"]}}})
    with pytest.raises(ValueError, match="layers.attn.wq"):
        packed.pack_tree(cfg, params, masks, "nm24")


# ---------------------------------------------------------------------------
# spmm kernels vs dense reference
# ---------------------------------------------------------------------------

@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 10_000),
       T=st.integers(1, 6),
       d_out=st.integers(1, 7),
       nb=st.integers(1, 4),
       kernel=st.sampled_from(["jnp", "pallas"]))
def test_spmm_nm_matches_dense(seed, T, d_out, nb, kernel):
    w = _rand(seed, (d_out, nb * 4), "float32")
    mask = masks_lib.make_mask(_scores(seed, w.shape), masks_lib.NM(2, 4))
    pw = packed.pack(w, mask, "nm24")
    x = _rand(seed + 1, (T, nb * 4), "float32")
    want = x @ (w * mask).T
    got = spmm.spmm(x, pw, kernel=kernel)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 10_000),
       d_in=st.integers(4, 20),
       sparsity=st.sampled_from([0.25, 0.5, 0.75]),
       kernel=st.sampled_from(["jnp", "pallas"]))
def test_spmm_gather_matches_dense(seed, d_in, sparsity, kernel):
    pat = masks_lib.PerRow(sparsity)
    if pat.keep_per_row(d_in) == 0:
        return
    w = _rand(seed, (5, d_in), "float32")
    mask = masks_lib.make_mask(_scores(seed, w.shape), pat)
    pw = packed.pack(w, mask, "gathered")
    x = _rand(seed + 1, (3, d_in), "float32")
    want = x @ (w * mask).T
    got = spmm.spmm(x, pw, kernel=kernel)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_spmm_vmem_fallback_pinned_at_bound(monkeypatch):
    """The pallas VMEM boundary is ``_VMEM_BOUND`` exactly, and past it
    there is no fallback: one byte under the estimate raises (the call
    is never handed to jnp); at the estimate the kernel runs (reason
    stays "forced")."""
    w = _rand(0, (4, 16), "float32")
    mask = masks_lib.make_mask(_scores(0, w.shape), masks_lib.NM(2, 4))
    pw = packed.pack(w, mask, "nm24")
    x = _rand(1, (2, 16), "float32")
    want = np.asarray(x @ (w * mask).T)
    plan = spmm._plan(2, 16, pw.values.shape[-1], (2, 4),
                      tile_t=spmm.TILE_T, tile_o=spmm.TILE_O,
                      tile_d=spmm.TILE_D, tile_s=spmm.TILE_S)
    est = spmm._vmem_bytes(plan, 4, 4)
    orig_pallas = spmm._spmm_pallas
    monkeypatch.setattr(spmm, "_VMEM_BOUND", est - 1)
    monkeypatch.setattr(
        spmm, "_spmm_pallas",
        lambda *a, **k: (_ for _ in ()).throw(AssertionError("kernel ran")))
    monkeypatch.setattr(
        spmm, "_spmm_jnp",
        lambda *a, **k: (_ for _ in ()).throw(AssertionError("jnp ran")))
    with spmm.record_dispatch() as rec:
        with pytest.raises(ValueError, match="VMEM"):
            spmm.spmm(x, pw, kernel="pallas")
    assert rec == []
    # inclusive at the bound: the kernel path runs (interpret on CPU)
    monkeypatch.setattr(spmm, "_VMEM_BOUND", est)
    monkeypatch.setattr(spmm, "_spmm_pallas", orig_pallas)
    with spmm.record_dispatch() as rec:
        got = spmm.spmm(x, pw, kernel="pallas")
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-5)
    assert [(r["kernel"], r["reason"]) for r in rec] == [("pallas",
                                                          "forced")]


def test_spmm_stacked_vmaps_per_instance():
    ws = _rand(2, (3, 4, 8), "float32")
    ms = masks_lib.make_mask(_scores(2, ws.shape), masks_lib.NM(2, 4))
    pws = packed.pack(ws, ms, "nm24")
    xs = _rand(3, (3, 5, 8), "float32")
    got = spmm.spmm_stacked(xs, pws, kernel="jnp")
    want = jnp.einsum("ntd,nod->nto", xs, ws * ms)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


# ---------------------------------------------------------------------------
# fused epilogue
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("fmt", ["masked", "nm24", "gathered"])
def test_epilogue_fused_matches_unfused(dtype, fmt):
    """``dense(..., bias=, act=)`` under a fusing policy == the same
    matmul with ``act(y + bias)`` applied outside, for every execution
    format and both serving dtypes. Covers all EPILOGUES keys plus the
    bias-only and act-only corners."""
    from repro.models import common
    w = _rand(0, (6, 16), dtype)
    mask = masks_lib.make_mask(_scores(0, w.shape), masks_lib.NM(2, 4))
    x = _rand(1, (5, 16), dtype)
    bias = _rand(2, (6,), dtype)
    if fmt == "masked":
        wexec, mexec = w, mask
    else:
        wexec, mexec = packed.pack(w, mask, fmt), None
    tol = 1e-6 if dtype == "float32" else 2e-2
    for act in [*spmm.EPILOGUES, None]:
        for b in (bias, None):
            with common.use_matmul_policy(
                    common.PackedMatmulPolicy("jnp", fuse_epilogue=True)):
                fused = common.dense(x, wexec, mask=mexec, bias=b, act=act)
            with common.use_matmul_policy(
                    common.PackedMatmulPolicy("jnp", fuse_epilogue=False)):
                unfused = common.dense(x, wexec, mask=mexec, bias=b,
                                       act=act)
            assert fused.dtype == unfused.dtype == x.dtype
            np.testing.assert_allclose(
                np.asarray(fused, np.float32),
                np.asarray(unfused, np.float32),
                atol=tol, rtol=tol, err_msg=f"{fmt}/{act}/bias={b is not None}")


def test_epilogue_fused_in_pallas_kernel():
    """The in-kernel epilogue (interpret mode) matches the jnp fallback
    bit-for-bit on the fp32 accumulator path."""
    w = _rand(4, (4, 16), "float32")
    mask = masks_lib.make_mask(_scores(4, w.shape), masks_lib.NM(2, 4))
    pw = packed.pack(w, mask, "nm24")
    x = _rand(5, (3, 16), "float32")
    bias = _rand(6, (4,), "float32")
    for act in ("silu", "relu2"):
        got = spmm.spmm(x, pw, kernel="pallas", bias=bias, act=act)
        ref = spmm.apply_epilogue(
            jnp.asarray(x @ (w * mask).T, jnp.float32), bias, act)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=1e-5, err_msg=act)


# ---------------------------------------------------------------------------
# whole-model packing + export artifacts
# ---------------------------------------------------------------------------

def test_pack_tree_bytes_and_report_entrypoint():
    import repro.configs as configs
    import repro.models as models
    from repro import pruning
    cfg = configs.get_tiny("llama31-8b")
    api = models.build(cfg)
    params = api.init(jax.random.key(0))
    batches = list(pruning.calibration_batches(cfg, n_samples=2, seq_len=16,
                                               batch_size=2))
    rep = pruning.prune_model(api, params, batches, masks_lib.NM(2, 4),
                              method="none")
    pt = packed.from_report(cfg, params, rep, "nm24")
    dense_bytes = sum(int(l.nbytes) for l in jax.tree.leaves(params))
    assert packed.packed_bytes(pt) < dense_bytes
    leaves = jax.tree.leaves(
        pt, is_leaf=lambda x: isinstance(x, packed.PackedWeight))
    pws = [l for l in leaves if isinstance(l, packed.PackedWeight)]
    assert pws, "no site was packed"
    for pw in pws:
        # 2:4 packed: half the values + 1B/slot metadata
        assert pw.nbytes < pw.dense_nbytes
        np.testing.assert_array_equal(
            np.asarray(jnp.sum(packed.mask_of(pw))),
            np.asarray(jnp.float32(pw.values.size)))


def test_export_packed_load_packed_roundtrip(tmp_path):
    """PruneExecutor.export_packed -> load_packed_tree is bit-identical
    to packing in memory, and the masks ride-along loads too."""
    import repro.configs as configs
    import repro.models as models
    from repro import pruning
    cfg = configs.get_tiny("llama31-8b")
    api = models.build(cfg)
    params = api.init(jax.random.key(0))
    batches = list(pruning.calibration_batches(cfg, n_samples=2, seq_len=16,
                                               batch_size=2))
    plan = pruning.plan_pruning(
        api, params, pruning.PruneRecipe.single(masks_lib.NM(2, 4),
                                                method="none"))
    ex = pruning.PruneExecutor(api, params, plan)
    rep = ex.run(batches)
    ex.export_packed(tmp_path, "nm24")
    loaded = packed.load_packed_tree(params, tmp_path)
    in_mem = packed.pack_tree(cfg, params, rep.masks, "nm24")
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                   np.asarray(b)),
        loaded, in_mem)
    masks = packed.load_mask_tree(cfg, params, tmp_path)
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                   np.asarray(b)),
        masks["layers"], rep.masks["layers"])


def test_export_before_run_raises(tmp_path):
    import repro.configs as configs
    import repro.models as models
    from repro import pruning
    cfg = configs.get_tiny("llama31-8b")
    api = models.build(cfg)
    params = api.init(jax.random.key(0))
    plan = pruning.plan_pruning(
        api, params, pruning.PruneRecipe.single(masks_lib.NM(2, 4),
                                                method="none"))
    ex = pruning.PruneExecutor(api, params, plan)
    with pytest.raises(ValueError, match="call run"):
        ex.export_packed(tmp_path)
