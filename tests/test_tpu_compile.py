"""The main path's Pallas kernels compile for a TPU v5e at minitron-4b widths.

Nothing here runs: each test lowers a kernel (or the group-batched
refinement that calls one) for one chip of a *described* ``v5e:2x2``
topology and compiles it with the TPU compiler, which refuses what
interpret mode accepts — a lane slice not aligned to the tiling, more
VMEM than a kernel may use. Shapes are minitron-4b's: d_model 3072,
d_ff 9216, unstructured 0.6 (k = 0.4·d_in kept per row) and 2:4; the
swap search also at chatglm3-6b's 4096 and 13696.

The topology is described inside module fixtures (only the worker that
runs this file loads the TPU compiler), and the persistent compilation
cache is off while these compiles run: an entry compiled for a described
chip cannot be read back without one.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (AxisType, Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.core import packed, sparseswaps, swap_math
from repro.kernels import ops as kops
from repro.kernels import spmm

D_MODEL, D_FF = 3072, 9216
N_LAYERS = 4            # layers stacked in one site group


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo):
    """A (data=4, model=1) host mesh over the four described chips."""
    return Mesh(np.array(topo.devices).reshape(4, 1), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)


@pytest.fixture(scope="module")
def no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture
def compile_tpu(one_chip, no_compile_cache, monkeypatch):
    """compile(fn, *(shape, dtype)) -> compiled executable for one v5e
    chip. The pruning wrappers pick interpret mode from the backend the
    process sees (the CPU here); steer them to the kernel path."""
    monkeypatch.setattr(kops, "_on_tpu", lambda: True)

    def compile_(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
                for s, dt in shapes]
        return jax.jit(fn).lower(*args).compile()

    return compile_


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def test_gram_compiles(compile_tpu):
    c = compile_tpu(kops.gram_xtx, ((512, D_FF), jnp.bfloat16))
    assert _has_kernel(c)


@pytest.mark.parametrize("d_model,d_ff", [(D_MODEL, D_FF), (4096, 13696)])
def test_swap_topk_stacked_compiles(compile_tpu, d_model, d_ff):
    """The swap search over a stacked (N, R, d) down-proj group, with the
    tiles it picks at minitron-4b's widths and at chatglm3-6b's, whose
    d_ff 13696 pads to 13824."""
    f32 = jnp.float32
    run = jax.vmap(lambda w, m, c, g: kops.swap_topk(w, m, c, g, k=8))
    c = compile_tpu(run, ((N_LAYERS, d_model, d_ff), f32),
                    ((N_LAYERS, d_model, d_ff), f32),
                    ((N_LAYERS, d_model, d_ff), f32),
                    ((N_LAYERS, d_ff, d_ff), f32))
    assert _has_kernel(c)


def test_swap_topk_commit_compiles(compile_tpu):
    f32 = jnp.float32
    c = compile_tpu(lambda w, m, c, g: kops.swap_topk_commit(w, m, c, g, k=8),
                    ((D_MODEL, D_FF), f32), ((D_MODEL, D_FF), f32),
                    ((D_MODEL, D_FF), f32), ((D_FF, D_FF), f32))
    assert _has_kernel(c)


@pytest.mark.parametrize("block", [None, 4])
def test_group_refinement_compiles(compile_tpu, block):
    """The vmapped refinement loop exactly as ``pruning.engine`` runs the
    stacked down-proj group: unstructured on the Pallas backend (k-swap,
    column commit) and 2:4 (block search). Either fits one chip's 16 GB;
    a (..., 4)-minor array would be padded to 128 lanes and would not."""
    f32 = jnp.float32
    run = jax.vmap(lambda w, m, g: sparseswaps._refine_block(
        w, m, g, t_max=4, eps=0.0, method="pallas", block=block, chunk=512,
        track_history=False, k_swaps=8))
    c = compile_tpu(run, ((N_LAYERS, D_MODEL, D_FF), f32),
                    ((N_LAYERS, D_MODEL, D_FF), f32),
                    ((N_LAYERS, D_FF, D_FF), f32))
    mem = c.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 16e9
    if block is None:
        assert _has_kernel(c)


def test_row_loss_matmul_not_fused_into_reduce(compile_tpu):
    """Row losses of a stacked down-proj group: the HIGHEST matmul is
    materialized in full before the multiply-reduce. Fused into one
    output fusion, a v5e returned losses that were off by several times
    their value, negative ones included."""
    f32 = jnp.float32
    c = compile_tpu(jax.vmap(swap_math.row_loss),
                    ((N_LAYERS, D_MODEL, D_FF), f32),
                    ((N_LAYERS, D_MODEL, D_FF), f32),
                    ((N_LAYERS, D_FF, D_FF), f32))
    dots = [line for line in c.as_text().splitlines()
            if "dot_general" in line and " fusion(" in line]
    assert dots
    assert all(f"f32[{N_LAYERS},{D_MODEL},{D_FF}]" in line for line in dots)


@pytest.mark.parametrize("d_out,d_in", [(D_MODEL, D_FF), (D_FF, D_MODEL)])
@pytest.mark.parametrize("T", [8, 512])
@pytest.mark.parametrize("fmt", ["nm24", "gathered"])
def test_spmm_compiles(compile_tpu, fmt, T, d_out, d_in):
    """Both packed formats at decode (T=8) and prefill (T=512) token
    counts, in both MLP orientations; the fused kernel never densifies
    the weight in HBM."""
    bf16 = jnp.bfloat16
    if fmt == "nm24":
        k, idx_dt = d_in // 2, jnp.uint8
        fn = lambda x, v, i: spmm.spmm_nm24(
            x, v, i, kernel="pallas", interpret=False, act="relu2")
    else:
        k, idx_dt = int(d_in * 0.4), jnp.int32
        fn = lambda x, v, i: spmm.spmm_gather(
            x, v, i, d_in=d_in, kernel="pallas", interpret=False,
            act="relu2")
    with spmm.record_dispatch() as rec:
        c = compile_tpu(fn, ((T, d_in), bf16), ((d_out, k), bf16),
                        ((d_out, k), idx_dt))
    assert [(r["kernel"], r["fmt"]) for r in rec] == [("pallas", fmt)]
    assert _has_kernel(c)
    assert c.memory_analysis().temp_size_in_bytes < d_out * d_in * 2


@pytest.mark.parametrize("k", [D_MODEL // 2, int(0.4 * D_MODEL)])
def test_pack_gather_fits(compile_tpu, k):
    """The packers' gather over a stacked 4-layer up-proj group (2:4
    keeps d_in/2 columns, unstructured 0.6 keeps 0.4 d_in) needs little
    beyond its operands; gathering from a (..., d_in/4, 4) view instead
    needed 14.7 GB of temp on one chip."""
    c = compile_tpu(packed.take_cols,
                    ((N_LAYERS, D_FF, D_MODEL), jnp.bfloat16),
                    ((N_LAYERS, D_FF, k), jnp.int32))
    mem = c.memory_analysis()
    assert mem.temp_size_in_bytes <= 2 * mem.argument_size_in_bytes


@pytest.mark.parametrize("tiles,fits", [((512, 512, 512, 512), True),
                                        ((512, 512, 1024, 1024), False)])
def test_spmm_vmem_estimate_matches_compiler(compile_tpu, tiles, fits):
    """``_vmem_bytes`` against the compiler's own VMEM accounting: tiles
    the estimate puts under ``_VMEM_BOUND`` compile, and tiles it puts
    past the 16 MiB scoped limit are refused."""
    tt, to, td, ts = tiles
    est = spmm._vmem_bytes({"tile_t": tt, "tile_o": to, "tile_d": td,
                            "tile_s": ts}, 2, 2)
    assert (est <= spmm._VMEM_BOUND) if fits else (est > 16 * 2**20)
    fn = lambda x, v, c, b: spmm._spmm_padded(
        x, v, c, b, nm_aligned=False, tile_t=tt, tile_o=to, tile_d=td,
        tile_s=ts, act=None, interpret=False)
    shapes = (((1024, 4096), jnp.bfloat16), ((4096, 2048), jnp.bfloat16),
              ((4096, 2048), jnp.int32), ((1, 4096), jnp.float32))
    if fits:
        assert _has_kernel(compile_tpu(fn, *shapes))
    else:
        with pytest.raises(Exception, match="vmem"):
            compile_tpu(fn, *shapes)


@pytest.mark.parametrize("fmt", ["nm24", "gathered"])
def test_spmm_compiles_on_mesh(four_chips, no_compile_cache, fmt):
    """On a four-chip mesh, with the packed leaves placed as
    ``dist.specs`` places them, the kernel runs per device on its own
    rows: XLA cannot partition a Mosaic call itself."""
    from repro.dist import specs
    d_out, d_in, T = D_FF, D_MODEL, 512
    k = d_in // 2 if fmt == "nm24" else int(d_in * 0.4)
    idx_dt = jnp.uint8 if fmt == "nm24" else jnp.int32
    rows = NamedSharding(four_chips, specs.packed_pspec((d_out, k),
                                                        four_chips))
    assert rows.spec == P(("data", "model"), None)
    args = [jax.ShapeDtypeStruct((T, d_in), jnp.bfloat16,
                                 sharding=NamedSharding(four_chips,
                                                        P("data"))),
            jax.ShapeDtypeStruct((d_out, k), jnp.bfloat16, sharding=rows),
            jax.ShapeDtypeStruct((d_out, k), idx_dt, sharding=rows)]
    kw = dict(kernel="pallas", interpret=False, mesh=four_chips)
    if fmt == "nm24":
        fn = lambda x, v, i: spmm.spmm_nm24(x, v, i, **kw)
    else:
        fn = lambda x, v, i: spmm.spmm_gather(x, v, i, d_in=d_in, **kw)
    c = jax.jit(fn).lower(*args).compile()
    assert _has_kernel(c)
    # the packed operands stay where they were placed: no collective
    # moves them (only x is gathered to every device)
    hlo = c.as_text()
    assert "all-to-all" not in hlo and "collective-permute" not in hlo


def test_spmm_mesh_refuses_uneven_rows(four_chips):
    """Rows that do not split over the mesh are refused, never served
    replicated on every device."""
    v = jnp.zeros((6, 4), jnp.float32)
    with pytest.raises(ValueError, match="do not split"):
        jax.eval_shape(lambda x, v, i: spmm.spmm_gather(
            x, v, i, d_in=8, kernel="pallas", interpret=True,
            mesh=four_chips), jnp.zeros((2, 8)), v, v.astype(jnp.int32))
