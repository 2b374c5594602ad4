"""Distributed refinement + multi-device behaviours (subprocess: these need
more than one device, so they run with their own XLA_FLAGS)."""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

SRC = str(Path(__file__).resolve().parents[1] / "src")


def run_py(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


def test_rows_sharded_matches_reference():
    out = run_py("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.launch import mesh as mesh_lib
        from repro.core import masks, warmstart, sparseswaps
        from repro.pruning import distributed as dist
        rng = np.random.default_rng(0)
        X = rng.normal(size=(48, 300)).astype(np.float32)
        W = rng.normal(size=(32, 48)).astype(np.float32)
        G = jnp.asarray(X @ X.T)
        pat = masks.PerRow(0.5)
        m0 = warmstart.warmstart_mask(jnp.asarray(W), G, pat, "wanda")
        mesh = mesh_lib.make_mesh((8,), ("data",))
        ref = sparseswaps.refine(jnp.asarray(W), G, m0, pat, t_max=15,
                                 method="chunked")
        m1, l0, l1 = dist.refine_rows_sharded(jnp.asarray(W), G, m0, pat,
                                              mesh, t_max=15)
        print("MATCH", bool(jnp.all(m1 == ref.mask)))
    """)
    assert "MATCH True" in out


def test_g_sharded_matches_reference():
    out = run_py("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.launch import mesh as mesh_lib
        from repro.core import masks, warmstart, sparseswaps
        from repro.pruning import distributed as dist
        rng = np.random.default_rng(1)
        X = rng.normal(size=(64, 300)).astype(np.float32)
        W = rng.normal(size=(16, 64)).astype(np.float32)
        G = jnp.asarray(X @ X.T)
        pat = masks.PerRow(0.5)
        m0 = warmstart.warmstart_mask(jnp.asarray(W), G, pat, "wanda")
        ref = sparseswaps.refine(jnp.asarray(W), G, m0, pat, t_max=12,
                                 method="chunked")
        for shape, names in [((8,), ("data",)), ((4, 2), ("data", "model"))]:
            mesh = mesh_lib.make_mesh(shape, names)
            m2, _, _ = dist.refine_g_sharded(jnp.asarray(W), G, m0, pat,
                                             mesh, t_max=12)
            print("MATCH", shape, bool(jnp.all(m2 == ref.mask)))
    """)
    assert out.count("True") == 2


def test_rows_sharded_kswap_matches_reference():
    """The k-swap step (top-k search + column-rescored commit) sharded
    over rows is bit-identical to the single-device k-swap loop."""
    out = run_py("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.launch import mesh as mesh_lib
        from repro.core import masks, warmstart, sparseswaps
        from repro.pruning import distributed as dist
        rng = np.random.default_rng(0)
        X = rng.normal(size=(48, 300)).astype(np.float32)
        W = rng.normal(size=(32, 48)).astype(np.float32)
        G = jnp.asarray(X @ X.T)
        pat = masks.PerRow(0.5)
        m0 = warmstart.warmstart_mask(jnp.asarray(W), G, pat, "wanda")
        mesh = mesh_lib.make_mesh((8,), ("data",))
        ref = sparseswaps.refine(jnp.asarray(W), G, m0, pat, t_max=15,
                                 method="chunked", k_swaps=8)
        m1, l0, l1 = dist.refine_rows_sharded(jnp.asarray(W), G, m0, pat,
                                              mesh, t_max=15, k_swaps=8)
        print("MATCH", bool(jnp.all(m1 == ref.mask)))
    """)
    assert "MATCH True" in out


def test_g_sharded_kswap_matches_reference():
    """Gram-sharded k-swap (distributed top-k merge + psum'd column
    commit) is bit-identical to single-device k-swap on 1-D and 2-D
    meshes, at k = 1 and k = 8."""
    out = run_py("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.launch import mesh as mesh_lib
        from repro.core import masks, warmstart, sparseswaps
        from repro.pruning import distributed as dist
        rng = np.random.default_rng(1)
        X = rng.normal(size=(64, 300)).astype(np.float32)
        W = rng.normal(size=(16, 64)).astype(np.float32)
        G = jnp.asarray(X @ X.T)
        pat = masks.PerRow(0.5)
        m0 = warmstart.warmstart_mask(jnp.asarray(W), G, pat, "wanda")
        for k in (1, 8):
            ref = sparseswaps.refine(jnp.asarray(W), G, m0, pat, t_max=12,
                                     method="chunked", k_swaps=k)
            for shape, names in [((8,), ("data",)),
                                 ((4, 2), ("data", "model"))]:
                mesh = mesh_lib.make_mesh(shape, names)
                m2, _, _ = dist.refine_g_sharded(jnp.asarray(W), G, m0, pat,
                                                 mesh, t_max=12, k_swaps=k)
                print("MATCH", k, shape, bool(jnp.all(m2 == ref.mask)))
    """)
    assert out.count("True") == 4


def test_nm_rows_sharded():
    out = run_py("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.launch import mesh as mesh_lib
        from repro.core import masks, warmstart, sparseswaps
        from repro.pruning import distributed as dist
        rng = np.random.default_rng(2)
        X = rng.normal(size=(32, 200)).astype(np.float32)
        W = rng.normal(size=(16, 32)).astype(np.float32)
        G = jnp.asarray(X @ X.T)
        pat = masks.NM(2, 4)
        m0 = warmstart.warmstart_mask(jnp.asarray(W), G, pat, "wanda")
        mesh = mesh_lib.make_mesh((8,), ("data",))
        ref = sparseswaps.refine(jnp.asarray(W), G, m0, pat, t_max=10)
        m1, _, _ = dist.refine_rows_sharded(jnp.asarray(W), G, m0, pat, mesh,
                                            t_max=10)
        print("MATCH", bool(jnp.all(m1 == ref.mask)))
    """)
    assert "MATCH True" in out


def test_prune_model_mesh_matches_single_device():
    """prune_model(mesh=make_host_mesh()) on an 8-device CPU mesh is
    bit-identical to the single-device per-instance reference, for both
    unstructured and N:M patterns (the pipeline's mesh dispatch path)."""
    out = run_py("""
        import jax, jax.numpy as jnp
        import repro.configs as C, repro.models as M
        from repro import pruning
        from repro.core import masks
        from repro.launch import mesh as mesh_lib
        cfg = C.get_tiny("llama31-8b"); api = M.build(cfg)
        params = api.init(jax.random.key(0))
        batches = list(pruning.calibration_batches(cfg, n_samples=2,
                                                   seq_len=24, batch_size=2))
        taps = pruning.accumulate(api, params, batches)
        mesh = mesh_lib.make_host_mesh()
        for pat in (masks.PerRow(0.6), masks.NM(2, 4)):
            ref = pruning.prune_model(api, params, None, pat, t_max=8,
                                      taps=taps, swap_method="chunked",
                                      engine_mode="reference")
            got = pruning.prune_model(api, params, None, pat, t_max=8,
                                      taps=taps, mesh=mesh)
            same = jax.tree.all(jax.tree.map(
                lambda a, b: bool(jnp.all(a == b)), ref.masks, got.masks))
            print("MATCH", pat.describe(), same)
    """)
    assert out.count("True") == 2


def test_prune_model_mesh_gram_sharded_fallback():
    """Forcing the per-device Gram replication budget to zero routes
    unstructured sites through the column-sharded-G refiner — same masks."""
    out = run_py("""
        import jax, jax.numpy as jnp
        import repro.configs as C, repro.models as M
        from repro import pruning
        from repro.core import masks
        from repro.launch import mesh as mesh_lib
        cfg = C.get_tiny("llama31-8b"); api = M.build(cfg)
        params = api.init(jax.random.key(0))
        batches = list(pruning.calibration_batches(cfg, n_samples=2,
                                                   seq_len=24, batch_size=2))
        taps = pruning.accumulate(api, params, batches)
        mesh = mesh_lib.make_host_mesh()
        pat = masks.PerRow(0.5)
        ref = pruning.prune_model(api, params, None, pat, t_max=6, taps=taps,
                                  swap_method="chunked",
                                  engine_mode="reference")
        got = pruning.prune_model(api, params, None, pat, t_max=6, taps=taps,
                                  mesh=mesh, gram_budget_bytes=0)
        same = jax.tree.all(jax.tree.map(
            lambda a, b: bool(jnp.all(a == b)), ref.masks, got.masks))
        print("MATCH", same)
    """)
    assert "MATCH True" in out


def test_data_parallel_gram_psum():
    """Gram accumulated per-shard + psum == global Gram."""
    out = run_py("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.launch import mesh as mesh_lib
        from functools import partial
        from jax.sharding import PartitionSpec as P
        from repro.core import gram as gram_lib
        rng = np.random.default_rng(3)
        acts = rng.normal(size=(8, 16, 12)).astype(np.float32)
        mesh = mesh_lib.make_mesh((8,), ("data",))
        @partial(jax.shard_map, mesh=mesh, in_specs=P("data", None, None),
                 out_specs=P())
        def sharded_gram(a):
            st = gram_lib.GramState.create(12).update(a)
            return gram_lib.psum_gram(st, "data").G
        got = sharded_gram(jnp.asarray(acts))
        x = acts.reshape(-1, 12)
        print("MATCH", np.allclose(np.asarray(got), x.T @ x, rtol=1e-4,
                                   atol=1e-2))
    """)
    assert "MATCH True" in out


def test_elastic_checkpoint_reshard():
    """Save sharded on 8 devices -> restore onto 4-device mesh (and back)."""
    out = run_py("""
        import tempfile, numpy as np, jax, jax.numpy as jnp
        from repro.launch import mesh as mesh_lib
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro import ckpt
        mesh8 = mesh_lib.make_mesh((8,), ("data",))
        w = jnp.arange(64.0).reshape(8, 8)
        w = jax.device_put(w, NamedSharding(mesh8, P("data", None)))
        d = tempfile.mkdtemp()
        ckpt.save(d, 1, {"w": w})
        mesh4 = mesh_lib.make_mesh((4, 2), ("data", "model"))
        sh = {"w": NamedSharding(mesh4, P("model", "data"))}
        got, _ = ckpt.restore(d, 1, {"w": jax.ShapeDtypeStruct((8, 8),
                                                              jnp.float32)},
                              shardings=sh)
        print("MATCH", np.allclose(np.asarray(got["w"]), np.asarray(w)))
    """)
    assert "MATCH True" in out


def test_train_step_sharded_runs():
    """One real sharded train step on an 8-device host mesh."""
    out = run_py("""
        import jax, jax.numpy as jnp
        import repro.configs as C, repro.models as M
        from repro.launch import mesh as mesh_lib
        from repro.optim import adamw
        from repro.train import steps
        from repro.data import synthetic
        cfg = C.get_tiny("llama31-8b")
        api = M.build(cfg)
        mesh = mesh_lib.make_mesh((4, 2), ("data", "model"))
        with mesh_lib.activate(mesh, cfg):
            st = steps.init_state(api, jax.random.key(0))
            ts = steps.make_train_step(api, adamw.AdamWConfig(lr=1e-3))
            pipe = synthetic.DataPipeline(
                synthetic.CorpusConfig(cfg.vocab_size), 8, 32)
            for i in range(3):
                st, m = ts(st, pipe.get(i))
        print("LOSS", float(m["loss"]), bool(jnp.isfinite(m["loss"])))
    """)
    assert "True" in out
