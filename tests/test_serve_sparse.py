"""Sparse serving parity: packed decode == masked-dense decode.

The acceptance surface of the serving runtime: for transformer, MoE and
zamba tiny configs, prefill+decode on packed weights (both formats, both
kernels) is allclose (atol 1e-5, f32) to the masked-dense reference —
single-device here, on an 8-device host mesh in the subprocess test —
plus the executor-ckpt -> serve round-trip and the ``--masks-from`` fix.
"""
import gc
import os
import subprocess
import sys
import textwrap
import weakref
from pathlib import Path

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import repro.configs as configs
import repro.models as models
from repro import pruning
from repro.core import masks as masks_lib
from repro.data import synthetic
from repro.serve import ServeEngine, bench_rows

SRC = str(Path(__file__).resolve().parents[1] / "src")

ARCHS = ["llama31-8b", "mixtral-8x7b", "zamba2-7b"]


def _setup(arch, pattern, *, method="none", seed=0):
    cfg = configs.get_tiny(arch)
    api = models.build(cfg)
    params = api.init(jax.random.key(seed))
    batches = list(pruning.calibration_batches(
        cfg, n_samples=2, seq_len=16, batch_size=2, seed=seed))
    rep = pruning.prune_model(api, params, batches, pattern, method=method,
                              t_max=3)
    pipe = synthetic.DataPipeline(synthetic.CorpusConfig(cfg.vocab_size),
                                  2, 8, split="val")
    prompt = synthetic.with_modality(pipe.get(0), cfg, jax.random.key(seed))
    return cfg, api, params, rep, prompt


@pytest.mark.parametrize("arch", ARCHS)
def test_packed_decode_allclose_masked_dense(arch):
    """nm24 + gathered decode logits allclose (atol 1e-5) to masked-dense
    on the acceptance matrix; greedy tokens identical."""
    cfg, api, params, rep, prompt = _setup(arch, masks_lib.NM(2, 4))
    ref_eng = ServeEngine(api, params, masks=rep, fmt="masked")
    ref = np.asarray(ref_eng.logits_trace(prompt, 4))
    ref_toks = np.asarray(ref_eng.generate(prompt, 4).tokens)
    for fmt in ("nm24", "gathered"):
        eng = ServeEngine(api, params, masks=rep, fmt=fmt, kernel="jnp")
        got = np.asarray(eng.logits_trace(prompt, 4))
        np.testing.assert_allclose(got, ref, atol=1e-5, err_msg=fmt)
        np.testing.assert_array_equal(
            np.asarray(eng.generate(prompt, 4).tokens), ref_toks)
        assert eng.weight_bytes() < ref_eng.weight_bytes()


def test_pallas_kernel_decode_allclose():
    """kernel="pallas" (interpret on CPU) serves allclose to masked-dense
    — the Pallas spmm wiring end to end (one arch: interpret is slow)."""
    cfg, api, params, rep, prompt = _setup("llama31-8b", masks_lib.NM(2, 4))
    ref = np.asarray(ServeEngine(api, params, masks=rep,
                                 fmt="masked").logits_trace(prompt, 2))
    got = np.asarray(ServeEngine(api, params, masks=rep, fmt="nm24",
                                 kernel="pallas").logits_trace(prompt, 2))
    np.testing.assert_allclose(got, ref, atol=1e-5)


def test_sparseswaps_refined_perrow_serves_gathered():
    """A real SparseSwaps refinement (equal-R by construction) serves
    through the gathered format with identical tokens."""
    cfg, api, params, rep, prompt = _setup(
        "llama31-8b", masks_lib.PerRow(0.5), method="sparseswaps")
    ref = ServeEngine(api, params, masks=rep, fmt="masked")
    eng = ServeEngine(api, params, masks=rep, fmt="gathered")
    np.testing.assert_array_equal(
        np.asarray(eng.generate(prompt, 4).tokens),
        np.asarray(ref.generate(prompt, 4).tokens))


def test_executor_ckpt_to_serve_roundtrip(tmp_path):
    """Masks checkpointed by a PruneExecutor run serve identically to the
    in-memory report, through every --masks-from resolution rule."""
    cfg = configs.get_tiny("llama31-8b")
    api = models.build(cfg)
    params = api.init(jax.random.key(0))
    batches = list(pruning.calibration_batches(cfg, n_samples=2, seq_len=16,
                                               batch_size=2))
    plan = pruning.plan_pruning(
        api, params,
        pruning.PruneRecipe.single(masks_lib.NM(2, 4), method="sparseswaps",
                                   t_max=3))
    ex = pruning.PruneExecutor(api, params, plan, ckpt_dir=tmp_path)
    rep = ex.run(batches)
    pipe = synthetic.DataPipeline(synthetic.CorpusConfig(cfg.vocab_size),
                                  2, 8, split="val")
    prompt = pipe.get(0)
    want = np.asarray(ServeEngine(api, params, masks=rep,
                                  fmt="nm24").generate(prompt, 4).tokens)
    # executor group checkpoints (the dir the executor was given)
    eng = ServeEngine.from_executor_ckpt(api, params, tmp_path, fmt="nm24")
    np.testing.assert_array_equal(
        np.asarray(eng.generate(prompt, 4).tokens), want)


def test_sparsegpt_ckpt_serves_updated_weights(tmp_path):
    """SparseGPT checkpoints carry updated weights; serving --masks-from
    must splice them in, not pack the original weights under the mask."""
    cfg = configs.get_tiny("llama31-8b")
    api = models.build(cfg)
    params = api.init(jax.random.key(0))
    batches = list(pruning.calibration_batches(cfg, n_samples=2, seq_len=16,
                                               batch_size=2))
    plan = pruning.plan_pruning(
        api, params,
        pruning.PruneRecipe.single(masks_lib.PerRow(0.5),
                                   method="sparsegpt"))
    ex = pruning.PruneExecutor(api, params, plan, ckpt_dir=tmp_path)
    rep = ex.run(batches)
    assert rep.updated_params is not None
    pipe = synthetic.DataPipeline(synthetic.CorpusConfig(cfg.vocab_size),
                                  2, 8, split="val")
    prompt = pipe.get(0)
    # ground truth: the report's updated weights, masked
    want = ServeEngine(api, rep.updated_params, masks=rep.masks,
                       fmt="gathered").logits_trace(prompt, 3)
    got = ServeEngine.from_executor_ckpt(api, params, tmp_path,
                                         fmt="gathered").logits_trace(
                                             prompt, 3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    # the report object resolves its own updated weights too
    via_report = ServeEngine(api, params, masks=rep,
                             fmt="gathered").logits_trace(prompt, 3)
    np.testing.assert_allclose(np.asarray(via_report), np.asarray(want),
                               atol=1e-5)
    # ... and so does an export_packed artifact dir (masks + weights dump)
    ex.export_packed(tmp_path / "export", "gathered")
    via_export = ServeEngine(api, params, masks=tmp_path / "export",
                             fmt="gathered").logits_trace(prompt, 3)
    np.testing.assert_allclose(np.asarray(via_export), np.asarray(want),
                               atol=1e-5)
    # ... and a launcher --out-dir root, where BOTH a mask-only masks/
    # tree and the executor prune_ckpt/ coexist: the executor ckpts (the
    # only artifact carrying new_weights) must win the resolution
    from repro import ckpt as ckpt_lib
    root = tmp_path / "root"
    ckpt_lib.save(root / "masks", 0, rep.masks)
    (root / "prune_ckpt").symlink_to(tmp_path, target_is_directory=True)
    via_root = ServeEngine(api, params, masks=root,
                           fmt="gathered").logits_trace(prompt, 3)
    np.testing.assert_allclose(np.asarray(via_root), np.asarray(want),
                               atol=1e-5)


def test_serve_launcher_masks_from(tmp_path):
    """launch/serve.py --masks-from loads a pruning run's artifacts (the
    old code raised SystemExit unconditionally)."""
    from repro.launch.prune import prune
    from repro.launch.serve import serve
    prune("llama31-8b", tiny=True, pattern="2:4", method="none", t_max=2,
          n_calib=2, calib_seq=16, out_dir=str(tmp_path), verbose=False)
    out = serve("llama31-8b", tiny=True, batch=2, prompt_len=8, gen=3,
                masks_from=str(tmp_path), fmt="nm24", verbose=False)
    assert out["tokens"].shape == (2, 3) and out["format"] == "nm24"
    # masks given but no format -> faithful masked-dense default, through
    # the CLI entry point too (argparse must not pin a dense default)
    out2 = serve("llama31-8b", tiny=True, batch=2, prompt_len=8, gen=3,
                 masks_from=str(tmp_path), verbose=False)
    assert out2["format"] == "masked"
    np.testing.assert_array_equal(np.asarray(out["tokens"]),
                                  np.asarray(out2["tokens"]))
    from repro.launch.serve import main as serve_main
    import contextlib, io
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        serve_main(["--arch", "llama31-8b", "--tiny", "--batch", "2",
                    "--prompt-len", "8", "--gen", "3",
                    "--masks-from", str(tmp_path)])
    assert "format=masked" in buf.getvalue()


def test_serve_launcher_masks_from_missing_raises(tmp_path):
    from repro.launch.serve import serve
    with pytest.raises(FileNotFoundError, match="no mask checkpoint"):
        serve("llama31-8b", tiny=True, batch=2, prompt_len=8, gen=2,
              masks_from=str(tmp_path / "nothing"), verbose=False)


def test_engine_error_paths():
    cfg = configs.get_tiny("llama31-8b")
    api = models.build(cfg)
    params = api.init(jax.random.key(0))
    with pytest.raises(ValueError, match="unknown serve format"):
        ServeEngine(api, params, fmt="csr")
    with pytest.raises(ValueError, match="needs masks"):
        ServeEngine(api, params, fmt="nm24")


@pytest.mark.parametrize("fmt", ["masked", "gathered"])
def test_engine_freed_without_gc(fmt):
    """A dropped engine frees its device weights at once: no reference
    cycle (a compiled fn closing over the engine) keeps it alive until
    the cycle collector runs — at published widths two such engines fill
    a 16 GB chip. Covers the batch loop and the scheduler's paged path."""
    from repro.serve import ContinuousScheduler
    cfg, api, params, rep, prompt = _setup("llama31-8b", masks_lib.PerRow(0.5))
    eng = ServeEngine(api, params, masks=rep, fmt=fmt, kernel="jnp")
    eng.generate(prompt, 3)
    eng.logits_trace(prompt, 2)
    sch = ContinuousScheduler(eng, max_batch=2, capacity=32, page_size=8,
                              decode_chunk=2)
    sch.submit(prompt["tokens"][0], 3)
    assert len(sch.run_until_idle()) == 1
    ref = weakref.ref(eng)
    gc.disable()
    try:
        del eng, sch
        assert ref() is None, "engine outlived its last reference"
    finally:
        gc.enable()


def test_bench_rows_per_phase_schema():
    """One prefill + one decode row per variant, kernel_used recorded,
    and the whole doc passes the CI schema guard
    (benchmarks/check_serve_bench.py)."""
    cfg, api, params, rep, prompt = _setup("llama31-8b", masks_lib.NM(2, 4))
    rows = bench_rows(api, params, rep, prompt, 3,
                      formats=("dense", "masked", "nm24"), kernel="jnp",
                      repeats=1)
    by = {(r["variant"], r["phase"]): r for r in rows}
    assert set(by) == {(v, p) for v in ("dense", "masked", "nm24")
                       for p in ("prefill", "decode")}
    assert by[("nm24", "prefill")]["weight_bytes"] < \
        by[("masked", "prefill")]["weight_bytes"]
    assert all(r["tok_s"] > 0 for r in rows)
    assert "prefill_s" in by[("nm24", "prefill")]
    assert "cold_tok_s" in by[("nm24", "decode")]
    # packed variants record the spmm kernel that actually served them;
    # dense/masked serve plain matmuls
    assert by[("nm24", "prefill")]["kernel_used"] == "jnp"
    assert by[("nm24", "decode")]["kernel_used"] == "jnp"
    assert by[("dense", "decode")]["kernel_used"] == "dense"
    # the committed-bench guard accepts the schema (ratio check included
    # — jnp nm24 prefill must stay within 50x here only to catch gross
    # wiring breakage, not a perf bound at tiny test shapes)
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "check_serve_bench",
        Path(__file__).resolve().parents[1] / "benchmarks"
        / "check_serve_bench.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    doc = {"arch": cfg.name, "batch": 2, "prompt_len": 8, "gen": 3,
           "devices": 1, "rows": rows}
    assert mod.check(doc, max_nm24_prefill_ratio=50.0) == []
    # and a malformed doc is caught
    bad = dict(doc, rows=[dict(rows[0], kernel_used="")])
    assert mod.check(bad, max_nm24_prefill_ratio=50.0)


def test_prefill_decode_logits_consistent():
    """The scanned decode loop agrees with prefill: re-prefilling the
    prompt extended by the generated tokens reproduces the per-step
    decode logits (KV-cache parity), and generate()'s tokens are the
    argmax of the trace."""
    cfg, api, params, rep, prompt = _setup("llama31-8b", masks_lib.NM(2, 4))
    n_new = 5
    eng = ServeEngine(api, params, masks=rep, fmt="nm24")
    trace = np.asarray(eng.logits_trace(prompt, n_new))   # (n_new, B, V)
    toks = np.asarray(eng.generate(prompt, n_new).tokens)  # (B, n_new)
    np.testing.assert_array_equal(toks, trace.argmax(-1).T)
    # deterministic: a second trace is bitwise identical
    np.testing.assert_array_equal(
        np.asarray(eng.logits_trace(prompt, n_new)), trace)
    # teacher-forced prefills: re-prefilling the prompt extended by the
    # first i generated tokens must land on the logits decode step i
    # produced (prefill returns only the last position). allclose, not
    # bitwise — XLA schedules the (B, S+i) prefill matmuls differently
    # from the (B, 1) decode steps, so fp32 reductions legitimately
    # differ in the lsb.
    from repro.train import steps as steps_lib
    from repro.models import common
    ptoks = np.asarray(prompt["tokens"])
    B, S = ptoks.shape
    with common.use_matmul_policy(common.PackedMatmulPolicy("jnp")):
        eng2 = ServeEngine(api, params, masks=rep, fmt="nm24")
        prefill, _ = steps_lib.make_serve_steps(api, masks=eng2.masks)
        for i in range(n_new):
            ext = dict(prompt)
            ext["tokens"] = np.concatenate([ptoks, toks[:, :i]], axis=1)
            ext["labels"] = np.zeros_like(ext["tokens"])
            cache = api.init_cache(eng2.params, B, S + i)
            logits, _ = prefill(eng2.params, ext, cache)
            np.testing.assert_allclose(
                np.asarray(logits[:, -1], np.float32), trace[i],
                atol=1e-4, rtol=1e-4, err_msg=f"step {i}")


@pytest.mark.parametrize("arch", ["rwkv6-1.6b", "seamless-m4t-medium",
                                  "llama-3.2-vision-90b"])
def test_masked_serving_equals_hard_zero_all_families(arch):
    """Masked prefill+decode == serving hard-zeroed weights dense.

    Regression pin for two latent mask-routing bugs the packed runtime
    surfaced: rwkv layers passed the per-layer mask dict one level too
    high (the "tm" subtree was never consulted), and the enc-dec / VLM
    cross-KV precompute projected the encoder states with *unmasked*
    wk/wv. Packed decode must agree too — it bakes the mask in.
    """
    cfg, api, params, rep, prompt = _setup(arch, masks_lib.NM(2, 4))
    hard = pruning.apply(jax.tree.map(lambda x: x, params), rep.masks)
    from repro.train import steps as steps_lib
    want = steps_lib.greedy_decode(api, hard, prompt, 3)
    got_masked = steps_lib.greedy_decode(api, params, prompt, 3,
                                         masks=rep.masks)
    np.testing.assert_array_equal(np.asarray(got_masked), np.asarray(want))
    from repro.core import packed
    got_packed = steps_lib.greedy_decode(
        api, packed.pack_tree(cfg, params, rep.masks, "nm24"), prompt, 3)
    np.testing.assert_array_equal(np.asarray(got_packed), np.asarray(want))


@pytest.mark.slow
def test_mesh_sharded_packed_serve_matches_single_device():
    """8-device host mesh: packed weights sharded with dist.specs serve
    the same tokens as single-device masked-dense (subprocess)."""
    code = """
        import numpy as np, jax
        import repro.configs as configs, repro.models as models
        from repro import pruning
        from repro.core import masks as masks_lib
        from repro.data import synthetic
        from repro.launch import mesh as mesh_lib
        from repro.serve import ServeEngine

        assert len(jax.devices()) == 8
        mesh = mesh_lib.make_host_mesh(data=4, model=2)
        for arch in ("llama31-8b", "mixtral-8x7b", "zamba2-7b"):
            cfg = configs.get_tiny(arch)
            api = models.build(cfg)
            params = api.init(jax.random.key(0))
            batches = list(pruning.calibration_batches(
                cfg, n_samples=2, seq_len=16, batch_size=2))
            rep = pruning.prune_model(api, params, batches,
                                      masks_lib.NM(2, 4), method="none")
            pipe = synthetic.DataPipeline(
                synthetic.CorpusConfig(cfg.vocab_size), 4, 8, split="val")
            prompt = synthetic.with_modality(pipe.get(0), cfg,
                                             jax.random.key(0))
            want = ServeEngine(api, params, masks=rep,
                               fmt="masked").generate(prompt, 4).tokens
            eng = ServeEngine(api, params, masks=rep, fmt="nm24",
                              kernel="jnp", mesh=mesh)
            # packed leaves actually landed sharded on the mesh
            n_sh = sum(
                1 for l in jax.tree.leaves(eng.params)
                if len(getattr(l.sharding, "device_set", [])) == 8)
            assert n_sh > 0, "no leaf sharded over the mesh"
            got = eng.generate(prompt, 4).tokens
            np.testing.assert_array_equal(np.asarray(got),
                                          np.asarray(want))
            print(arch, "OK")
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    for arch in ARCHS:
        assert f"{arch} OK" in out.stdout


def test_mesh_packed_pallas_runs_per_device():
    """The Pallas spmm under a 4-device mesh runs per device (a
    ``shard_map`` over output rows — XLA cannot partition a Mosaic call)
    and serves the same tokens and logits as one device (subprocess;
    interpret mode on the CPU)."""
    code = """
        import numpy as np, jax
        import repro.configs as configs, repro.models as models
        from repro import pruning
        from repro.core import masks as masks_lib
        from repro.data import synthetic
        from repro.launch import mesh as mesh_lib
        from repro.serve import ServeEngine

        assert len(jax.devices()) == 4
        mesh = mesh_lib.make_host_mesh()
        cfg = configs.get_tiny("llama31-8b")
        api = models.build(cfg)
        params = api.init(jax.random.key(0))
        batches = list(pruning.calibration_batches(
            cfg, n_samples=2, seq_len=16, batch_size=2))
        for pat, fmt in ((masks_lib.NM(2, 4), "nm24"),
                         (masks_lib.PerRow(0.6), "gathered")):
            rep = pruning.prune_model(api, params, batches, pat,
                                      method="none")
            pipe = synthetic.DataPipeline(
                synthetic.CorpusConfig(cfg.vocab_size), 4, 8, split="val")
            prompt = synthetic.with_modality(pipe.get(0), cfg,
                                             jax.random.key(0))
            one = ServeEngine(api, params, masks=rep, fmt=fmt,
                              kernel="pallas")
            four = ServeEngine(api, params, masks=rep, fmt=fmt,
                               kernel="pallas", mesh=mesh)
            np.testing.assert_array_equal(
                np.asarray(four.generate(prompt, 4).tokens),
                np.asarray(one.generate(prompt, 4).tokens))
            np.testing.assert_allclose(
                np.asarray(four.logits_trace(prompt, 1)),
                np.asarray(one.logits_trace(prompt, 1)),
                rtol=1e-5, atol=1e-5)
            print(fmt, "OK")
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "nm24 OK" in out.stdout and "gathered OK" in out.stdout
