"""Data pipeline statistics, training convergence, serving consistency."""
from pathlib import Path

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import repro.configs as configs
import repro.models as models
from repro.data import synthetic
from repro.optim import adamw
from repro.train import steps as steps_lib


def test_zipf_marginal_heavy_tail():
    cfg = synthetic.CorpusConfig(vocab_size=256, seed=0)
    toks = np.asarray(synthetic.sample_batch(cfg, jax.random.key(0), 64, 256))
    counts = np.bincount(toks.reshape(-1), minlength=256)
    top = np.sort(counts)[::-1]
    # heavy tail: top-10 tokens carry a large share, but not everything
    share = top[:10].sum() / counts.sum()
    assert 0.2 < share < 0.95


def test_markov_topic_correlation():
    """Adjacent tokens correlate via sticky topics: P(same-topic emission)
    markedly above independence."""
    cfg = synthetic.CorpusConfig(vocab_size=512, n_topics=4, stickiness=0.98)
    toks = np.asarray(synthetic.sample_batch(cfg, jax.random.key(1), 32, 512))
    # mutual information proxy: adjacent-pair repetition rate vs shuffled
    same_adj = np.mean(toks[:, 1:] == toks[:, :-1])
    rng = np.random.default_rng(0)
    shuf = toks.copy().reshape(-1)
    rng.shuffle(shuf)
    shuf = shuf.reshape(toks.shape)
    same_shuf = np.mean(shuf[:, 1:] == shuf[:, :-1])
    assert same_adj > 1.5 * same_shuf


def test_training_reduces_loss():
    cfg = configs.get_tiny("llama31-8b")
    api = models.build(cfg)
    state = steps_lib.init_state(api, jax.random.key(0))
    step = steps_lib.make_train_step(api, adamw.AdamWConfig(
        lr=2e-3, warmup_steps=5, total_steps=60))
    pipe = synthetic.DataPipeline(synthetic.CorpusConfig(cfg.vocab_size),
                                  8, 48)
    losses = []
    for i in range(60):
        state, m = step(state, pipe.get(i))
        losses.append(float(m["loss"]))
    assert np.mean(losses[-10:]) < np.mean(losses[:10]) - 0.3


def test_masked_finetune_keeps_mask_invariant():
    """Sparse finetuning: pruned weights stay exactly zero through updates."""
    from repro import pruning
    from repro.core import masks as masks_lib
    cfg = configs.get_tiny("llama31-8b")
    api = models.build(cfg)
    params = api.init(jax.random.key(0))
    batches = list(pruning.calibration_batches(cfg, n_samples=4, seq_len=32,
                                               batch_size=2))
    rep = pruning.prune_model(api, params, batches, masks_lib.PerRow(0.5),
                              method="none")
    params = pruning.apply(params, rep.masks)
    state = steps_lib.TrainState(params=params, opt=adamw.init(params))
    step = steps_lib.make_train_step(api, adamw.AdamWConfig(lr=1e-3),
                                     masks=rep.masks, donate=False)
    pipe = synthetic.DataPipeline(synthetic.CorpusConfig(cfg.vocab_size), 4, 32)
    for i in range(3):
        state, _ = step(state, pipe.get(i))
    w = state.params["layers"]["attn"]["wq"]
    m = rep.masks["layers"]["attn"]["wq"]
    assert float(jnp.max(jnp.abs(
        w.astype(jnp.float32) * (1 - m)))) == 0.0
    # and unpruned weights did move
    assert float(jnp.max(jnp.abs(w.astype(jnp.float32) * m))) > 0


def test_greedy_decode_matches_stepwise_forward():
    """prefill+decode greedy == argmax over repeated full forwards."""
    cfg = configs.get_tiny("llama31-8b")
    api = models.build(cfg)
    params = api.init(jax.random.key(0))
    pipe = synthetic.DataPipeline(synthetic.CorpusConfig(cfg.vocab_size), 2, 8)
    prompt = pipe.get(0)
    n_new = 4
    got = steps_lib.greedy_decode(api, params, prompt, n_new)
    # reference: repeatedly run the full forward on the growing sequence
    toks = prompt["tokens"]
    for _ in range(n_new):
        batch = {"tokens": toks, "labels": jnp.roll(toks, -1, 1)}
        hidden, _, _ = api.forward(params, batch)
        logits = api.module.lm_head(params, hidden, cfg)
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        toks = jnp.concatenate([toks, nxt[:, None]], axis=1)
    want = toks[:, -n_new:]
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_serve_launcher_smoke():
    from repro.launch.serve import serve
    out = serve("llama31-8b", tiny=True, batch=2, prompt_len=16, gen=4,
                verbose=False)
    assert out["tokens"].shape == (2, 4)


def test_prune_launcher_smoke(tmp_path):
    from repro.launch.prune import prune
    out = prune("llama31-8b", tiny=True, pattern="2:4", method="sparseswaps",
                t_max=5, n_calib=4, calib_seq=32, out_dir=str(tmp_path),
                verbose=False)
    assert out["report"].mean_error_reduction() > 0
    assert (tmp_path / "report.json").exists()


def test_launchers_cut_depth_only(capsys):
    """``n_layers`` replaces the depth and nothing else, and the run
    prints it next to the published depth."""
    import repro.configs as configs
    from repro.launch.prune import prune
    from repro.launch.serve import serve
    tiny = configs.get_tiny("minitron-4b")
    cut = configs.resolve("minitron-4b", tiny=True, n_layers=1)
    assert cut == tiny.replace(n_layers=1)
    assert configs.resolve("minitron-4b", n_layers=4) == \
        configs.get("minitron-4b").replace(n_layers=4)
    out = prune("minitron-4b", tiny=True, n_layers=1, t_max=2, n_calib=2,
                calib_seq=16, calib_batch=2)
    assert all(len(s.labels) == 1 for s in out["report"].sites)
    assert "n_layers=1 (published 32)" in capsys.readouterr().out
    out = serve("minitron-4b", tiny=True, n_layers=1, batch=2,
                prompt_len=8, gen=2)
    assert out["engine"].cfg == cut
    assert out["tokens"].shape == (2, 2)
    assert "n_layers=1 (published 32)" in capsys.readouterr().out


def test_compile_cache_dir(monkeypatch):
    """The env variable wins and nothing is set; otherwise the cache sits
    at a fixed path inside the checkout."""
    import jax
    from repro.launch import compile_cache
    prev = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    assert compile_cache.enable() == "/elsewhere"
    assert jax.config.jax_compilation_cache_dir == prev
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        path = compile_cache.enable()
        assert jax.config.jax_compilation_cache_dir == path
        root = Path(__file__).resolve().parents[1]
        assert path == str(root / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
