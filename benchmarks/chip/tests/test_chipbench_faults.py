"""A run with the timed path broken underneath must come out incorrect.

Each test skips only the harness's look for a chip: it drives the rest
of a run (set-up, window, the reference comparison) at a CPU size, with
one fault planted in the program, and reads ``correct``.
"""
import sys
from pathlib import Path

import jax.numpy as jnp
import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

import harness  # noqa: E402

FIX = HERE / "tests" / "fixtures"


def drive(config: str, mix: str, seed: int = 2**31 + 11,
          **overrides) -> harness.Run:
    """One run; ``overrides`` set runner module constants."""
    cfg, mx = harness.config_doc(config, FIX), harness.mix_doc(mix, FIX)
    peaks = harness.load_json(HERE / "peaks.json")["TPU v5 lite"]
    run = harness.Run(cell={"name": "tiny", "config": config,
                            "traffic": mix, "chips": 1},
                      config=cfg, mix=mx, seed=seed, seconds=0.5,
                      trace=False, peaks=peaks)
    runner = harness.runner(mx)
    for k, v in overrides.items():
        setattr(runner, k, v)
    runner.run(run)
    return run


# -- pruning --------------------------------------------------------------

def test_sound_prune_run_is_correct():
    run = drive("tiny-gated", "prune-tiny")
    assert run.correct, run.checks
    assert run.facts["passes"] >= 1
    assert 0 < run.facts["loss_reduction_pct"] < 100


def _patch_refiner(monkeypatch, wrap):
    from repro.pruning import engine
    orig = engine.REFINERS["sparseswaps"]
    monkeypatch.setitem(engine.REFINERS, "sparseswaps",
                        lambda W, gram, pattern, ctx:
                        wrap(orig(W, gram, pattern, ctx), W, gram,
                             pattern, ctx))


def test_prune_answer_altered_where_produced(monkeypatch):
    """Each row's first kept and first pruned entry swap places after the
    refiner returns: the masks keep their density, the reported losses
    no longer belong to them."""
    def flip(res, *_):
        m = res.masks
        kept = jnp.argmax(m, axis=-1)[..., None]
        pruned = jnp.argmin(m, axis=-1)[..., None]
        m = jnp.put_along_axis(m, kept, 0.0, axis=-1, inplace=False)
        m = jnp.put_along_axis(m, pruned, 1.0, axis=-1, inplace=False)
        res.masks = m
        return res
    _patch_refiner(monkeypatch, flip)
    run = drive("tiny-plain", "prune-tiny")
    assert not run.correct
    assert not run.checks["loss_gap"]["ok"]


def test_prune_step_returns_its_state_unchanged(monkeypatch):
    """The search hands back the warmstart untouched."""
    from repro.pruning import engine

    def stay(res, W, gram, pattern, ctx):
        return engine.REFINERS["none"](W, gram, pattern, ctx)
    _patch_refiner(monkeypatch, stay)
    run = drive("tiny-plain", "prune-tiny")
    assert not run.correct
    assert not run.checks["least_gain"]["ok"]


def test_prune_half_the_batches_left_out(monkeypatch):
    """Calibration folds in only every other batch."""
    from repro.pruning import stats
    orig = stats.accumulate_stats
    monkeypatch.setattr(stats, "accumulate_stats",
                        lambda api, params, batches, **kw:
                        orig(api, params, list(batches)[::2], **kw))
    from repro.pruning import executor
    monkeypatch.setattr(executor.stats_lib, "accumulate_stats",
                        stats.accumulate_stats)
    run = drive("tiny-plain", "prune-tiny")
    assert not run.correct
    assert not run.checks["gram_gap"]["ok"]


# -- serving -------------------------------------------------------------

@pytest.mark.parametrize("positions", [128, 3])
def test_sound_serve_run_is_correct(positions):
    """Also with fewer reference positions to a call than a request has
    served tokens: the check then scores each request in blocks."""
    run = drive("tiny-plain", "serve-tiny", POSITIONS=positions)
    assert run.correct, run.checks
    assert run.facts["tokens"] > 0 and run.facts["checked_tokens"] >= 64


def _patch_decode(monkeypatch, wrap):
    from repro.serve import engine
    orig = engine.ServeEngine.decode_chunk

    def chunk(self, tok, cache, active, samp, *, n_steps, bucket):
        toks, new = orig(self, tok, cache, active, samp, n_steps=n_steps,
                         bucket=bucket)
        return wrap(toks, new, cache)
    monkeypatch.setattr(engine.ServeEngine, "decode_chunk", chunk)


@pytest.mark.parametrize("fault", ["token_altered", "half_batch"])
def test_serve_fault_is_caught(monkeypatch, fault):
    def wrap(toks, new, cache):
        if fault == "token_altered":       # each row's last token of a chunk
            toks = toks.at[-1].add(1) % 256
        else:                              # rows past half keep a stale id
            half = max(toks.shape[1] // 2, 1)
            toks = toks.at[:, half:].set(toks[:, :1])
        return toks, new
    _patch_decode(monkeypatch, wrap)
    run = drive("tiny-plain", "serve-tiny")
    assert not run.correct
    assert not run.checks["mean_gap"]["ok"]
