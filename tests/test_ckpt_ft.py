"""Checkpointing (atomicity, corruption, elastic restore) + FT runtime."""
import json
import os
import shutil
import time
from pathlib import Path

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import ckpt
from repro.runtime import (Heartbeat, PreemptionGuard, StragglerMonitor,
                           retry)


@pytest.fixture
def tree():
    return {"w": jnp.arange(24.0).reshape(4, 6),
            "opt": {"m": jnp.ones((3,)), "step": jnp.asarray(7)}}


def test_roundtrip(tmp_path, tree):
    ckpt.save(tmp_path, 3, tree)
    got, man = ckpt.restore(tmp_path, 3, jax.eval_shape(lambda: tree))
    assert man["step"] == 3
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(tree)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b))


def test_atomicity_no_partial_visible(tmp_path, tree):
    """A crashed writer leaves only .tmp dirs; steps() never sees them."""
    ckpt.save(tmp_path, 1, tree)
    fake = tmp_path / "step_00000002.tmp-abc"
    fake.mkdir()
    (fake / "MANIFEST.json").write_text("{}")
    assert ckpt.steps(tmp_path) == [1]
    ckpt.gc(tmp_path)
    assert not fake.exists()


def test_corruption_detected_and_skipped(tmp_path, tree):
    ckpt.save(tmp_path, 1, tree)
    ckpt.save(tmp_path, 2, tree)
    shard = next((tmp_path / "step_00000002").glob("shard_*.npz"))
    shard.write_bytes(b"corrupt")
    assert not ckpt.validate(tmp_path / "step_00000002")
    assert ckpt.latest_valid(tmp_path) == 1        # falls back to step 1


def test_restore_hash_check_raises(tmp_path, tree):
    ckpt.save(tmp_path, 1, tree)
    shard = next((tmp_path / "step_00000001").glob("shard_*.npz"))
    data = dict(np.load(shard))
    for k in data:
        data[k] = data[k] + 1
    np.savez(shard, **data)
    with pytest.raises(IOError):
        ckpt.restore(tmp_path, 1, jax.eval_shape(lambda: tree))


def test_save_retries_transient_rename_failure(tmp_path, tree,
                                               monkeypatch):
    """A flaky filesystem failing the atomic publish twice does not
    abort the save — ``ft.retry`` re-drives it and the checkpoint
    restores bitwise."""
    from repro.ckpt import store
    real_replace, fails = os.replace, []

    def flaky_replace(src, dst):
        if len(fails) < 2:
            fails.append(1)
            raise OSError("transient rename failure")
        return real_replace(src, dst)

    monkeypatch.setattr(store.os, "replace", flaky_replace)
    ckpt.save(tmp_path, 9, tree)
    assert len(fails) == 2
    assert ckpt.latest_valid(tmp_path) == 9
    got, _ = ckpt.restore(tmp_path, 9, jax.eval_shape(lambda: tree))
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(tree)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # a persistent failure still raises and leaves no partial ckpt
    monkeypatch.setattr(store.os, "replace",
                        lambda s, d: (_ for _ in ()).throw(
                            OSError("permanent")))
    with pytest.raises(OSError, match="permanent"):
        ckpt.save(tmp_path, 10, tree, retries=1)
    assert ckpt.steps(tmp_path) == [9]
    assert not list(tmp_path.glob("*.tmp-*"))


def test_gc_keeps_newest(tmp_path, tree):
    for s in (1, 2, 3, 4, 5):
        ckpt.save(tmp_path, s, tree)
    ckpt.gc(tmp_path, keep=2)
    assert ckpt.steps(tmp_path) == [4, 5]


def test_elastic_restore_onto_sharding(tmp_path, tree):
    """Restore with explicit shardings (device_put path)."""
    ckpt.save(tmp_path, 1, tree)
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch import mesh as mesh_lib
    mesh = mesh_lib.make_mesh((1,), ("data",))
    sh = jax.tree.map(lambda _: NamedSharding(mesh, P()), tree)
    got, _ = ckpt.restore(tmp_path, 1, jax.eval_shape(lambda: tree),
                          shardings=sh)
    np.testing.assert_allclose(np.asarray(got["w"]), np.asarray(tree["w"]))


# --- fault-tolerance runtime ------------------------------------------------

def test_retry_eventually_succeeds():
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise OSError("transient")
        return "ok"

    assert retry(flaky, retries=5, base_delay=0.001) == "ok"
    assert len(calls) == 3


def test_retry_gives_up():
    def broken():
        raise OSError("permanent")

    with pytest.raises(OSError):
        retry(broken, retries=2, base_delay=0.001)


def test_preemption_guard_simulated():
    with PreemptionGuard() as g:
        assert not g.should_save
        g.simulate()
        assert g.should_save


def test_straggler_detection():
    sm = StragglerMonitor(factor=2.0)
    for _ in range(10):
        sm.record(0, 1.0)
        sm.record(1, 0.9)
        sm.record(2, 4.0)
    assert sm.stragglers() == [2]


def test_heartbeat_dead_host(tmp_path):
    hb = Heartbeat(dir=tmp_path, host=0, interval=0.01)
    hb.ping(step=5)
    assert hb.dead_hosts([0], timeout=60.0) == []
    assert hb.dead_hosts([0, 1], timeout=60.0) == [1]   # host 1 never pinged
    # stale heartbeat
    p = tmp_path / "heartbeat_0.json"
    p.write_text(json.dumps({"t": time.time() - 999, "step": 5}))
    assert hb.dead_hosts([0], timeout=30.0) == [0]


def test_train_restart_replays_identical_batches():
    """Deterministic keyed data: host replay after restart is identical."""
    from repro.data import synthetic
    cfg = synthetic.CorpusConfig(vocab_size=128, seed=9)
    a = synthetic.DataPipeline(cfg, 4, 16, split="train", host=3)
    b = synthetic.DataPipeline(cfg, 4, 16, split="train", host=3)
    for step in (0, 5, 17):
        np.testing.assert_array_equal(np.asarray(a.get(step)["tokens"]),
                                      np.asarray(b.get(step)["tokens"]))
    # different host/split/step -> different batches
    c = synthetic.DataPipeline(cfg, 4, 16, split="train", host=4)
    assert not np.array_equal(np.asarray(a.get(0)["tokens"]),
                              np.asarray(c.get(0)["tokens"]))


def test_train_launcher_resume(tmp_path):
    from repro.launch.train import train
    out1 = train("llama31-8b", tiny=True, n_steps=4, batch=2, seq=16,
                 ckpt_dir=str(tmp_path), ckpt_every=2, verbose=False)
    assert ckpt.latest_valid(tmp_path) == 4
    out2 = train("llama31-8b", tiny=True, n_steps=6, batch=2, seq=16,
                 ckpt_dir=str(tmp_path), ckpt_every=2, verbose=False)
    assert len(out2["losses"]) == 2                 # only steps 4..5 ran
