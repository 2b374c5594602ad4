"""Serving robustness: deadlines, eviction, fault injection, degradation.

The acceptance surface of the fault-tolerant serving layer: evicted
decode rows resume bitwise (greedy AND seeded — the positional PRNG
guarantee survives a host round-trip), injected pool exhaustion leaks
nothing and perturbs no completed stream, deadlines/TTLs free pages,
``cancel`` compacts the decode batch without touching neighbours, a
simulated SIGTERM drains clean, failed page ships roll back and retry,
shed-mode admission returns typed ``Rejected``, and the ``run_chaos``
harness's leak/bitwise gates hold end to end. Plus the bench sweep's
error-row tolerance and the checker's handling of it.
"""
import importlib.util
import json
import re
from pathlib import Path

import numpy as np
import jax.numpy as jnp
import pytest

import jax
import repro.configs as configs
import repro.models as models
from repro.serve import (GREEDY, ContinuousScheduler, FaultInjector,
                         FaultPlan, PagedKVCache, Rejected, SamplingParams,
                         ServeEngine, ShipFault)
from repro.serve import loadgen


@pytest.fixture(scope="module")
def tiny():
    cfg = configs.get_tiny("llama31-8b")
    api = models.build(cfg)
    params = api.init(jax.random.key(0))
    return cfg, api, params, ServeEngine(api, params, fmt="dense")


def _prompt(n, seed=0, vocab=256):
    return np.random.default_rng(seed).integers(
        0, vocab, size=n).astype(np.int32)


def _sched(engine, **kw):
    kw.setdefault("max_batch", 4)
    kw.setdefault("capacity", 64)
    kw.setdefault("page_size", 8)
    kw.setdefault("decode_chunk", 4)
    return ContinuousScheduler(engine, **kw)


def _solo(engine, prompt, n_new, samp, **kw):
    kw.setdefault("bucket_batch", False)
    sch = _sched(engine, **kw)
    rid = sch.submit(prompt, n_new, sampling=samp)
    return sch.run_until_idle()[rid].tokens


SEEDED = SamplingParams(temperature=0.9, top_p=0.95, seed=11)


# -- spill / restore (kvcache) ------------------------------------------------


def test_spill_restore_roundtrip_bitwise(tiny):
    cfg = tiny[0]
    pool = PagedKVCache(cfg, n_pages=8, page_size=4)
    L, kvh, dh = cfg.n_layers, cfg.n_kv_heads, cfg.head_dim
    rng = np.random.default_rng(5)
    k_row = rng.normal(size=(L, 16, kvh, dh)).astype(np.float32)
    v_row = rng.normal(size=(L, 16, kvh, dh)).astype(np.float32)
    pool.alloc("s", 11)
    pool.store("s", jnp.asarray(k_row), jnp.asarray(v_row), 11)
    sp = pool.spill("s", capacity=16)
    assert pool.used_bytes == 0 and "s" not in pool.sessions()
    assert sp.length == 11 and sp.nbytes > 0
    assert pool.spilled_bytes_out == 3 * pool.page_bytes
    pool.restore_spill(sp)
    k, v, _, length = pool.load("s", 16)
    assert length == 11
    np.testing.assert_array_equal(np.asarray(k)[:, :11], k_row[:, :11])
    np.testing.assert_array_equal(np.asarray(v)[:, :11], v_row[:, :11])
    # restore into a full pool raises BEFORE mutating anything
    sp2 = pool.spill("s", capacity=16)
    pool.alloc("hog", 8 * 4)
    with pytest.raises(MemoryError):
        pool.restore_spill(sp2)
    assert "s" not in pool.sessions()
    pool.free("hog")
    assert pool.used_bytes == 0


# -- eviction -> resume bitwise -----------------------------------------------


@pytest.mark.parametrize("samp", [GREEDY, SEEDED],
                         ids=["greedy", "seeded"])
def test_evict_resume_mid_decode_bitwise(tiny, samp):
    """A decode row forced out to host mid-request resumes and finishes
    with the exact tokens the uninterrupted run produces."""
    _, _, _, engine = tiny
    reqs = [(_prompt(6, seed=1), 16), (_prompt(9, seed=2), 14)]
    want = [_solo(engine, p, n, samp) for p, n in reqs]
    sch = _sched(engine, bucket_batch=False)
    rids = [sch.submit(p, n, sampling=samp) for p, n in reqs]
    for _ in range(2):                       # get both rows decoding
        sch.step()
    assert len(sch.slots) == 2
    assert sch._evict_row_lru()
    assert sch.counters["evicted"] == 1 and len(sch.slots) == 1
    done = sch.run_until_idle()
    assert sch.counters["evict_resumed"] == 1
    assert sch.pool.used_bytes == 0
    for rid, w in zip(rids, want):
        np.testing.assert_array_equal(done[rid].tokens, w,
                                      err_msg=f"request {rid}")


def test_idle_kept_session_spill_and_resume_bitwise(tiny):
    """An idle kept session evicted to host resumes exactly where it
    left off — the spill round-trip is invisible to the stream."""
    _, _, _, engine = tiny
    prompt = _prompt(10, seed=7)
    want = _solo(engine, prompt, 10, SEEDED)
    sch = _sched(engine, bucket_batch=False)
    r1 = sch.submit(prompt, 4, sampling=SEEDED, session="s0", keep=True)
    first = sch.run_until_idle()[r1]
    assert sch._evict_idle_lru()             # forced idle spill
    assert "s0" in sch._spilled and sch.pool.used_bytes == 0
    r2 = sch.submit(None, 6, sampling=SEEDED, session="s0")
    second = sch.run_until_idle()[r2]
    np.testing.assert_array_equal(
        np.concatenate([first.tokens, second.tokens]), want)
    assert sch.pool.used_bytes == 0


def test_page_pressure_evicts_instead_of_stalling(tiny):
    """A pool too small for the whole offered load plus a kept hog
    session completes everything by spilling the idle hog."""
    _, _, _, engine = tiny
    sch = _sched(engine, n_pages=8)          # 64 tokens total
    h = sch.submit(_prompt(40, seed=9), 4, session="hog", keep=True)
    sch.run_until_idle()
    assert sch.pool.used_bytes > 0           # hog keeps 6 of 8 pages
    rids = [sch.submit(_prompt(16, seed=s), 8) for s in range(2)]
    done = sch.run_until_idle()
    assert set(rids) <= set(done)
    assert sch.counters["evicted"] >= 1
    assert "hog" in sch._spilled             # resumable, just on host
    sch.release("hog")
    assert sch.pool.used_bytes == 0


# -- injected faults ----------------------------------------------------------


def test_injected_exhaustion_no_leak_bitwise(tiny):
    """Armed pool exhaustion at alloc time is absorbed by the retry and
    never changes the tokens or leaks a page."""
    _, _, _, engine = tiny
    reqs = [(_prompt(5 + s, seed=s), 6) for s in range(4)]
    want = [_solo(engine, p, n, GREEDY) for p, n in reqs]
    plan = FaultPlan(exhaust_pool_at=(1, 2, 3))
    sch = _sched(engine, bucket_batch=False, faults=plan)
    rids = [sch.submit(p, n) for p, n in reqs]
    done = sch.run_until_idle()
    assert sch._injector.fired("exhaust") >= 1
    assert sch.counters["alloc_retries"] >= 1
    assert sch.pool.used_bytes == 0
    engine.dispatch_hook = None
    for rid, w in zip(rids, want):
        np.testing.assert_array_equal(done[rid].tokens, w)


def test_ship_failure_rolls_back_and_retries(tiny):
    """Disaggregated mode: a ShipFault before the transfer mutates
    nothing; the retry re-drives it and the stream is unperturbed."""
    _, _, _, engine = tiny
    prompt, n_new = _prompt(9, seed=3), 7
    kw = dict(disaggregate=True, bucket_batch=False)
    want = _solo(engine, prompt, n_new, SEEDED, **kw)
    sch = _sched(engine, faults=FaultPlan(fail_ship=(1,)), **kw)
    rid = sch.submit(prompt, n_new, sampling=SEEDED)
    done = sch.run_until_idle()
    assert sch.counters["ship_retries"] == 1
    assert sch._injector.fired("ship") == 1
    np.testing.assert_array_equal(done[rid].tokens, want)
    assert sch.pool.used_bytes == 0
    assert sch.prefill_pool.used_bytes == 0
    engine.dispatch_hook = None


def test_persistent_ship_failure_waits_then_recovers(tiny):
    """Every retry of the first ship window fails -> the session parks
    (ship_failures counted), next step's fresh ordinals succeed."""
    _, _, _, engine = tiny
    plan = FaultPlan(fail_ship=(1, 2, 3, 4))   # ship_retries=3 -> 4 attempts
    kw = dict(disaggregate=True, bucket_batch=False)
    want = _solo(engine, _prompt(8, seed=4), 5, GREEDY, **kw)
    sch = _sched(engine, faults=plan, **kw)
    rid = sch.submit(_prompt(8, seed=4), 5)
    done = sch.run_until_idle()
    assert sch.counters["ship_failures"] == 1
    assert sch.counters["ship_retries"] == 3
    np.testing.assert_array_equal(done[rid].tokens, want)
    assert sch.pool.used_bytes == 0
    assert sch.prefill_pool.used_bytes == 0
    engine.dispatch_hook = None


def test_slow_step_injection_lands_in_lane_timing(tiny):
    _, _, _, engine = tiny
    naps = []
    inj = FaultInjector(FaultPlan(slow_steps=((2, 0.5),)),
                        sleep=naps.append)
    inj.begin_step(1)
    inj.on_dispatch("decode")
    assert naps == []
    inj.begin_step(2)
    inj.on_dispatch("decode")
    inj.on_dispatch("decode")                # fires once per step
    assert naps == [0.5] and inj.fired("slow") == 1


def test_faultplan_chaos_deterministic():
    assert FaultPlan.chaos(7) == FaultPlan.chaos(7)
    assert FaultPlan.chaos(7) != FaultPlan.chaos(8)
    p = FaultPlan.chaos(7)
    assert "exhaust@" in p.describe() and "sigterm@" in p.describe()
    assert FaultPlan().describe() == "no-faults"


# -- deadlines / TTLs / cancel ------------------------------------------------


def test_deadline_and_ttl_expiry_free_pages(tiny):
    """Queue TTL expires a waiting request; a total deadline expires an
    ACTIVE decode row; both free every page and surface in events."""
    _, _, _, engine = tiny
    t = [0.0]
    sch = _sched(engine, bucket_batch=False, clock=lambda: t[0],
                 max_batch=2)
    # deadline victim enters the decode batch, ttl victim waits behind
    # a full batch (max_batch=2)
    ra = sch.submit(_prompt(6, seed=1), 30, deadline_s=5.0)
    rb = sch.submit(_prompt(6, seed=2), 30)
    rc = sch.submit(_prompt(6, seed=3), 30, queue_ttl_s=2.0)
    for _ in range(2):
        sch.step()
    assert len(sch.slots) == 2 and len(sch.queue) == 1
    t[0] = 3.0                               # past rc's TTL, not ra's deadline
    ev = sch.step()
    assert ev.expired == [rc] and len(sch.queue) == 0
    t[0] = 6.0                               # past ra's deadline
    ev = sch.step()
    assert ra in ev.expired
    assert sch.counters["expired"] == 2
    done = sch.run_until_idle()
    assert rb in done and ra not in done and rc not in done
    assert sch.pool.used_bytes == 0


def test_cancel_mid_decode_compacts_batch(tiny):
    """Cancelling an active row swap-removes it; the surviving rows'
    streams match their solo references bitwise."""
    _, _, _, engine = tiny
    reqs = [(_prompt(6, seed=s), 16) for s in range(3)]
    want = [_solo(engine, p, n, GREEDY) for p, n in reqs]
    sch = _sched(engine, bucket_batch=False)
    rids = [sch.submit(p, n) for p, n in reqs]
    for _ in range(3):
        sch.step()
    assert len(sch.slots) == 3
    assert sch.cancel(rids[1])
    assert len(sch.slots) == 2
    assert not sch.cancel(rids[1])           # already gone
    assert not sch.cancel(10_000)            # never existed
    done = sch.run_until_idle()
    assert rids[1] not in done
    assert sch.counters["cancelled"] == 1
    for i in (0, 2):
        np.testing.assert_array_equal(done[rids[i]].tokens, want[i])
    assert sch.pool.used_bytes == 0


def test_cancel_queued_and_resume_requests(tiny):
    _, _, _, engine = tiny
    sch = _sched(engine)
    r1 = sch.submit(_prompt(6, seed=1), 4, session="keep", keep=True)
    sch.run_until_idle()
    kept_bytes = sch.pool.used_bytes
    assert kept_bytes > 0
    # cancel a waiting request before any step touches it
    r2 = sch.submit(_prompt(6, seed=2), 4)
    assert sch.cancel(r2) and len(sch.queue) == 0
    # cancelling a queued RESUME leaves the kept session intact
    r3 = sch.submit(None, 4, session="keep")
    assert sch.cancel(r3)
    assert sch.pool.used_bytes == kept_bytes
    sch.release("keep")
    assert sch.pool.used_bytes == 0


# -- admission control / shed / drain -----------------------------------------


def test_shed_mode_returns_typed_rejected(tiny):
    _, _, _, engine = tiny
    sch = _sched(engine, admission="shed", max_queue=1)
    x = sch.submit(_prompt(4, seed=1), 2)
    y = sch.submit(_prompt(4, seed=2), 2)
    assert isinstance(x, int)
    assert isinstance(y, Rejected) and y.reason == "queue_full"
    assert sch.counters["shed"] == 1
    done = sch.run_until_idle()
    assert sorted(done) == [x]
    # the default mode raises on the same overload
    strict = _sched(engine, max_queue=1)
    strict.submit(_prompt(4), 2)
    with pytest.raises(RuntimeError, match="admission refused"):
        strict.submit(_prompt(4), 2)
    strict.run_until_idle()
    with pytest.raises(ValueError):
        _sched(engine, admission="maybe")


def test_sigterm_drains_inflight_and_shuts_down_clean(tiny):
    """A simulated SIGTERM mid-traffic: in-flight requests finish,
    queued ones stay queued, shutdown leaves the pool at zero pages."""
    _, _, _, engine = tiny
    sch = _sched(engine, bucket_batch=False, max_batch=2,
                 faults=FaultPlan(sigterm_at=2))
    rids = [sch.submit(_prompt(6, seed=s), 8) for s in range(4)]
    done = sch.run_until_idle()
    assert sch.draining and sch.drained
    assert sch._injector.fired("sigterm") == 1
    assert 0 < len(done) < len(rids)         # in-flight finished, rest queued
    assert len(sch.queue) == len(rids) - len(done)
    with pytest.raises(RuntimeError, match="draining"):
        sch.submit(_prompt(4), 2)
    spills = sch.shutdown()
    assert spills == {}                      # nothing was kept
    assert sch.pool.used_bytes == 0
    engine.dispatch_hook = None
    # shed mode sheds instead of raising while draining
    shed = _sched(engine, admission="shed", faults=FaultPlan(sigterm_at=1))
    shed.step()
    r = shed.submit(_prompt(4), 2)
    assert isinstance(r, Rejected) and r.reason == "draining"
    engine.dispatch_hook = None


def test_shutdown_refuses_with_inflight_and_spills_kept(tiny):
    _, _, _, engine = tiny
    sch = _sched(engine)
    sch.submit(_prompt(6, seed=1), 6, session="k", keep=True)
    sch.step()
    with pytest.raises(RuntimeError, match="in flight"):
        sch.shutdown()
    sch.run_until_idle()
    assert sch.pool.used_bytes > 0           # the kept session
    spills = sch.shutdown()
    assert set(spills) == {"k"} and sch.pool.used_bytes == 0


# -- the chaos harness --------------------------------------------------------


def _chaos_kw():
    return dict(max_batch=4, capacity=64, page_size=8, decode_chunk=4)


def test_run_chaos_verdict_ok(tiny):
    _, _, _, engine = tiny
    load = loadgen.LoadConfig(arrival_rate=40.0, duration_s=0.3,
                              prompt_len=(4, 8), output_len=(2, 6))
    workload = loadgen.make_workload(load)
    assert len(workload) >= 6
    plan = FaultPlan(exhaust_pool_at=(2, 4), fail_ship=())
    res = loadgen.run_chaos(engine, workload, plan, **_chaos_kw())
    assert res["ok"], res
    assert res["leaked_bytes"] == 0 and res["leaked_bytes_clean"] == 0
    assert res["stream_mismatches"] == 0
    assert res["completed_faulted"] == len(workload)
    assert any(k == "exhaust" for _, k in res["faults_fired"])
    assert engine.dispatch_hook is None      # harness detaches its hooks


def test_run_chaos_with_sigterm_partial_completion(tiny):
    _, _, _, engine = tiny
    load = loadgen.LoadConfig(arrival_rate=40.0, duration_s=0.4,
                              prompt_len=(4, 8), output_len=(4, 8),
                              seed=3)
    workload = loadgen.make_workload(load)
    plan = FaultPlan(sigterm_at=3)
    res = loadgen.run_chaos(engine, workload, plan, **_chaos_kw())
    assert res["leaked_bytes"] == 0
    assert res["stream_mismatches"] == 0 and res["ok"]
    assert res["completed_faulted"] < res["completed_clean"]
    assert any(k == "sigterm" for _, k in res["faults_fired"])


# -- bench sweep: error rows + counters ---------------------------------------


def _check_mod():
    spec = importlib.util.spec_from_file_location(
        "check_serve_bench",
        Path(__file__).resolve().parents[1] / "benchmarks"
        / "check_serve_bench.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_load_rows_carry_robustness_counters(tiny):
    _, api, params, _ = tiny
    load = loadgen.LoadConfig(duration_s=0.2, prompt_len=(4, 8),
                              output_len=(2, 4))
    rows = loadgen.bench_load_rows(
        api, params, None, formats=("dense",), rates=(32.0,), load=load,
        max_batch=4, capacity=32, page_size=8, decode_chunk=2)
    for r in rows:
        for k in ("shed", "expired", "cancelled", "evicted"):
            assert r[k] == 0                 # healthy run: all quiet
    mod = _check_mod()
    doc = {"arch": "tiny", "batch": 4, "prompt_len": 8, "gen": 4,
           "devices": 1, "rows": rows}
    assert mod.check(doc, max_nm24_prefill_ratio=50.0) == []
    bad = dict(rows[0])
    bad["expired"] = -1
    errs = mod.check({**doc, "rows": [bad]}, max_nm24_prefill_ratio=50.0)
    assert any("expired negative" in e for e in errs)


def test_bench_sweep_survives_failing_cell(tiny, monkeypatch):
    """One mode blowing up becomes an error row, not an aborted sweep;
    the checker tolerates-but-flags it and keeps it out of the gates."""
    _, api, params, _ = tiny

    def boom(*a, **kw):
        raise RuntimeError("injected bench failure")

    monkeypatch.setattr(loadgen, "run_fixed", boom)
    load = loadgen.LoadConfig(duration_s=0.2, prompt_len=(4, 8),
                              output_len=(2, 4))
    rows = loadgen.bench_load_rows(
        api, params, None, formats=("dense",), rates=(32.0,), load=load,
        max_batch=4, capacity=32, page_size=8, decode_chunk=2)
    by_mode = {r["mode"]: r for r in rows}
    assert "error" not in by_mode["continuous"]
    err = by_mode["fixed"]
    assert err["error"] == "RuntimeError: injected bench failure"
    assert err["phase"] == "load" and err["arrival_rate"] == 32.0
    mod = _check_mod()
    doc = {"arch": "tiny", "batch": 4, "prompt_len": 8, "gen": 4,
           "devices": 1, "rows": rows}
    warnings = []
    assert mod.check(doc, max_nm24_prefill_ratio=50.0,
                     warnings=warnings) == []
    assert len(warnings) == 1 and "injected bench failure" in warnings[0]
    # error rows never satisfy the -wins gates
    errs = mod.check(doc, max_nm24_prefill_ratio=50.0,
                     require_continuous_wins=True)
    assert any("need both" in e for e in errs)


def test_serve_load_bench_exits_nonzero_on_error_row(tmp_path, monkeypatch,
                                                    capsys):
    """The launcher writes and prints the sweep, error rows included,
    then exits non-zero: a cell that failed must not pass as a finished
    run."""
    from repro.launch.serve import serve

    def boom(*a, **kw):
        raise RuntimeError("injected bench failure")

    monkeypatch.setattr(loadgen, "run_fixed", boom)
    out = tmp_path / "bench.json"
    with pytest.raises(SystemExit, match="injected bench failure"):
        serve("llama31-8b", batch=2, prompt_len=8, gen=2, load_bench=True,
              load_rates=(32.0,), load_duration=0.2,
              load_prompt_len=(4, 8), load_output_len=(2, 4),
              bench_out=out, verbose=True)
    rows = json.loads(out.read_text())["rows"]
    assert [r["mode"] for r in rows if "error" in r] == ["fixed"]
    assert re.search(r"fixed +rate .* ERROR .*injected bench failure",
                     capsys.readouterr().out)


def test_run_continuous_deadline_expires_on_virtual_clock(tiny):
    """A deadline far tighter than the simulated service time expires
    requests on the virtual timeline and shows up in the row."""
    _, _, _, engine = tiny
    load = loadgen.LoadConfig(arrival_rate=64.0, duration_s=0.25,
                              prompt_len=(4, 8), output_len=(4, 8))
    workload = loadgen.make_workload(load)
    row = loadgen.run_continuous(engine, workload, warmup=False,
                                 deadline_s=1e-6, max_batch=4,
                                 capacity=32, page_size=8, decode_chunk=2)
    assert row["expired"] > 0
    assert row["completed"] + row["expired"] >= len(workload)
