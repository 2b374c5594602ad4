"""Program spans (``repro.runtime.trace``): off costs nothing and records
nothing, on nests and counts, and the instrumented scheduler and executor
serve and prune exactly the same with spans on as off."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as configs
import repro.models as models
from repro import pruning
from repro.core import masks as masks_lib
from repro.core import sparseswaps
from repro.pruning import stats as stats_lib
from repro.runtime import trace
from repro.serve import ContinuousScheduler, ServeEngine


@pytest.fixture(autouse=True)
def _fresh():
    trace.disable()
    trace.clear()
    yield
    trace.disable()
    trace.clear()


class _Annotations:
    """Stands in for ``jax.profiler.TraceAnnotation``; keeps the names."""

    def __init__(self):
        self.names = []

    def __call__(self, name):
        self.names.append(name)
        return self

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def _by_name(recs):
    out = {}
    for r in recs:
        out.setdefault(r.name, []).append(r)
    return out


def test_off_records_nothing_and_builds_no_annotation(monkeypatch):
    ann = _Annotations()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", ann)
    assert not trace.enabled()
    assert trace.span("a") is trace.span("b", rid=3)
    with trace.span("a") as s:
        s.set(x=1)
    with trace.timed("lane") as t:
        pass
    assert t.seconds >= 0.0
    x = jnp.arange(3)
    assert trace.wait(x, "sync") is x
    np.testing.assert_array_equal(trace.wait(x, "fetch", np.asarray),
                                  np.arange(3))
    assert trace.records() == []
    assert ann.names == []


def test_on_nests_parents_and_keeps_attrs(monkeypatch):
    ann = _Annotations()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", ann)
    trace.enable()
    with trace.span("outer", rid=7) as o:
        with trace.span("inner"):
            pass
        with trace.timed("lane", k=2):
            pass
        o.set(n=5)
    recs = _by_name(trace.records())
    outer, inner, lane = recs["outer"][0], recs["inner"][0], recs["lane"][0]
    assert outer.parent is None
    assert inner.parent == lane.parent == outer.id
    assert outer.attrs == {"rid": 7, "n": 5} and lane.attrs == {"k": 2}
    assert outer.t0 <= inner.t0 <= inner.t1 <= lane.t0 <= outer.t1
    assert [r.name for r in trace.records()] == ["inner", "lane", "outer"]
    assert ann.names == ["repro:outer", "repro:inner", "repro:lane"]
    trace.clear()
    assert trace.records() == []


def test_wait_counts_each_sync():
    trace.enable()
    x = jnp.ones(4)
    assert trace.wait(x, "a") is x
    got = trace.wait(x, "a", np.asarray)
    assert isinstance(got, np.ndarray)
    with trace.span("parent"):
        trace.wait(x, "b", jax.device_get)
    recs = _by_name(trace.records())
    assert len(recs["a.wait"]) == 2
    assert recs["b.wait"][0].parent == recs["parent"][0].id


def test_a_profiler_trace_turns_recording_on(tmp_path):
    with trace.span("before"):
        pass
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert trace.enabled()
        with trace.span("during"):
            pass
    finally:
        jax.profiler.stop_trace()
    assert not trace.enabled()
    assert [r.name for r in trace.records()] == ["during"]


# -- serving ------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_engine():
    cfg = configs.get_tiny("llama31-8b")
    api = models.build(cfg)
    params = api.init(jax.random.key(0))
    return cfg, ServeEngine(api, params, fmt="dense")


def _serve(engine, steps_out=None):
    """Three requests through one scheduler; {rid: tokens}."""
    sch = ContinuousScheduler(engine, max_batch=4, capacity=64, page_size=8,
                              decode_chunk=4)
    rng = np.random.default_rng(0)
    for n, new in ((5, 6), (11, 3), (3, 9)):
        sch.submit(rng.integers(0, 256, n).astype(np.int32), new)
    done = {}
    while not sch.idle:
        trace.clear()
        ev = sch.step()
        if steps_out is not None:
            steps_out.append((ev, trace.records()))
        done.update({c.rid: c.tokens for c in ev.completed})
    return done


def test_scheduler_step_spans_and_lanes(tiny_engine):
    _, engine = tiny_engine
    off = _serve(engine)
    assert trace.records() == []
    trace.enable()
    steps = []
    on = _serve(engine, steps)
    assert off.keys() == on.keys()
    for rid in off:
        np.testing.assert_array_equal(off[rid], on[rid])

    decoded = 0
    for ev, recs in steps:
        names = _by_name(recs)
        step = names["sched.step"][0]
        kids = {r.name for r in recs if r.parent == step.id}
        assert {"sched.expire", "sched.prefill", "sched.join"} <= kids
        pre = names["sched.prefill"][0]
        join = names["sched.join"][0]
        assert ev.prefill_lane_s == pre.t1 - pre.t0
        if "sched.decode" in names:
            dec = names["sched.decode"][0]
            assert ev.decode_lane_s == join.t1 - join.t0 + (dec.t1 - dec.t0)
            under = {r.name for r in recs if r.parent == dec.id}
            assert {"sched.decode.upload", "engine.decode",
                    "sched.decode.fetch.wait"} <= under
            eng = names["engine.decode"][0]
            assert names["engine.decode.wait"][0].parent == eng.id
            a = step.attrs
            assert a["n_active"] <= a["bucket"]
            assert a["wasted"] == ev.wasted_decode_tokens
            decoded += a["n_active"] * a["n_steps"] - a["wasted"]
            assert len(names.get("sched.leave", [])) == sum(
                1 for c in ev.completed if c.n_new > 1)
        else:
            assert ev.decode_lane_s == join.t1 - join.t0
        if ev.prefilled:
            assert names["engine.prefill.wait"][0].parent == \
                names["engine.prefill"][0].id
            assert all(r.parent == pre.id
                       for r in names["sched.prefill.first.wait"])
        assert "n_queued" in step.attrs
    # every decoded token the step attrs count was delivered
    assert decoded == sum(len(t) - 1 for t in on.values())


def test_engine_programs_are_named(tiny_engine):
    cfg, engine = tiny_engine
    shapes = {}

    def keep(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype) \
            if hasattr(a, "shape") else a

    orig = ServeEngine._call

    def spy(self, phase, key, build, *args):
        shapes[key] = jax.tree.map(keep, args)
        return orig(self, phase, key, build, *args)

    ServeEngine._call = spy
    try:
        _serve(engine)
        sch = ContinuousScheduler(engine, max_batch=4, capacity=64,
                                  page_size=8, decode_chunk=4, prefill_chunk=8)
        sch.submit(np.arange(12, dtype=np.int32), 2)
        sch.run_until_idle()
    finally:
        ServeEngine._call = orig
    want = {"prefill_session": "jit_prefill_session",
            "prefill_chunk": "jit_prefill_chunk", "chunk": "jit_decode_chunk"}
    seen = set()
    for key, args in shapes.items():
        text = engine._fns[key].lower(engine.params, engine.masks,
                                      *args).as_text()
        assert f"module @{want[key[0]]} " in text, key
        seen.add(key[0])
    assert seen == set(want)

    prompt = {"tokens": jnp.zeros((1, 4), jnp.int32)}
    engine.generate(prompt, 3)
    cache = engine.api.init_cache(engine.params, 1, 8)
    text = engine._decode_scan(2, False).lower(
        engine.params, engine.masks, jnp.zeros((1,), jnp.int32), cache,
        None).as_text()
    assert "module @jit_decode_scan " in text


# -- pruning ------------------------------------------------------------------

class _CountPasses(pruning.PruneCallback):
    """Counts each group's search passes with the program's own hook."""

    def __init__(self):
        self.passes, self._cm = {}, None

    def on_group_start(self, planned, index, total):
        self._cm = sparseswaps.count_search_passes()
        self._cnt = self._cm.__enter__()

    def on_group_done(self, planned, report, *, restored):
        self._cm.__exit__(None, None, None)
        self.passes[planned.name] = self._cnt.passes


@pytest.fixture(scope="module")
def tiny_prune():
    cfg = configs.get_tiny("llama31-8b")
    api = models.build(cfg)
    params = api.init(jax.random.key(0))
    batches = list(pruning.calibration_batches(cfg, n_samples=4, seq_len=24,
                                               batch_size=2))
    recipe = pruning.PruneRecipe.single(masks_lib.PerRow(0.6),
                                        method="sparseswaps",
                                        warmstart="wanda", t_max=4)
    plan = pruning.plan_pruning(api, params, recipe)
    return api, params, batches, plan


def test_executor_group_spans(tiny_prune):
    api, params, batches, plan = tiny_prune
    off = pruning.PruneExecutor(api, params, plan).run(batches)
    assert trace.records() == []
    trace.enable()
    cb = _CountPasses()
    on = pruning.PruneExecutor(api, params, plan, callback=cb).run(batches)
    for a, b in zip(jax.tree.leaves(off.masks), jax.tree.leaves(on.masks)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    names = _by_name(trace.records())
    run = names["prune.run"][0]
    assert on.wall_time_s == run.t1 - run.t0
    assert names["prune.masks.wait"][0].parent == run.id
    for n in ("prune.calibrate", "prune.sites", "prune.assemble"):
        assert names[n][0].parent == run.id
    calib = names["prune.calibrate"][0]
    assert len(names["prune.calib.batch"]) == len(batches)
    assert all(r.parent == calib.id for r in names["prune.calib.batch"])

    groups = names["prune.group"]
    active = [pg for pg in plan.groups if not pg.skip]
    assert [g.attrs["name"] for g in groups] == [pg.name for pg in active]
    swaps = {s.name: int(np.sum(np.asarray(s.swaps))) for s in on.sites}
    for g in groups:
        a = g.attrs
        kids = {r.name for r in trace.records() if r.parent == g.id}
        assert {"prune.refine", "prune.check.wait"} <= kids
        assert a["passes"] == cb.passes[a["name"]] > 0
        assert a["swaps"] == swaps[a["name"]]
        assert a["k"] == 8
        assert a["rows_scored"] == a["instances"] * a["rows"] * a["passes"]
        assert 0 <= a["swaps"] <= a["k"] * a["rows_scored"]


def test_calib_step_is_named(tiny_prune):
    api, params, batches, plan = tiny_prune
    spec = plan.calib_spec(minimal=False)
    state = stats_lib.init_state(api, spec, params, batches[0])
    text = stats_lib.make_carry_step(api, spec).lower(
        params, state, batches[0]).as_text()
    assert "module @jit_calib_step " in text
