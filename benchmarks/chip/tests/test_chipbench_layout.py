"""BENCHMARK.json and the files the harness finds by name."""
import json
import re
import shutil
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import harness  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    return harness.benchmark()


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["benchmarks/chip"]
    assert bench["command"][1] == "benchmarks/chip/run.py"
    assert 1 <= bench["run_seconds"] <= 51
    assert len(json.dumps(bench)) < 64 * 1024


def test_names_units_directions(bench):
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in bench[group]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append((group, entry["name"]))
    for w in bench["workloads"]:
        assert NAME.match(w["traffic"]) and NAME.match(w["config"])
        assert w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for c in bench["configs"]:
        assert all(NAME.match(k) for k in c["reduced"])
    assert len(set(names)) == len(names)


def test_bounds_and_sources(bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in e2e.values():
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        assert m["workloads"]
        for c in m["workloads"]:
            assert c in cells
            moved = e2e[m["moves"]]
            assert "workloads" not in moved or c in moved["workloads"]
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"


def test_every_cell_finds_its_files(bench):
    for w in bench["workloads"]:
        cfg = harness.config_doc(w["config"])
        mix = harness.mix_doc(w["traffic"])
        assert harness.runner(mix).run
        assert harness.reference(cfg)
        for m in harness.cell_metrics(bench, w["name"], trace=False) + \
                harness.cell_metrics(bench, w["name"], trace=True):
            assert callable(harness.metric_reader(m["name"]).read)
        e2e = {m["name"] for m in harness.cell_metrics(bench, w["name"],
                                                       trace=False)}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert harness.cell_metrics(bench, w["name"], trace=True)


def test_config_files_match_the_catalog_entry(bench):
    """Each file holds its config as run: every published width kept,
    only the keys in ``reduced`` changed from the program's own entry."""
    sys.path.insert(0, str(harness.ROOT / "src"))
    import repro.configs as configs
    for c in bench["configs"]:
        doc = json.loads((harness.ROOT / c["file"]).read_text())
        assert doc["name"] == c["name"] and doc["source"] == c["source"]
        assert doc["reduced"] == c["reduced"]
        pub = configs.get(doc["arch"])
        for k, v in doc["model"].items():
            if k not in c["reduced"]:
                assert getattr(pub, k) == v, (c["name"], k)
        for k in c["reduced"]:
            assert doc["published"][k] == getattr(pub, k)


def test_a_new_mix_file_is_picked_up_by_name(tmp_path):
    """A later change adds a mix by adding a file: no code edit."""
    here = tmp_path / "chip"
    shutil.copytree(HERE, here, ignore=shutil.ignore_patterns(
        "tests", "__pycache__"))
    mix = json.loads((here / "mixes" / "prune-0.6.json").read_text())
    mix["calib_sequences"] = 256
    (here / "mixes" / "prune-calib-heavy.json").write_text(json.dumps(mix))
    doc = harness.mix_doc("prune-calib-heavy", here)
    assert doc["calib_sequences"] == 256
    assert harness.runner(doc, here).__file__.endswith("runners/prune.py")
    (here / "metrics" / "new_metric.py").write_text(
        "def read(run):\n    return 7.0\n")
    assert harness.metric_reader("new_metric", here).read(None) == 7.0
    with pytest.raises(harness.Refusal):
        harness.mix_doc("no-such-mix", here)


def test_cell_metrics_by_workloads_key():
    bench = {"end_to_end": [
        {"name": "setup_s"}, {"name": "a", "workloads": ["x"]},
        {"name": "b", "workloads": ["y"]}],
        "per_layer": [{"name": "p", "moves": "a", "workloads": ["x"]},
                      {"name": "q", "moves": "b", "workloads": ["y"]}]}
    assert [m["name"] for m in harness.cell_metrics(
        bench, "x", trace=False)] == ["setup_s", "a"]
    assert [m["name"] for m in harness.cell_metrics(
        bench, "x", trace=True)] == ["p"]
    assert [m["name"] for m in harness.cell_metrics(
        bench, "y", trace=True)] == ["q"]


def test_percentile_nearest_rank():
    xs = list(range(1, 101))
    assert harness.percentile(xs, 95) == 95
    assert harness.percentile([3.0], 95) == 3.0
    assert harness.percentile([1, 2, 3, 4], 50) == 2
