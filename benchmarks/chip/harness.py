"""The benchmark's machinery: cells, files found by name, the run record.

A cell is one entry of ``workloads`` in ``BENCHMARK.json``. Everything
else is a file found by its name, so a later change adds a cell by adding
files and entries only:

  configs/<config>.json    sizes as run, source, cuts, plain reference
  mixes/<traffic>.json     job or traffic parameters; ``runner`` names
                           runners/<runner>.py, the code for that kind
  metrics/<metric>.py      ``read(run) -> float | None`` for one metric
  references/<name>.py     the plain reference a configuration names

A runner fills a ``Run``; readers turn it into metrics.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import logging
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


class Refusal(Exception):
    """The run cannot start here (no chip, missing files): exit non-zero."""


def load_json(path: Path) -> dict:
    try:
        return json.loads(Path(path).read_text())
    except FileNotFoundError:
        raise Refusal(f"missing file {path}") from None


def load_module(path: Path, name: str | None = None):
    """Import a file by path (names may hold dots and dashes)."""
    path = Path(path)
    if not path.is_file():
        raise Refusal(f"missing file {path}")
    mod_name = "chipbench_" + (name or path.stem).replace(".", "_").replace(
        "-", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def benchmark(root: Path = ROOT) -> dict:
    return load_json(Path(root) / "BENCHMARK.json")


def cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise Refusal(f"no workload {name!r} in BENCHMARK.json")


def config_doc(name: str, here: Path = HERE) -> dict:
    return load_json(Path(here) / "configs" / f"{name}.json")


def mix_doc(name: str, here: Path = HERE) -> dict:
    return load_json(Path(here) / "mixes" / f"{name}.json")


def runner(mix: dict, here: Path = HERE):
    return load_module(Path(here) / "runners" / f"{mix['runner']}.py")


def reference(cfg: dict, here: Path = HERE):
    return load_module(Path(here) / "references" / f"{cfg['reference']}.py")


def cell_metrics(bench: dict, name: str, *, trace: bool) -> list[dict]:
    """The metric entries a run of cell ``name`` reports.

    End-to-end metrics without a ``workloads`` key hold in every cell;
    every per-layer metric lists its cells.
    """
    if not trace:
        return [m for m in bench["end_to_end"]
                if "workloads" not in m or name in m["workloads"]]
    return [m for m in bench["per_layer"] if name in m["workloads"]]


def program_model(config: dict):
    """The program's config and model for a configuration file."""
    import repro.configs as configs
    import repro.models as models
    cfg = configs.get(config["arch"]).replace(**config["model"])
    return cfg, models.build(cfg)


def tree_get(tree, path):
    for k in path:
        tree = tree[k]
    return tree


def metric_reader(name: str, here: Path = HERE):
    return load_module(Path(here) / "metrics" / f"{name}.py", name)


class _CompileLog(logging.Handler):
    """Names of the programs compiled inside the window, from the compile
    cache's misses (a compile there is a fault of the warm-up; the run
    reports them on stderr). A cache hit loads a program and is no
    compile."""

    def __init__(self, run):
        super().__init__(logging.DEBUG)
        self.run = run

    @classmethod
    def install(cls, run) -> "_CompileLog":
        log = logging.getLogger("jax._src.compiler")
        log.setLevel(logging.DEBUG)
        log.propagate = False           # its debug lines stay off stderr
        handler = cls(run)
        log.addHandler(handler)
        return handler

    def remove(self) -> None:
        logging.getLogger("jax._src.compiler").removeHandler(self)

    def emit(self, record):
        msg = record.getMessage()
        if "CACHE MISS for" in msg:
            self.run.compiled_in_window.append(msg.split("'")[1])


class Run:
    """What one run did, as the runners record it and the readers read it.

    Host times are ``time.perf_counter`` seconds. ``facts`` holds counts
    and results by name; ``checks`` holds each compared number with its
    limit; ``trace`` is the reduced device trace of a ``--trace 1`` run.
    """

    def __init__(self, *, cell: dict, config: dict, mix: dict, seed: int,
                 seconds: float, trace: bool, peaks: dict,
                 t_start: float | None = None,
                 trace_dir: Path | None = None):
        self.cell, self.config, self.mix = cell, config, mix
        self.seed, self.seconds, self.tracing = seed, seconds, trace
        self.peaks = peaks
        self.t_start = time.perf_counter() if t_start is None else t_start
        self.trace_dir = trace_dir
        self.spans: list[tuple[str, float, float]] = []
        self.facts: dict = {}
        self.checks: dict = {}
        self.setup_s: float | None = None
        self.window: tuple[float, float] | None = None
        self.memory_peak: int = 0
        self.attempted = 0
        self.failed = 0
        self.trace: dict | None = None
        self.compiled_in_window: list[str] = []
        self.control = False        # also read the float8 control
        self.control_run: Run | None = None   # the control's checks

    def note(self, what: str) -> None:
        """A progress line on stderr: seconds since the process started."""
        print(f"chipbench: {time.perf_counter() - self.t_start:9.3f} s "
              f"{what} done", file=sys.stderr, flush=True)

    # -- spans ----------------------------------------------------------

    @contextlib.contextmanager
    def span(self, name: str):
        """A host span; in a traced run also an annotation in the trace."""
        ann = contextlib.nullcontext()
        if self.tracing:
            import jax
            ann = jax.profiler.TraceAnnotation("bench:" + name)
        t0 = time.perf_counter()
        with ann:
            yield
        self.spans.append((name, t0, time.perf_counter()))

    def span_total(self, name: str) -> float:
        w0, w1 = self.window
        return sum(t1 - t0 for n, t0, t1 in self.spans
                   if n == name and t0 >= w0 and t1 <= w1)

    def span_durations(self, name: str) -> list[float]:
        w0, w1 = self.window
        return [t1 - t0 for n, t0, t1 in self.spans
                if n == name and t0 >= w0 and t1 <= w1]

    # -- the measured window --------------------------------------------

    def open_window(self) -> float:
        """End set-up; start the window (and the profiler when tracing)."""
        if self.tracing:
            import jax
            jax.profiler.start_trace(str(self.trace_dir))
            self._ann = jax.profiler.TraceAnnotation("bench:window")
            self._ann.__enter__()
        self._compile_log = _CompileLog.install(self)
        t = time.perf_counter()
        self.setup_s = t - self.t_start
        self.window = (t, None)
        return t

    def close_window(self) -> float:
        t = time.perf_counter()
        self.window = (self.window[0], t)
        self._compile_log.remove()
        if self.tracing:
            import jax
            self._ann.__exit__(None, None, None)
            jax.profiler.stop_trace()
        return t

    def read_memory_peak(self) -> None:
        """The peak bytes in use on the fullest chip, so far."""
        import jax
        self.memory_peak = max(int((d.memory_stats() or {}).get(
            "peak_bytes_in_use", 0)) for d in jax.local_devices())

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def check(self, name: str, value: float, limit: float, *,
              above: bool = False) -> None:
        """Record a compared number: it passes when ``value <= limit``
        (``above=True``: when ``value >= limit``)."""
        ok = value >= limit if above else value <= limit
        self.checks[name] = {"value": float(value), "limit": float(limit),
                             "ok": bool(ok)}

    def shadow(self) -> "Run":
        """An empty run of the same cell and seed, for the control's
        readings to be judged by the same checks."""
        return Run(cell=self.cell, config=self.config, mix=self.mix,
                   seed=self.seed, seconds=0, trace=False, peaks=self.peaks)

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(c["ok"] for c in
                                         self.checks.values())


def percentile(values, q: float) -> float:
    """Nearest-rank percentile (q in [0, 100]) of a non-empty list."""
    xs = sorted(values)
    k = max(0, min(len(xs) - 1, int(-(-q * len(xs) // 100)) - 1))
    return xs[k]
