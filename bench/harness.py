"""The harness: runs one cell of ``BENCHMARK.json`` and builds its result line.

Everything that belongs to one configuration, traffic mix or metric is
found by name, so a later cell is added as files and entries only:

- ``BENCHMARK.json`` names the cell's configuration and traffic mix;
- the configuration's file (its ``file`` entry) holds the deployment's
  sizes and guarantees, and names its ``driver`` (``bench/drivers/<d>.py``,
  the general generator and loop for that kind of system) and its plain
  ``reference`` (``bench/reference/<r>.py``);
- the traffic mix is ``bench/traffic/<traffic>.json``, parameters only;
- every metric, end to end or per layer, is read by
  ``bench/metrics/<metric name>.py``, whose ``read(run)`` returns a number,
  or None where the run holds nothing for it to read.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import os
import pathlib
import shutil
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parent
CHECKOUT = BENCH.parent


def process_start_time() -> float:
    """Wall-clock time at which this process started (Linux ``/proc``);
    the harness's own import time where ``/proc`` cannot tell."""
    try:
        with open("/proc/self/stat") as f:
            fields = f.read().rsplit(")", 1)[1].split()
        start_ticks = float(fields[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return time.time() - (uptime - start_ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return _IMPORTED


_IMPORTED = time.time()


# ---------------------------------------------------------------------------
# finding a cell's pieces by name
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict                # the configuration file's contents
    traffic: dict               # the traffic mix file's contents
    end_to_end: list            # BENCHMARK.json metric entries of this cell
    per_layer: list
    root: pathlib.Path          # checkout root the cell was read from


def load_benchmark(root: pathlib.Path = CHECKOUT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(name: str, root: pathlib.Path = CHECKOUT) -> Cell:
    spec = load_benchmark(root)
    work = {w["name"]: w for w in spec["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(known: {sorted(work)})")
    w = work[name]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    with open(root / conf["file"]) as f:
        config = json.load(f)
    with open(root / "bench" / "traffic" / f"{w['traffic']}.json") as f:
        traffic = json.load(f)
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic,
                end_to_end=[m for m in spec["end_to_end"] if _applies(m, name)],
                per_layer=[m for m in spec["per_layer"] if _applies(m, name)],
                root=root)


_MODULES: dict = {}


def load_module(path: pathlib.Path):
    """Import a benchmark file by its path (metric names hold dots, so
    readers are not importable by module name)."""
    path = pathlib.Path(path).resolve()
    if path not in _MODULES:
        if not path.is_file():
            raise FileNotFoundError(path)
        spec = importlib.util.spec_from_file_location(
            "bench_file_" + str(len(_MODULES)), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _MODULES[path] = mod
    return _MODULES[path]


def driver(cell: Cell):
    return load_module(cell.root / "bench" / "drivers"
                       / f"{cell.config['driver']}.py")


def reference(cell: Cell):
    return load_module(cell.root / "bench" / "reference"
                       / f"{cell.config['reference']}.py")


def reader(cell: Cell, metric: str):
    return load_module(cell.root / "bench" / "metrics" / f"{metric}.py").read


# ---------------------------------------------------------------------------
# what a driver hands back
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Run:
    cell: Cell
    seed: int
    setup_s: float
    window_s: float
    attempted: int
    failed: int
    work: dict                  # counts of work completed in the window
    latencies: dict             # timed op -> [seconds of each call]
    counters: dict              # program counters, change over the window
    spans: list                 # program span events inside the window
    shapes: dict                # sizes the readers need (from the config)
    checks: dict                # compared number -> {"value", "limit"}
    memory_peak_bytes: int | None
    trace: object = None        # trace_reduce.TraceSummary of a traced run
    peaks: object = None        # peaks.Peaks of the device, where known
    evidence: dict = None       # what the driver compared (the control
    #   and the calibration read it again)
    window_compiles: int = 0    # backend compiles inside the window (0)

    @property
    def correct(self) -> bool:
        return all(c["value"] <= c["limit"] for c in self.checks.values())


_COMPILES = {"count": 0, "listening": False}


def _on_duration(event, duration, **_):
    if event == "/jax/core/compile/backend_compile_duration":
        _COMPILES["count"] += 1


class Window:
    """The measured window.  With ``trace`` it runs under the JAX profiler
    (host annotations on, Python tracer off) inside one ``bench.window``
    annotation; ``mark(name)`` brackets a step of the loop."""

    def __init__(self, trace: bool, log_dir: pathlib.Path | None):
        self.trace = trace
        self.log_dir = log_dir
        self.wall0 = self.t0 = self.seconds = None
        self.compiles = self._compiles0 = 0
        self._outer = None

    def __enter__(self):
        import jax
        self._compiles0 = _COMPILES["count"]
        if not _COMPILES["listening"]:
            jax.monitoring.register_event_duration_secs_listener(_on_duration)
            _COMPILES["listening"] = True
        if self.trace:
            import jax
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            shutil.rmtree(self.log_dir, ignore_errors=True)
            jax.profiler.start_trace(str(self.log_dir),
                                     profiler_options=opts)
            self._outer = jax.profiler.TraceAnnotation("bench.window")
            self._outer.__enter__()
        self.wall0 = time.time()
        self.t0 = time.perf_counter()
        return self

    def elapsed(self) -> float:
        return time.perf_counter() - self.t0

    def mark(self, name: str):
        if not self.trace:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def __exit__(self, *exc):
        self.seconds = time.perf_counter() - self.t0
        self.compiles = _COMPILES["count"] - self._compiles0
        if self.trace:
            import jax
            self._outer.__exit__(*exc)
            jax.profiler.stop_trace()
        return False


# ---------------------------------------------------------------------------
# one run of one cell
# ---------------------------------------------------------------------------

def trace_dir(cell: Cell, seed: int) -> pathlib.Path:
    return cell.root / "bench_out" / "trace" / f"{cell.name}-{seed}"


def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool,
             started: float) -> Run:
    """Drive the cell once; returns a :class:`Run`.  ``started`` is the
    wall time set-up began (the process start for the command)."""
    window = Window(trace, trace_dir(cell, seed))
    run = driver(cell).run(cell, seed=seed, seconds=seconds, window=window,
                           started=started, reference=reference(cell))
    run.window_compiles = window.compiles
    import jax
    from .peaks import PEAKS
    run.peaks = PEAKS.get(jax.devices()[0].device_kind)
    if trace:
        from . import trace_reduce
        xplane = trace_reduce.find_xplane(str(window.log_dir))
        run.trace = trace_reduce.reduce_events(trace_reduce.load_events(xplane))
        shutil.rmtree(window.log_dir, ignore_errors=True)
    return run


def _number(x) -> float | int:
    return int(x) if isinstance(x, (int,)) and not isinstance(x, bool) \
        else float(x)


def result_line(run: Run, *, trace: bool, device: dict) -> dict:
    """The contract's last line.  With ``trace`` the metrics are the cell's
    per-layer metrics, else its end-to-end metrics; a reader that finds
    nothing leaves its metric out."""
    cell = run.cell
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = reader(cell, m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": _number(value), "unit": m["unit"]}
    dev = dict(device, memory_peak_bytes=run.memory_peak_bytes)
    line = {"correct": run.correct, "attempted": run.attempted,
            "failed": run.failed, "metrics": metrics, "device": dev}
    if trace and run.trace is not None:
        dev["busy_s"] = run.trace.busy_s
        dev["window_s"] = run.trace.window_s
        line["breakdown"] = run.trace.breakdown()
    line["window_compiles"] = run.window_compiles
    line["checks"] = {k: {"value": _number(v["value"]),
                          "limit": _number(v["limit"])}
                      for k, v in run.checks.items()}
    return line


def print_checks(run: Run, stream=sys.stderr) -> None:
    print(f"compiles inside the window: {run.window_compiles}", file=stream)
    for k, v in run.checks.items():
        verdict = "ok" if v["value"] <= v["limit"] else "FAILED"
        print(f"check {k}: {v['value']!r} limit {v['limit']!r} {verdict}",
              file=stream, flush=True)
