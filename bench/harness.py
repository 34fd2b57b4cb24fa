"""What every cell shares: finding its files by name, host spans, the count
of compilations, the per-layer metric readers and the result line.

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix. The
configuration is ``bench/configs/<config>.json``; the traffic mix is
``bench/traffic/<traffic>.json``, whose ``kind`` names the driver
``bench/drivers/<kind>.py`` that runs it; a per-layer metric ``<name>`` is
read by ``bench/metrics/<name>.py``. Adding any of them adds files and
entries, and edits none.
"""
from __future__ import annotations

import collections
import contextlib
import importlib.util
import json
import sys
import time
from pathlib import Path
from types import ModuleType

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


# ---------------------------------------------------------------------------
# registry: everything is found by its name in BENCHMARK.json
# ---------------------------------------------------------------------------

def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def workload(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                   f"known: {[w['name'] for w in bench['workloads']]}")


def config(bench: dict, name: str, root: Path = ROOT) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return json.loads((root / c["file"]).read_text())
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def traffic(name: str, bench_dir: Path = BENCH_DIR) -> dict:
    return json.loads((bench_dir / "traffic" / f"{name}.json").read_text())


def _load_file(path: Path) -> ModuleType:
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def driver(kind: str, bench_dir: Path = BENCH_DIR) -> ModuleType:
    return _load_file(bench_dir / "drivers" / f"{kind}.py")


def metric_reader(name: str, bench_dir: Path = BENCH_DIR) -> ModuleType:
    return _load_file(bench_dir / "metrics" / f"{name}.py")


def metrics_of(bench: dict, cell: str, section: str) -> list[dict]:
    """The metrics of ``section`` (``end_to_end`` or ``per_layer``) that
    ``cell`` reports: those that list it, and those that list no cells and
    move an end-to-end metric the cell reports (for ``per_layer``), or that
    list no cells at all (for ``end_to_end``)."""
    def listed(m, e2e=None):
        if "workloads" in m:
            return cell in m["workloads"]
        return e2e is None or m["moves"] in e2e
    e2e = [m["name"] for m in bench["end_to_end"] if listed(m)]
    if section == "end_to_end":
        return [m for m in bench["end_to_end"] if listed(m)]
    return [m for m in bench["per_layer"] if listed(m, e2e)]


# ---------------------------------------------------------------------------
# host spans and compile events
# ---------------------------------------------------------------------------

class Spans:
    """Host-clock spans kept in memory, with the calling thread's CPU time
    in each and the CPUs it ended on. With ``annotate`` each span is also a
    ``jax.profiler.TraceAnnotation`` named ``bench:<name>``, so that the
    profiler's trace carries it on the device's clock."""

    PREFIX = "bench:"

    def __init__(self):
        self.records: list[tuple[str, float, float, float]] = []
        self.cpus: collections.Counter = collections.Counter()
        self.annotate = False

    @contextlib.contextmanager
    def __call__(self, name: str):
        ann = contextlib.nullcontext()
        if self.annotate:
            import jax
            ann = jax.profiler.TraceAnnotation(self.PREFIX + name)
        c0, t0 = time.thread_time(), time.perf_counter()
        with ann:
            try:
                yield
            finally:
                t1 = time.perf_counter()
                self.records.append((name, t0, t1, time.thread_time() - c0))
                self.cpus[_cpu_now()] += 1

    def total(self, *names: str) -> float:
        return sum(t1 - t0 for n, t0, t1, _ in self.records if n in names)

    def summary(self, t0: float, t1: float) -> str:
        """Per span name inside ``[t0, t1]``: how many, and the mean wall
        and thread-CPU milliseconds of one."""
        by: dict[str, list] = {}
        for n, a, b, c in self.records:
            if t0 <= a and b <= t1:
                by.setdefault(n, []).append((b - a, c))
        return "; ".join(
            f"{n} n={len(v)} wall={1e3 * sum(w for w, _ in v) / len(v):.3f} ms "
            f"cpu={1e3 * sum(c for _, c in v) / len(v):.3f} ms" for n, v in by.items())


def _cpu_now() -> int:
    """The CPU the calling thread last ran on (-1 where Linux's /proc has
    no record of it)."""
    try:
        with open("/proc/thread-self/stat") as f:
            return int(f.read().rsplit(")", 1)[1].split()[36])
    except (OSError, IndexError, ValueError):
        return -1


class CompileEvents:
    """JAX's compile-time events (tracing, lowering, and compilation or its
    load from the persistent cache) with the host time they ended at."""

    NAMES = ("/jax/core/compile/jaxpr_trace_duration",
             "/jax/core/compile/jaxpr_to_mlir_module_duration",
             "/jax/core/compile/backend_compile_duration")
    BACKEND = NAMES[2]

    def __init__(self):
        import jax
        self.events: list[tuple[str, float, float]] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_):
        if event in self.NAMES:
            self.events.append((event, duration, time.perf_counter()))

    def seconds(self, t0: float, t1: float) -> float:
        return sum(d for _, d, t in self.events if t0 <= t <= t1)

    def backend_compiles(self, t0: float, t1: float) -> int:
        return sum(1 for e, _, t in self.events if e == self.BACKEND and t0 <= t <= t1)


# ---------------------------------------------------------------------------
# the run's record and the result line
# ---------------------------------------------------------------------------

class Record:
    """Everything a run measured, handed to the per-layer metric readers.

    ``kind`` is the traffic kind; ``spans`` and ``compiles`` are the host
    records; ``window`` is the host-clock ``(start, end)`` of the measured
    window; ``units`` the steps or requests completed in it; ``flops_per_unit``
    the model FLOPs of one; ``peak`` the chip's row of the peak table;
    ``trace`` the reduced profiler trace of the window (``--trace 1`` only).
    """

    def __init__(self, kind: str, chips: int, peak: dict):
        self.kind = kind
        self.chips = chips
        self.peak = peak
        self.spans = Spans()
        self.compiles = CompileEvents()
        self.window = (0.0, 0.0)
        self.setup_end = 0.0
        self.units = 0
        self.flops_per_unit = 0
        self.trace: dict | None = None

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]


def program_bytes(label: str, lower) -> int | None:
    """Arguments, outputs and temporaries of one compiled program by the
    compiler's memory analysis, outputs that alias arguments counted once.
    ``lower()`` returns the lowered program, built through the program's
    public entry points. Where that fails, a line says why and None comes
    back: a change in the program costs the figure, never the run."""
    try:
        m = lower().compile().memory_analysis()
        total = (m.argument_size_in_bytes + m.output_size_in_bytes
                 - m.alias_size_in_bytes + m.temp_size_in_bytes)
    except Exception as e:          # noqa: BLE001 - reported, not raised
        print(f"memory_analysis {label}: not available ({e!r})", file=sys.stderr)
        return None
    print(f"memory_analysis {label}: {total} B ({m})", file=sys.stderr)
    return total


def check(value: float, limit: float) -> dict:
    return {"value": value, "limit": limit}


def emit(correct: bool, attempted: int, failed: int, metrics: dict,
         device: dict, checks: dict, breakdown: dict | None = None):
    """Print each compared number beside its limit on standard error, then
    the result line on standard output, with ``checks`` as its last key."""
    for name, c in checks.items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
