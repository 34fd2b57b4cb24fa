#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` once, on the chips of this machine.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up (the graph build, compilation or its load from JAX's persistent
cache, weights from the seed, warm-up of every program the window runs)
counts as ``setup_s``; then the window measures for ``--seconds``. With
``--trace 0`` the result line holds the cell's end-to-end metrics; with
``--trace 1`` the window runs under the profiler and the line holds the
cell's per-layer metrics, the device's busy time and a breakdown. After the
window, what the timed path produced is compared with the plain reference,
and each compared number is printed beside its limit; ``correct`` is true
when every one is within it.

Without a TPU, or with fewer chips than the cell asks for, it exits with
code 2 and prints no result line.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
# the system under test, and this directory as the ``bench`` package
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

LIMITS_DIR = ROOT / "bench" / "limits"


class NoChip(RuntimeError):
    """No TPU, or fewer chips than the cell asks for."""


def require_chips(n: int) -> list:
    import jax
    try:
        devices = jax.devices("tpu")
    except RuntimeError as e:
        raise NoChip(f"no TPU found by JAX ({e}); this benchmark runs on the chip only")
    if len(devices) < n:
        raise NoChip(f"the cell asks for {n} TPU chips and JAX finds {len(devices)}")
    return devices[:n]


@dataclasses.dataclass
class Cell:
    """One run of one cell: what the drivers are handed."""
    name: str
    config: dict
    mix: dict
    devices: list
    seed: int
    seconds: float
    trace: bool
    record: object


def limits(name: str) -> dict:
    return json.loads((LIMITS_DIR / f"{name}.json").read_text())


def run_cell(bench: dict, name: str, seed: int, seconds: float, traced: bool,
             devices: list, config: dict | None = None, lims: dict | None = None,
             t_start: float = T_START) -> dict:
    """Run the cell and return its result line as a dict. ``config`` and
    ``lims`` replace the cell's configuration file and limits, for tests."""
    from bench import harness, peaks

    wl = harness.workload(bench, name)
    mix = harness.traffic(wl["traffic"])
    config = config or harness.config(bench, wl["config"])
    lims = lims or limits(name)
    kind = devices[0].device_kind
    peak = peaks.peaks_for(kind) if devices[0].platform == "tpu" else {}
    record = harness.Record(mix["kind"], len(devices), peak)
    cell = Cell(name, config, mix, devices, seed, seconds, traced, record)
    out = harness.driver(mix["kind"]).run(cell)

    setup_s = record.setup_end - t_start
    compiles = record.compiles.backend_compiles(*record.window)
    print(f"set-up {setup_s:.3f} s; window {record.window_s:.3f} s, "
          f"{record.units} {'steps' if mix['kind'] == 'train' else 'requests'}; "
          f"compilations in the window: {compiles}", file=sys.stderr)
    print(f"window spans: {record.spans.summary(*record.window)}; "
          f"CPUs the main thread ended spans on: {dict(record.spans.cpus)}", file=sys.stderr)
    values = dict(out["metrics"], setup_s=setup_s)
    # the runtime's peak leaves out a program's temporaries on the TPU; the
    # compiler's count of the windowed programs' bytes is the larger figure
    runtime_peak, program = out["memory_runtime_peak_bytes"], out["memory_program_bytes"]
    device = {"platform": devices[0].platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": max(runtime_peak, program or 0),
              "memory_runtime_peak_bytes": runtime_peak,
              "memory_program_bytes": program}
    breakdown = None
    if traced:
        values = {}
        for m in harness.metrics_of(bench, name, "per_layer"):
            v = harness.metric_reader(m["name"]).read(record)
            if v is not None:
                values[m["name"]] = v
        if record.trace is not None:
            device.update(busy_s=record.trace["busy_s"], window_s=record.trace["window_s"])
            breakdown = record.trace["breakdown"]
    section = "per_layer" if traced else "end_to_end"
    metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
               for m in harness.metrics_of(bench, name, section) if m["name"] in values}
    checks = {k: harness.check(v, lims[k]) for k, v in out["readings"].items()}
    correct = (out["failed"] == 0 and out["attempted"] > 0
               and all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
                       for c in checks.values()))
    return {"correct": correct, "attempted": out["attempted"], "failed": out["failed"],
            "metrics": metrics, "device": device, "breakdown": breakdown,
            "checks": checks}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness
    bench = harness.load_benchmark(ROOT)
    chips = harness.workload(bench, args.workload)["chips"]
    try:
        devices = require_chips(chips)
    except NoChip as e:
        print(f"bench/run.py: {e}", file=sys.stderr)
        return 2

    import jax
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    # every program, however quick to compile, is cached: a later run of the
    # cell compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    print(f"compilation cache: {cache}; devices: {devices}", file=sys.stderr)

    res = run_cell(bench, args.workload, args.seed, args.seconds, bool(args.trace), devices)
    harness.emit(res["correct"], res["attempted"], res["failed"], res["metrics"],
                 res["device"], res["checks"], res["breakdown"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
