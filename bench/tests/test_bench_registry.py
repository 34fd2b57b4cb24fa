"""Cells, configurations, traffic mixes and per-layer metrics are found by
their names: adding one adds files and entries and edits none."""
import json
import re
from pathlib import Path

import pytest

from bench import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _write(path: Path, text: str):
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(text)


def test_dummy_entries_are_discovered(tmp_path):
    bench_dir = tmp_path / "bench"
    spec = {
        "configs": [{"name": "dummy-model", "file": "bench/configs/dummy-model.json"}],
        "workloads": [{"name": "dummy-cell", "config": "dummy-model",
                       "traffic": "dummy-mix", "chips": 1}],
        "end_to_end": [{"name": "setup_s"},
                       {"name": "dummy_ms", "workloads": ["dummy-cell"]}],
        "per_layer": [{"name": "dummy.layer", "moves": "dummy_ms"},
                      {"name": "elsewhere", "moves": "other_ms"}],
    }
    _write(tmp_path / "BENCHMARK.json", json.dumps(spec))
    _write(bench_dir / "configs" / "dummy-model.json", json.dumps({"hidden": 4}))
    _write(bench_dir / "traffic" / "dummy-mix.json", json.dumps({"kind": "dummykind"}))
    _write(bench_dir / "drivers" / "dummykind.py", "def run(cell):\n    return 'ran'\n")
    _write(bench_dir / "metrics" / "dummy.layer.py", "def read(rec):\n    return 42.0\n")

    bench = harness.load_benchmark(tmp_path)
    cell = harness.workload(bench, "dummy-cell")
    assert harness.config(bench, cell["config"], tmp_path) == {"hidden": 4}
    mix = harness.traffic(cell["traffic"], bench_dir)
    assert harness.driver(mix["kind"], bench_dir).run(None) == "ran"
    names = [m["name"] for m in harness.metrics_of(bench, "dummy-cell", "per_layer")]
    assert names == ["dummy.layer"]
    assert harness.metric_reader("dummy.layer", bench_dir).read(None) == 42.0
    assert [m["name"] for m in harness.metrics_of(bench, "dummy-cell", "end_to_end")] == [
        "setup_s", "dummy_ms"]
    with pytest.raises(KeyError, match="no workload"):
        harness.workload(bench, "missing")


def test_every_entry_of_the_benchmark_has_its_files():
    bench = harness.load_benchmark()
    for c in bench["configs"]:
        assert NAME.match(c["name"]) and (harness.ROOT / c["file"]).is_file()
    for m in bench["per_layer"]:
        assert NAME.match(m["name"])
        assert hasattr(harness.metric_reader(m["name"]), "read")
        assert m["moves"] in {e["name"] for e in bench["end_to_end"]}
    for w in bench["workloads"]:
        assert NAME.match(w["name"])
        mix = harness.traffic(w["traffic"])
        assert all(hasattr(harness.driver(mix["kind"]), f) for f in ("run", "calibrate"))
        assert (harness.BENCH_DIR / "limits" / f"{w['name']}.json").is_file()
        e2e = {m["name"] for m in harness.metrics_of(bench, w["name"], "end_to_end")}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert harness.metrics_of(bench, w["name"], "per_layer")
