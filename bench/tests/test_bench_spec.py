"""`BENCHMARK.json` against the rules of its format, every name in it
resolving to its file, and a cell, mix or metric added by files alone."""
import dataclasses
import json
import re
import shutil
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, loops, spec  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def reported(metric, cell):
    return cell in metric.get("workloads", CELLS)


def test_top_level_keys_and_command():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert BENCH["paths"] == ["bench"]
    assert isinstance(BENCH["run_seconds"], int) and 1 <= BENCH["run_seconds"] <= 51
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_names_units_and_entry_keys():
    names = [e["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for e in BENCH[k]]
    assert all(NAME.match(n) for n in names) and len(set(names)) == len(names)
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        assert NAME.match(w["traffic"]) and NAME.match(w["config"])
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                         "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span", "program_counter",
                               "host_clock")
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(1, len(CELLS) // 2)


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_reports_setup_another_end_to_end_and_a_layer(cell):
    e2e = [m["name"] for m in BENCH["end_to_end"] if reported(m, cell)]
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = [m for m in BENCH["per_layer"] if reported(m, cell)]
    assert layer
    for m in layer:        # each moves an end-to-end metric this cell reports
        assert m["moves"] in e2e


def test_rooflines_and_mfu_are_percent_shares():
    for m in BENCH["per_layer"]:
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"


@pytest.mark.parametrize("cell", CELLS)
def test_every_name_resolves_to_its_file(cell):
    loaded = spec.load_cell(cell)
    assert loaded.reference.forward and loaded.traffic["mode"] in ("offline", "online")
    for m in loaded.per_layer:
        assert callable(spec.load_module(spec.metric_file(m["name"])).read)


@pytest.mark.parametrize("config", [c["name"] for c in BENCH["configs"]])
def test_configuration_files_hold_the_repo_presets(config):
    from repro.configs import vgg9_snn
    entry = next(c for c in BENCH["configs"] if c["name"] == config)
    assert entry["file"].startswith("bench/configs/") and entry["reduced"] == []
    doc = json.loads((ROOT / entry["file"]).read_text())
    preset = getattr(vgg9_snn, doc["preset"].rsplit(".", 1)[1])
    model = dict(doc["model"], stages=tuple(doc["model"]["stages"]))
    assert model == dataclasses.asdict(preset)
    assert doc["reduced"] == entry["reduced"]


def test_a_cell_mix_and_metric_are_added_by_files_alone(tmp_path):
    """A new cell on a new mix, with a new per-layer metric: new files and
    new entries in BENCHMARK.json, no edit to an existing file."""
    root = tmp_path / "repo"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns(".*", "__pycache__", "tests"))
    bench = json.loads(json.dumps(BENCH))
    (root / "bench/traffic/bursty.offline.json").write_text(json.dumps(
        {"mode": "offline", "slots": 16, "max_queue": 32, "kinds": {"patch": 1},
         "pool": 8}))
    (root / "bench/metrics/steps_in_window.py").write_text(
        "def read(ctx):\n    return len(ctx.window.steps) or None\n")
    bench["workloads"].append({"name": "cifar10_fp32.bursty", "chips": 1,
                               "config": "vgg9_cifar10_fp32",
                               "traffic": "bursty.offline", "why": "test"})
    bench["per_layer"].append({"name": "steps_in_window", "unit": "count",
                               "better": "higher", "source": "host_clock",
                               "layer": "engine", "moves": "images_per_s",
                               "workloads": ["cifar10_fp32.bursty"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = spec.load_cell("cifar10_fp32.bursty", root=root)
    assert cell.traffic["slots"] == 16 and cell.traffic["name"] == "bursty.offline"
    assert [m["name"] for m in cell.per_layer] == ["steps_in_window"]
    window = loops.Window(start=0.0, end=1.0)
    window.steps = [loops.Step(0, 0.0, 1.0, {}, {})] * 3
    out = harness.per_layer(cell.per_layer, SimpleNamespace(window=window),
                            bench_dir=root / "bench")
    assert out == {"steps_in_window": {"value": 3.0, "unit": "count"}}
    window.steps = []       # nothing to read: the metric is left out
    assert harness.per_layer(cell.per_layer, SimpleNamespace(window=window),
                             bench_dir=root / "bench") == {}
