"""BENCHMARK.json resolves, by name, to files of the benchmark."""

import importlib
import json
import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]
PER_LAYER = [m["name"] for m in BENCH["per_layer"]]


def _load(path):
    return json.loads((ROOT / path).read_text())


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_to_its_files(cell):
    entry = next(w for w in BENCH["workloads"] if w["name"] == cell)
    spec = _load(f"chipbench/workloads/{cell}.json")
    assert spec["config"] == entry["config"]
    cfg = next(c for c in BENCH["configs"] if c["name"] == entry["config"])
    conf = _load(cfg["file"])
    for key in ("source", "reduced", "assumed", "departures"):
        assert key in conf
    assert conf["reduced"] == cfg["reduced"]
    driver = importlib.import_module(f"chipbench.drivers.{spec['driver']}")
    assert callable(driver.run)
    assert len(entry["why"]) <= 200 and entry["chips"] in (1, 4)


@pytest.mark.parametrize("metric", PER_LAYER)
def test_per_layer_metric_resolves_and_moves_a_reported_metric(metric):
    entry = next(m for m in BENCH["per_layer"] if m["name"] == metric)
    spec = _load(f"chipbench/metrics/{metric}.json")
    reader = importlib.import_module(f"chipbench.readers.{spec['reader']}")
    assert callable(reader.read)
    assert spec["layer"] == entry["layer"] and spec["moves"] == entry["moves"]
    if "counts" in spec.get("args", {}):
        module, fn = spec["args"]["counts"].rsplit(".", 1)
        assert callable(getattr(
            importlib.import_module(f"chipbench.counts.{module}"), fn))
    moved = next(m for m in BENCH["end_to_end"] if m["name"] == entry["moves"])
    for cell in entry["workloads"]:
        assert cell in CELLS
        assert "workloads" not in moved or cell in moved["workloads"]


def test_names_and_units_use_only_the_allowed_characters():
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [x["name"] for x in BENCH[section]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names), names
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for w in BENCH["workloads"]:
        assert NAME.match(w["traffic"]) and NAME.match(w["config"])
    assert any(m["name"] == "setup_s" for m in BENCH["end_to_end"])
    assert all(0.01 <= m["bound"] <= 0.1 for m in BENCH["end_to_end"])
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(CELLS) // 4)


def test_every_cell_reports_an_end_to_end_and_a_per_layer_metric():
    for cell in CELLS:
        e2e = [m["name"] for m in BENCH["end_to_end"]
               if "workloads" not in m or cell in m["workloads"]]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert any(cell in m["workloads"] for m in BENCH["per_layer"])


def test_run_py_names_no_cell_configuration_or_metric():
    text = (ROOT / "chipbench/run.py").read_text()
    named = (CELLS + PER_LAYER + [c["name"] for c in BENCH["configs"]]
             + [m["name"] for m in BENCH["end_to_end"]])
    assert not [n for n in named if n in text]


def test_on_the_cpu_the_command_refuses_and_prints_no_metric():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, *BENCH["command"][1:], "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "refused" in p.stderr
