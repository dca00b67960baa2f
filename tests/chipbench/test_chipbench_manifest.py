"""BENCHMARK.json resolves, by name, to files of the benchmark."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

import manifest_rules as rules  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
PER_LAYER = [m["name"] for m in BENCH["per_layer"]]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_to_its_files(cell):
    rules.check_cell(BENCH, cell)


@pytest.mark.parametrize("metric", PER_LAYER)
def test_per_layer_metric_resolves_and_moves_a_reported_metric(metric):
    rules.check_entry(BENCH, metric)


def test_names_and_units_use_only_the_allowed_characters():
    rules.check_names(BENCH)


def test_every_cell_reports_an_end_to_end_and_a_per_layer_metric():
    rules.check_reports(BENCH)


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
