"""CPU rehearsals of the harness: every cell end to end at a tiny size,
the command's refusals, and a cell, a traffic mix and a metric added
as files alone."""

import json
import os
import shutil
import subprocess
import sys
import time

import pytest

import harness
from conftest import CELLS, ROOT, shrink


def run(cell, trace, root=ROOT, seconds=1.0, seed=2**40 + 3):
    return harness.run_cell(root, cell, seed, seconds, trace,
                            time.perf_counter(), require_accelerator=False,
                            shrink=shrink)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_end_to_end(cell):
    out = run(cell, trace=False)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 1
    spec = harness.load_spec(ROOT)
    want = {m["name"] for m in harness.cell_metrics(spec, cell, False)}
    assert set(out["metrics"]) == want
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert list(out)[-1] == "compared"
    assert out["compared"]["off_device_keys"]["value"] == 0
    assert out["closures"]
    assert out["device"]["platform"] == "cpu"


@pytest.mark.parametrize("cell", CELLS)
def test_traced_run_reports_per_layer_metrics(cell):
    out = run(cell, trace=True)
    assert out["correct"] is True
    spec = harness.load_spec(ROOT)
    want = {m["name"] for m in harness.cell_metrics(spec, cell, True)}
    # the CPU has no device plane, so the device-trace metrics that
    # find nothing are left out; every span and counter metric is there
    got = set(out["metrics"])
    assert want - got <= {"closure_kernel_share"}
    assert out["metrics"]["compiles_in_window"]["value"] == 0
    assert out["device"]["window_s"] > 0
    assert "idle_gaps" in out["breakdown"]


def command(root, cell="tendermint-cas.short-run"):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", cell,
         "--seed", str(2**35 + 1), "--seconds", "1", "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)


def test_command_without_an_accelerator_exits_non_zero():
    p = command(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no accelerator" in p.stderr


def test_command_without_the_program_exits_non_zero(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns(".*", "__pycache__"))
    p = command(str(tmp_path))
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_new_cell_traffic_and_metric_are_found_by_name(tmp_path):
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns(".*", "__pycache__"))
    spec = harness.load_spec(ROOT)
    b = tmp_path / "benchmark"
    cfg = json.loads((b / "configs" / "tendermint-cas.json").read_text())
    cfg["name"] = "other-register"
    cfg["values"] = 3
    (b / "configs" / "other-register.json").write_text(json.dumps(cfg))
    (b / "traffic" / "two-keys.json").write_text(json.dumps(
        {"keys": 2, "pool": 2,
         "faults": {"corrupt": {"every": 2, "at": 1}}}))
    (b / "metrics" / "checks_per_window.py").write_text(
        "def read(run):\n    return len(run.checks)\n")
    spec["configs"].append({"name": "other-register", "source": "x",
                            "file": "benchmark/configs/other-register.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "other-register.two-keys",
                              "config": "other-register",
                              "traffic": "two-keys", "chips": 1,
                              "why": "test"})
    spec["per_layer"].append({"name": "checks_per_window", "unit": "count",
                              "better": "higher",
                              "source": "program_counter", "layer": "test",
                              "moves": "ops_per_s",
                              "workloads": ["other-register.two-keys"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    out = run("other-register.two-keys", trace=True, root=str(tmp_path))
    assert out["correct"] is True
    assert out["metrics"]["checks_per_window"]["value"] == out["attempted"]
