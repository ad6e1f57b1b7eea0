"""Every cell at smoke size on the CPU, and a new cell picked up from new
files and entries alone.  See ``rehearse.py``; run by hand."""
import json
import os
import shutil

import pytest

from rehearse import REPO, edit_json, result, run_cell, smoke_tree

BENCH = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return smoke_tree(str(tmp_path_factory.mktemp("checkout")))


def _expected(cell, trace):
    if trace:
        return {m["name"] for m in BENCH["per_layer"]
                if cell in m["workloads"]
                and m["source"] != "device_trace"}   # no device on a CPU
    return {m["name"] for m in BENCH["end_to_end"]
            if cell in m.get("workloads", [cell])}


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_correct_at_smoke_size(tree, cell, trace):
    out = result(run_cell(tree, cell, trace=trace))
    assert out["correct"] is True, out["compared"]
    assert list(out)[-1] == "compared"
    assert set(out["metrics"]) == _expected(cell, trace)
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["device"]["platform"] == "cpu"
    assert all(v["value"] > 0 for v in out["metrics"].values())


def test_refuses_a_host_without_a_tpu(tree):
    import subprocess
    import sys
    proc = subprocess.run(
        [sys.executable, os.path.join(tree, "chipbench", "run.py"),
         "--workload", CELLS[0], "--seed", "1", "--seconds", "1"],
        cwd=tree, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "'cpu'" in proc.stderr
    assert proc.stdout.strip() == ""


def test_new_cell_config_and_metric_from_files_alone(tree, tmp_path):
    """A later PR adds a configuration, a traffic mix, limits and a
    per-layer metric as new files plus new entries: no file changes."""
    new = str(tmp_path / "checkout")
    shutil.copytree(tree, new, symlinks=True)
    cb = os.path.join(new, "chipbench")
    shutil.copytree(os.path.join(cb, "configs", "mamba2-130m"),
                    os.path.join(cb, "configs", "mamba2-wide"))
    edit_json(os.path.join(cb, "configs", "mamba2-wide", "config.json"),
              lambda c: c["arch"].update(name="mamba2-wide", d_model=96))
    shutil.copy(os.path.join(cb, "traffic", "diagnose_8k.json"),
                os.path.join(cb, "traffic", "diagnose_tiny.json"))
    edit_json(os.path.join(cb, "traffic", "diagnose_tiny.json"),
              lambda t: t.update(scales=[4, 8, 16], hosts_per_cycle=1))
    shutil.copy(os.path.join(cb, "limits", "mamba2-130m.diagnose_8k.json"),
                os.path.join(cb, "limits", "mamba2-wide.diagnose_tiny.json"))
    with open(os.path.join(cb, "metrics", "cycles_per_window.py"), "w") as f:
        f.write("def read(raw):\n    return raw.get('cycles')\n")

    def add(b):
        b["configs"].append({"name": "mamba2-wide", "source": "test",
                             "file": "chipbench/configs/mamba2-wide/"
                                     "config.json",
                             "reduced": [], "why": "test"})
        b["workloads"].append({"name": "mamba2-wide.diagnose_tiny",
                               "config": "mamba2-wide",
                               "traffic": "diagnose_tiny", "chips": 1,
                               "why": "test"})
        for m in b["end_to_end"]:
            if m["name"] == "diagnose_ms_p95":
                m["workloads"].append("mamba2-wide.diagnose_tiny")
        b["per_layer"].append({"name": "cycles_per_window", "unit": "count",
                               "better": "higher", "source": "host_clock",
                               "layer": "diagnosis cycle",
                               "moves": "diagnose_ms_p95"})
    edit_json(os.path.join(new, "BENCHMARK.json"), add)
    out = result(run_cell(new, "mamba2-wide.diagnose_tiny", trace=1))
    assert out["correct"] is True
    assert out["metrics"]["cycles_per_window"]["value"] == out["attempted"]
    out = result(run_cell(new, "mamba2-wide.diagnose_tiny", trace=0))
    assert set(out["metrics"]) == {"setup_s", "diagnose_ms_p95"}
