"""The zamba2-2.7b cells at smoke size on the CPU.  See ``rehearse.py``;
run by hand.

``rehearse.smoke_tree`` shrinks only the configurations it lists, so this
file shrinks zamba2-2.7b in its own scratch tree: four layers with a
hybrid layer every two, so both shared blocks run and the PSG holds a
three-armed Branch in the layer Loop."""
import json
import os

import pytest

from rehearse import REPO, edit_json, result, run_cell, smoke_tree

BENCH = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
CELLS = [w["name"] for w in BENCH["workloads"] if w["config"] == "zamba2-2.7b"]
SMOKE_ARCH = {"n_layers": 4, "d_model": 64, "n_heads": 4, "n_kv_heads": 4,
              "head_dim": 32, "d_ff": 128, "vocab_size": 256,
              "ssm_state": 16, "ssm_head_dim": 16, "ssm_chunk": 8,
              "attn_every": 2, "n_shared_blocks": 2, "adapter_rank": 8,
              "loss_chunk": 16}
SMOKE_SHAPE = {"seq_len": 32, "batch": 2}


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    dst = smoke_tree(str(tmp_path_factory.mktemp("checkout")))

    def shrink(cfg):
        cfg["arch"].update(SMOKE_ARCH)
        cfg["shape"] = dict(SMOKE_SHAPE)
    edit_json(os.path.join(dst, "chipbench", "configs", "zamba2-2.7b",
                           "config.json"), shrink)
    return dst


def _expected(cell, trace):
    if trace:
        return {m["name"] for m in BENCH["per_layer"]
                if cell in m["workloads"]
                and m["source"] != "device_trace"}   # no device on a CPU
    return {m["name"] for m in BENCH["end_to_end"]
            if cell in m.get("workloads", [cell])}


def test_cells_are_the_two_added():
    assert CELLS == ["zamba2-2.7b.profile", "zamba2-2.7b.diagnose_8k"]


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_zamba2_cell_runs_correct_at_smoke_size(tree, cell, trace):
    proc = run_cell(tree, cell, trace=trace)
    out = result(proc)
    assert out["correct"] is True, out["compared"]
    assert set(out["metrics"]) == _expected(cell, trace)
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["device"]["platform"] == "cpu"
    assert all(v["value"] > 0 for v in out["metrics"].values())
    if cell.endswith("diagnose_8k"):
        # the hybrid job's PSG: a Branch inside the layer Loop
        assert "fleet: V " in proc.stderr
