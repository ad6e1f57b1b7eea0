"""CPU rehearsal of the chip benchmark: every cell at smoke size.

The benchmark refuses a host without a TPU, so these helpers steer it
from outside: they copy ``BENCHMARK.json`` and ``chipbench/`` into a
scratch checkout (``src`` linked), shrink each configuration and fleet to
smoke size there, and run ``chipbench/run.py`` in a child process whose
wrapper accepts the CPU, lends it the v5e's peaks, and puts the fused
detection kernels into Pallas interpret mode in float32, the kernel code
and precision the chip runs.  Run by hand::

    PYTHONPATH=src python -m pytest -q chipbench/tests
"""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

SMOKE_ARCH = {
    "mamba2-130m": {"n_layers": 2, "d_model": 64, "ssm_state": 16,
                    "ssm_head_dim": 16, "ssm_chunk": 8, "vocab_size": 256,
                    "loss_chunk": 16},
}
SMOKE_SHAPE = {"mamba2-130m": {"seq_len": 32, "batch": 2}}
SMOKE_SCALES = {"diagnose_8k": [16, 32, 64]}

WRAPPER = """
import os, sys
root, argv = sys.argv[1], sys.argv[2:]
sys.path[:0] = [os.path.join(root, "chipbench"), os.path.join(root, "src")]
import jax
import harness
harness.require_chips = lambda n: jax.devices()[:n]
_peaks = harness.peaks_for
harness.peaks_for = lambda kind: _peaks("TPU v5 lite")
from repro.kernels.detect_fused import ops
ops.kernel_mode = lambda interpret=None: "interpret"
{patch}
import run
sys.exit(run.main(argv))
"""


def edit_json(path: str, fn) -> None:
    with open(path) as f:
        data = json.load(f)
    fn(data)
    with open(path, "w") as f:
        json.dump(data, f, indent=1)


def smoke_tree(dst: str) -> str:
    """A scratch checkout with every cell at smoke size."""
    os.makedirs(dst, exist_ok=True)
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), dst)
    shutil.copytree(os.path.join(REPO, "chipbench"),
                    os.path.join(dst, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    os.symlink(os.path.join(REPO, "src"), os.path.join(dst, "src"))
    for name, arch in SMOKE_ARCH.items():
        def shrink(cfg, arch=arch, name=name):
            cfg["arch"].update(arch)
            cfg["shape"] = SMOKE_SHAPE[name]
        edit_json(os.path.join(dst, "chipbench", "configs", name,
                               "config.json"), shrink)
    for name, scales in SMOKE_SCALES.items():
        def fleet(t, scales=scales):
            t["scales"] = scales
            t["hosts_per_cycle"] = 2
            t["sample_cycles"] = 4
        edit_json(os.path.join(dst, "chipbench", "traffic", name + ".json"),
                  fleet)
    return dst


def run_cell(tree: str, workload: str, *, seed: int = 2 ** 31 + 11,
             seconds: float = 3.0, trace: int = 0, patch: str = ""
             ) -> subprocess.CompletedProcess:
    """Run one cell of ``tree`` on the CPU; ``patch`` is Python run in
    the child before the harness starts (to plant a fault)."""
    script = os.path.join(tree, "rehearse_wrapper.py")
    with open(script, "w") as f:
        f.write(WRAPPER.replace("{patch}", patch))
    env = dict(os.environ, JAX_PLATFORMS="cpu", SCALANA_DETECT_F32="1",
               JAX_COMPILATION_CACHE_DIR=os.path.join(tree, ".jax_cache"))
    return subprocess.run(
        [sys.executable, script, tree, "--workload", workload,
         "--seed", str(seed), "--seconds", str(seconds),
         "--trace", str(trace)],
        cwd=tree, env=env, capture_output=True, text=True, timeout=900)


def result(proc: subprocess.CompletedProcess) -> dict:
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])
