"""chip_smoke.py's phases at smoke size on the CPU.

The script itself needs a TPU; these tests import its phase functions
and run them on a reduced mamba2 config and a small fleet, with the
fused detection kernels steered into Pallas interpret mode in float32 —
the kernel code and precision the chip runs — so a broken phase shows
up here before a chip run."""
import importlib.util
import os

import pytest

jax = pytest.importorskip("jax")

from repro.configs import get_smoke
from repro.kernels.detect_fused import ops

_PATH = os.path.join(os.path.dirname(__file__), "..", "chip_smoke.py")


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_train_then_diagnose_phases_pass_on_cpu(chip_smoke, monkeypatch):
    monkeypatch.setattr(ops, "kernel_mode", lambda interpret=None:
                        "interpret")
    monkeypatch.setenv("SCALANA_DETECT_F32", "1")
    tr, checks, facts = chip_smoke.train_phase(
        get_smoke("mamba2-130m"), seq=32, batch=2, steps=3, sample_every=3)
    assert all(ok for _, ok in checks), checks
    assert facts["batch"] == 2 and len(facts["sampled_step_s"]) == 1
    checks, facts, report = chip_smoke.diagnose_phase(
        tr.profiler, 4.0 * facts["params"], n_procs=64, hosts=4,
        scales=(16, 32, 64), straggler=37)
    assert all(ok for _, ok in checks), checks
    assert facts["kernel_mode"] == "interpret"
    assert facts["detect_dtype"] == "float32"
    assert facts["launches_warm"] == {"non_scalable_live": 1, "abnormal": 1}
    assert "p37" in report                      # the straggler is named


def test_refuses_a_host_without_tpu(chip_smoke, capsys):
    assert chip_smoke.main([]) != 0
    captured = capsys.readouterr()
    assert "'cpu'" in captured.err
    assert '"ok"' not in captured.out
