"""The comparison that decides ``correct`` fails what it must, at smoke
size on the CPU: the control (the plain reference one precision below
the configuration's, put in the program's place), and each fault a cell
can have, planted in the timed path underneath an otherwise normal run.
One chip has no exchange between chips to leave out.  See
``rehearse.py``; run by hand."""
import pytest

from rehearse import result, run_cell, smoke_tree

PROFILE = "mamba2-130m.profile"
DIAGNOSE = ["mamba2-130m.diagnose_8k"]

# the reference with float8 matrix-product operands stands in for the
# program's set-up steps
PROFILE_CONTROL = """
import jax.numpy as jnp
drv = harness.load_module(os.path.join(root, "chipbench", "drivers",
                                       "profile.py"), "driver_profile")
_setup = drv.Driver.setup
def setup(self):
    _setup(self)
    losses, g, p = self.reference_run(matmul_dtype=jnp.float8_e4m3fn)
    self.setup_losses, self.params_set = losses, p
    self.mu1 = jax.tree.map(lambda x: (1 - self.opt["b1"]) * x, g)
drv.Driver.setup = setup
"""

# the reference in bfloat16 stands in for the program's answers
DIAGNOSE_CONTROL = """
import jax.numpy as jnp
drv = harness.load_module(os.path.join(root, "chipbench", "drivers",
                                       "diagnose.py"), "driver_diagnose")
_verify = drv.Driver.verify
def verify(self):
    picks = self.sample()
    control = self.reference_answers(picks, dtype=jnp.bfloat16)
    for c, (ns, ab) in control.items():
        self.results[c] = (ns, ab, self.results[c][2])
    return _verify(self)
drv.Driver.verify = verify
"""

PROFILE_FAULTS = {
    # the step hands back the state it was given
    "state_unchanged": """
from repro.core import profiler
_step = profiler.GraphProfiler.step
profiler.GraphProfiler.step = lambda self, *a: (a[0], _step(self, *a)[1])
""",
    # half of each batch left out, the mean taken over the rest
    "half_batch": """
from repro.models import ssm_lm
_loss = ssm_lm.train_loss
def train_loss(cfg, params, batch):
    t = batch["tokens"]
    return _loss(cfg, params, {"tokens": t[: t.shape[0] // 2]})
ssm_lm.train_loss = train_loss
""",
    # the loss altered where it is produced
    "answer_altered": """
from repro.models import ssm_lm
_loss = ssm_lm.train_loss
def train_loss(cfg, params, batch):
    loss, m = _loss(cfg, params, batch)
    return loss * 1.01, dict(m, loss=loss * 1.01)
ssm_lm.train_loss = train_loss
""",
}

DIAGNOSE_FAULTS = {
    # the device buffers keep their first upload: new rows never reach
    # the kernels
    "state_unchanged": """
from repro.core.shard import DeviceShardView
_refresh = DeviceShardView.refresh
def refresh(self, n_vertices=None, dtype=None):
    if self._time is None:
        return _refresh(self, n_vertices, dtype)
    return 0
DeviceShardView.refresh = refresh
""",
    # half of the processes left out of the abnormal detector's median
    "half_batch": """
import numpy as np
from repro.core import detect
_ab = detect.detect_abnormal
def detect_abnormal(ppg, **kw):
    mask = np.zeros(ppg.n_procs, bool)
    mask[: ppg.n_procs // 2] = True
    return _ab(ppg, proc_mask=mask, **kw)
import repro.core as core
core.detect_abnormal = detect_abnormal
""",
    # the typical time altered where it is produced
    "answer_altered": """
from repro.core import detect
import repro.core as core
_ab = detect.detect_abnormal
def detect_abnormal(ppg, **kw):
    out = _ab(ppg, **kw)
    for a in out:
        a.typical *= 1.01
    return out
core.detect_abnormal = detect_abnormal
""",
}


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return smoke_tree(str(tmp_path_factory.mktemp("checkout")))


def _failed(tree, cell, patch):
    out = result(run_cell(tree, cell, patch=patch))
    assert out["correct"] is False, out["compared"]
    return {k for k, v in out["compared"].items()
            if not v["value"] <= v["limit"]}


def test_profile_control_fails(tree):
    assert _failed(tree, PROFILE, PROFILE_CONTROL)


@pytest.mark.parametrize("cell", DIAGNOSE)
def test_diagnose_control_fails(tree, cell):
    assert _failed(tree, cell, DIAGNOSE_CONTROL)


@pytest.mark.parametrize("fault", sorted(PROFILE_FAULTS))
def test_profile_fault_fails(tree, fault):
    assert _failed(tree, PROFILE, PROFILE_FAULTS[fault])


@pytest.mark.parametrize("fault", sorted(DIAGNOSE_FAULTS))
@pytest.mark.parametrize("cell", DIAGNOSE)
def test_diagnose_fault_fails(tree, cell, fault):
    assert _failed(tree, cell, DIAGNOSE_FAULTS[fault])
