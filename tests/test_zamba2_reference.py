"""zamba2-2.7b's program against its plain reference, at smoke size on the CPU.

The reference (``chipbench/configs/zamba2-2.7b/reference.py``) is loaded
by path; it imports nothing of the program.  On seeded random weights
(the reference's own initialisation, Mamba-2's published A and dt draws)
the program's loss, per-leaf gradients and two AdamW steps through
``Trainer``'s train step must match it, and the reference with bfloat16
matrix-product operands must not.
"""
import dataclasses
import importlib.util
import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.configs import get_smoke  # noqa: E402
from repro.configs.base import RunConfig, ShapeConfig  # noqa: E402
from repro.training import Trainer  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OPT = dict(learning_rate=3e-4, warmup_steps=1, total_steps=1000,
           final_lr_frac=0.1, weight_decay=0.1, b1=0.9, b2=0.95, eps=1e-8,
           max_grad_norm=1.0)
B, S = 2, 32

# Both sides compute in float32; they differ in summation order (SSD
# chunk 8 against the reference's whole-sequence chunk, attention in one
# block against blocks of queries), about 1e-7 relative per operation.
# Measured: loss 9e-8, worst gradient leaf 9e-6, worst update leaf 1e-3;
# the bfloat16 control reads 3e-5, 2e-2 (its best leaf) and 1.9.
LOSS_TOL = 1e-6        # relative gap of the mean loss
GRAD_TOL = 1e-4        # per leaf: max |g - g_ref| over max |g_ref|
# Adam divides each gradient by its own root mean square, so a leaf whose
# gradient is near round-off moves by round-off: per leaf, the parameter
# change after two steps, max gap over max reference change
UPDATE_TOL = 2e-2


@pytest.fixture(scope="module")
def ref():
    spec = importlib.util.spec_from_file_location(
        "zamba2_reference", os.path.join(REPO, "chipbench", "configs",
                                         "zamba2-2.7b", "reference.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def setup(ref):
    cfg = get_smoke("zamba2-2.7b")
    run = RunConfig(arch=cfg.name, scalana=False,
                    learning_rate=OPT["learning_rate"],
                    warmup_steps=OPT["warmup_steps"],
                    total_steps=OPT["total_steps"],
                    weight_decay=OPT["weight_decay"])
    tr = Trainer(run, arch_cfg=cfg, shape=ShapeConfig("ref", S, B, "train"))
    state = ref.make_state(jax.eval_shape(tr.init_state), 7)
    rng = np.random.default_rng(3)
    batches = [rng.integers(0, cfg.vocab_size, (B, S + 1), dtype=np.int32)
               for _ in range(2)]
    return cfg, dataclasses.asdict(cfg), tr, state, batches


def _ref_grads(ref, arch, params, batch, q=None):
    with jax.default_matmul_precision("highest"):
        def mean_loss(p):
            return sum(ref.row_loss_sum(arch, p, jnp.asarray(r), q)
                       for r in batch) / (B * S)
        return jax.value_and_grad(mean_loss)(params)


def _leaf_gaps(got, want):
    paths = jax.tree_util.tree_flatten_with_path(want)[0]
    out = {}
    for (path, w), g in zip(paths, jax.tree.leaves(got)):
        w, g = np.asarray(w, np.float64), np.asarray(g, np.float64)
        out[jax.tree_util.keystr(path)] = \
            np.abs(g - w).max() / max(np.abs(w).max(), 1e-30)
    return out


@pytest.fixture(scope="module")
def grads(ref, setup):
    cfg, arch, tr, state, batches = setup
    batch = {"tokens": jnp.asarray(batches[0])}
    loss, g = jax.value_and_grad(
        lambda p: tr.model.train_loss(p, batch)[0])(state.params)
    params0 = jax.device_get(state.params)
    loss_r, g_r = _ref_grads(ref, arch, params0, batches[0])
    loss_c, g_c = _ref_grads(ref, arch, params0, batches[0], jnp.bfloat16)
    return (float(loss), g), (float(loss_r), g_r), (float(loss_c), g_c)


def test_loss_and_gradients_match_the_reference(grads):
    (loss, g), (loss_r, g_r), _ = grads
    assert abs(loss / loss_r - 1.0) < LOSS_TOL, (loss, loss_r)
    gaps = _leaf_gaps(g, g_r)
    worst = max(gaps, key=gaps.get)
    assert gaps[worst] < GRAD_TOL, (worst, gaps[worst])


def test_bfloat16_control_fails_the_tolerances(grads):
    _, (loss_r, g_r), (loss_c, g_c) = grads
    gaps = _leaf_gaps(g_c, g_r)
    assert abs(loss_c / loss_r - 1.0) > LOSS_TOL
    assert min(gaps.values()) > GRAD_TOL, gaps


def test_adamw_steps_match_the_reference(ref, setup):
    cfg, arch, tr, state, batches = setup
    params0 = jax.device_get(state.params)
    step = jax.jit(tr.train_step_fn)
    losses = []
    for b in batches:
        state, metrics = step(state, {"tokens": jnp.asarray(b)})
        losses.append(float(metrics["loss"]))
    ref_losses, _, ref_params = ref.train_steps(arch, OPT, params0, batches)
    np.testing.assert_allclose(losses, ref_losses, rtol=LOSS_TOL)

    def moved(p):
        return jax.tree.map(lambda x, y: np.asarray(x, np.float64)
                            - np.asarray(y, np.float64), p, params0)

    gaps = _leaf_gaps(moved(jax.device_get(state.params)),
                      moved(ref_params))
    worst = max(gaps, key=gaps.get)
    assert gaps[worst] < UPDATE_TOL, (worst, gaps[worst])
    _, _, ctl_params = ref.train_steps(arch, OPT, params0, batches,
                                       matmul_dtype=jnp.bfloat16)
    ctl = _leaf_gaps(moved(ctl_params), moved(ref_params))
    assert max(ctl.values()) > UPDATE_TOL


def test_both_shared_blocks_and_every_adapter_get_gradient(setup, grads):
    cfg, *_ = setup
    (_, g), _, _ = grads
    assert cfg.n_shared_blocks == 2 and cfg.n_hybrid_layers == 2
    for group, n in (("shared", cfg.n_shared_blocks),
                     ("hybrid", cfg.n_hybrid_layers)):
        for path, leaf in jax.tree_util.tree_flatten_with_path(g[group])[0]:
            leaf = np.asarray(leaf)
            assert leaf.shape[0] == n
            for i in range(n):
                assert np.abs(leaf[i]).max() > 0.0, (group, path, i)


def test_layer_scan_branches_over_no_block_and_each_shared_block(setup):
    """The PSG's view: one switch inside the layer scan, with an arm for
    no block and one for each shared block."""
    cfg, _, tr, state, batches = setup
    jaxpr = jax.make_jaxpr(lambda p, t: tr.model.train_loss(
        p, {"tokens": t})[0])(state.params, jnp.asarray(batches[0]))

    def conds(jx):
        for eqn in jx.eqns:
            if eqn.primitive.name == "cond":
                yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from conds(sub)

    arms = {len(e.params["branches"]) for e in conds(jaxpr.jaxpr)}
    assert arms == {cfg.n_shared_blocks + 1}
