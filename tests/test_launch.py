"""Launch layer: cell assembly, lowering, dry-run record structure.

Uses a 1x1 ("data","model") mesh so the full sharding/lowering path runs
on the single CPU device (the 512-device production meshes are exercised
by python -m repro.launch.dryrun, which owns the XLA_FLAGS override)."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from conftest import smoke_bundle
from repro.configs import SHAPES, get_smoke
from repro.configs.base import ShapeConfig
from repro.launch.mesh import make_host_mesh
from repro.launch.shardings import (abstract_train_state, build_cell,
                                    rules_for_shape, train_state_shardings)
from repro.core.hlo_walk import analyze_hlo
from repro.distributed import axes as ax


def _tiny_shapes():
    return {
        "train": ShapeConfig("t", 32, 2, "train"),
        "prefill": ShapeConfig("p", 32, 2, "prefill"),
        "decode": ShapeConfig("d", 32, 2, "decode"),
    }


@pytest.mark.parametrize("arch", ["tinyllama-1.1b", "mamba2-130m",
                                  "moonshot-v1-16b-a3b"])
@pytest.mark.parametrize("kind", ["train", "prefill", "decode"])
def test_cell_lowers_and_compiles(arch, kind, monkeypatch):
    """build_cell -> lower -> compile for every cell kind at smoke scale."""
    import repro.configs as configs
    cfg = get_smoke(arch)
    shape = _tiny_shapes()[kind]
    monkeypatch.setitem(SHAPES, shape.name, shape)
    mesh = make_host_mesh()
    cell = build_cell(arch, shape.name, mesh, cfg=cfg, donate=False)
    compiled = cell.lower().compile()
    assert compiled.cost_analysis().get("flops", 0) > 0
    cost = analyze_hlo(compiled.as_text())
    assert cost.dot_flops > 0


def test_cell_options_seq_shard_lowers(monkeypatch):
    cfg = get_smoke("tinyllama-1.1b")
    shape = _tiny_shapes()["train"]
    monkeypatch.setitem(SHAPES, shape.name, shape)
    mesh = make_host_mesh()
    cell = build_cell("tinyllama-1.1b", shape.name, mesh, cfg=cfg,
                      donate=False, options={"seq_shard": True})
    assert cell.rules["res_seq"] == "model"
    cell.lower().compile()


def test_abstract_state_matches_real_state():
    cfg, model, params = smoke_bundle("tinyllama-1.1b")
    abs_state = abstract_train_state(model)
    flat_abs = jax.tree.leaves(abs_state.params)
    flat_real = jax.tree.leaves(params)
    assert len(flat_abs) == len(flat_real)
    for a, r in zip(flat_abs, flat_real):
        assert a.shape == r.shape


def test_state_shardings_tree_congruent():
    cfg, model, _ = smoke_bundle("tinyllama-1.1b")
    mesh = make_host_mesh()
    with ax.use_rules(mesh):
        sh = train_state_shardings(model, mesh)
        st = abstract_train_state(model)
    assert (len(jax.tree.leaves(sh.opt.mu))
            == len(jax.tree.leaves(st.opt.mu)))


def test_shape_rules_are_pure():
    """rules_for_shape never mutates DEFAULT_RULES."""
    before = dict(ax.DEFAULT_RULES)
    mesh = make_host_mesh()
    for s in SHAPES.values():
        rules_for_shape(s, get_smoke("tinyllama-1.1b"), mesh)
    assert ax.DEFAULT_RULES == before


def test_scaling_profile_worker_refuses_a_wrong_device_count():
    """Job scales are emulated with forced CPU device counts; a worker
    that sees another count (an accelerator host) must fail, not write
    its profile under the scale it was asked for."""
    import argparse

    from repro.launch.scaling_profile import worker
    args = argparse.Namespace(scale=jax.device_count() + 1,
                              arch="mamba2-130m")
    with pytest.raises(SystemExit, match="asked for scale"):
        worker(args)


def test_compile_cache_env_wins_else_fixed_repo_dir(monkeypatch, tmp_path):
    from repro.launch import compile_cache
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compile_cache.use_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        fixed = str(compile_cache.REPO_CACHE_DIR)
        assert compile_cache.use_compile_cache() == fixed
        assert jax.config.jax_compilation_cache_dir == fixed
        assert compile_cache.REPO_CACHE_DIR.name == ".jax_cache"
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
