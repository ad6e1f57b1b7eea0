"""HLO parsing (collectives, trip-count walker) + sharding rules."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.hlo import (CollectiveOp, collective_bytes_total,
                            parse_collectives, shape_bytes)
from repro.core.hlo_walk import analyze_hlo, _split_computations
from repro.distributed import axes as ax


# ---------------------------------------------------------------------------
# hlo text parsing
# ---------------------------------------------------------------------------

def test_shape_bytes():
    assert shape_bytes("f32[4,8]") == 128
    assert shape_bytes("bf16[10]{0}") == 20
    assert shape_bytes("(f32[2,2], s32[3])") == 28
    assert shape_bytes("pred[16]") == 16
    assert shape_bytes("f32[]") == 4


SAMPLE = """
  %all-gather = f32[32,32]{0,1} all-gather(%copy), channel_id=1, replica_groups=[2,4]<=[8], dimensions={1}, use_global_device_ids=true
  %all-reduce.1 = f32[128]{0} all-reduce(%x), channel_id=2, replica_groups={{0,1,2,3},{4,5,6,7}}, to_apply=%add
  %collective-permute.2 = bf16[64]{0} collective-permute(%y), source_target_pairs={{0,1},{1,2},{2,3}}
  %reduce-scatter.3 = f32[16]{0} reduce-scatter(%z), channel_id=4, replica_groups=[1,8]<=[8], dimensions={0}, to_apply=%add
"""


def test_parse_collectives_kinds_and_bytes():
    ops = parse_collectives(SAMPLE)
    kinds = [o.kind for o in ops]
    assert kinds == ["all-gather", "all-reduce", "collective-permute",
                     "reduce-scatter"]
    assert ops[0].bytes == 32 * 32 * 4
    assert ops[0].replica_groups == [[0, 1, 2, 3], [4, 5, 6, 7]]
    assert ops[1].replica_groups == [[0, 1, 2, 3], [4, 5, 6, 7]]
    assert ops[2].p2p_pairs == [(0, 1), (1, 2), (2, 3)]
    totals = collective_bytes_total(SAMPLE)
    assert totals["total"] == (32 * 32 * 4 + 128 * 4 + 64 * 2 + 16 * 4)


def test_iota_replica_groups_with_transpose():
    line = ("  %ar = f32[8]{0} all-reduce(%x), "
            "replica_groups=[4,2]<=[2,4]T(1,0), to_apply=%add")
    ops = parse_collectives(line)
    arr = np.arange(8).reshape(2, 4).transpose(1, 0).reshape(4, 2)
    assert ops[0].replica_groups == arr.tolist()


def test_analyze_hlo_trip_count_exact():
    """Walker multiplies while bodies by known_trip_count (vs raw XLA)."""
    L, D = 6, 16

    def f(ws, x):
        def body(h, w):
            return jnp.tanh(h @ w), None
        h, _ = jax.lax.scan(body, x, ws)
        return jnp.sum(h)

    ws = jnp.ones((L, D, D))
    x = jnp.ones((D, D))
    compiled = jax.jit(f).lower(ws, x).compile()
    cost = analyze_hlo(compiled.as_text())
    analytic = L * 2 * D * D * D
    assert cost.dot_flops == pytest.approx(analytic, rel=0.05)


def test_split_computations_finds_entry():
    compiled = jax.jit(lambda x: jnp.sum(x * x)).lower(
        jnp.ones((8,))).compile()
    comps = _split_computations(compiled.as_text())
    assert any(e for _, e in comps.values())


# ---------------------------------------------------------------------------
# logical sharding rules
# ---------------------------------------------------------------------------

def _mesh22():
    from repro.launch.mesh import _mesh
    return _mesh((1, 1), ("data", "model"))


def test_spec_for_divisibility_opt_out():
    mesh = _mesh22()
    rules = {"vocab": "model", "embed": "data"}
    # divisible: sharded;  mesh axes are size 1 so everything divides —
    # use resolve_axis contract directly
    assert ax.resolve_axis("vocab", 100, mesh, rules) == "model"
    # non-divisible opt-out needs axis >1: simulate via rule product check
    spec = ax.spec_for(("vocab", "embed"), (100, 64), mesh, rules)
    assert spec == jax.sharding.PartitionSpec("model", "data")


def test_spec_for_no_double_axis_use():
    mesh = _mesh22()
    rules = {"a": "model", "b": "model"}
    spec = ax.spec_for(("a", "b"), (8, 8), mesh, rules)
    # second dim must not reuse 'model'
    assert spec[0] == "model"
    assert len(spec) < 2 or spec[1] is None


def test_logical_constraint_noop_without_mesh():
    x = jnp.ones((4, 4))
    y = ax.logical_constraint(x, "batch", "embed")
    np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_rules_for_shape_long_context():
    from repro.launch.shardings import rules_for_shape
    from repro.configs import SHAPES
    r_short = rules_for_shape(SHAPES["decode_32k"])
    r_long = rules_for_shape(SHAPES["long_500k"])
    assert r_short["kv_seq"] is None
    assert r_long["kv_seq"] == ("pod", "data")


def test_shardings_from_axes_cache_tree():
    from repro.launch.shardings import shardings_from_axes
    from conftest import smoke_bundle
    cfg, model, _ = smoke_bundle("tinyllama-1.1b")
    mesh = _mesh22()
    import dataclasses
    from repro.configs.base import ShapeConfig
    shape = ShapeConfig("t", 16, 2, "decode")
    cache_abs = model.cache_specs(2, 16)
    axes_tree = model.input_logical_axes(shape)["cache"]
    sh = shardings_from_axes(axes_tree, cache_abs, mesh)
    flat_sh = jax.tree.leaves(sh)
    flat_abs = jax.tree.leaves(cache_abs)
    assert len(flat_sh) == len(flat_abs)
    for s in flat_sh:
        assert isinstance(s, jax.sharding.NamedSharding)
