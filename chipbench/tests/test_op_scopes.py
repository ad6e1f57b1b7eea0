"""Device time by named scope, read from a small recorded trace: one TPU
plane whose ``XLA Ops`` carry their name stacks in the stat ``tf_op``,
and a host plane with the benchmark's ``window`` and the
program's ``trainer.step`` / ``profiler.compiled_step`` spans, written as
an XSpace text proto (times in picoseconds from the line's start).  Then
the abnormal kernel's time per cycle from a trace summary."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
pytest.importorskip("jax")
import harness  # noqa: E402
import op_scopes  # noqa: E402
import program_spans  # noqa: E402

MS = 1_000_000_000                    # picoseconds in a millisecond
SCOPED = "jit(train_step)/jvp()/while/body/cond/branch_1_fun/" \
    "hybrid.shared_block/dot_general"
BACKWARD = "jit(train_step)/transpose(jvp())/while/body/cond/" \
    "branch_1_fun/hybrid.shared_block/mul"
OTHER = "jit(train_step)/jvp()/while/body/ssd/exp"
NEAR = "jit(train_step)/hybrid.shared_block_norm/add"     # not the scope

HOST = [  # (name, start ms, end ms)
    ("window", 0, 30),
    ("scalana.trainer.step", 1, 9),
    ("scalana.profiler.sampled_step", 1.5, 8.5),
    ("scalana.trainer.step", 10, 19),
    ("scalana.profiler.compiled_step", 10.5, 18.5),
    ("scalana.trainer.step", 20, 29),
    ("scalana.profiler.compiled_step", 20.5, 28.5),
]
OPS = [  # (start ms, end ms, name stack)
    (2.0, 3.0, SCOPED),                   # in a sampled step: not read
    (11.0, 12.0, SCOPED), (12.0, 12.5, BACKWARD), (13.0, 14.0, OTHER),
    (14.0, 15.0, NEAR),
    (21.0, 23.0, SCOPED), (23.0, 24.0, BACKWARD),
]


def _event(meta: int, start_ms: float, end_ms: float, stats="") -> str:
    return (f"events {{ metadata_id: {meta} offset_ps: "
            f"{round(start_ms * MS)} duration_ps: "
            f"{round((end_ms - start_ms) * MS)}{stats} }}")


def _trace() -> str:
    """Name stacks as a trace keeps them: a stat on each op's event
    metadata, as a string or as a reference to a stat metadata that holds
    it; one op (``NEAR``) carries it on the event itself."""
    names = sorted({n for n, _, _ in HOST})
    meta = {n: i + 1 for i, n in enumerate(names)}
    host = "\n    ".join(_event(meta[n], s, e) for n, s, e in HOST)
    host_meta = "\n  ".join(
        f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
        for n, i in meta.items())
    stacks = [SCOPED, BACKWARD, OTHER, NEAR]
    op_id = {stack: i + 1 for i, stack in enumerate(stacks)}
    ops = "\n    ".join(
        _event(op_id[stack], s, e,
               f' stats {{ metadata_id: 1 str_value: "{stack}" }}'
               if stack == NEAR else "")
        for s, e, stack in OPS)
    op_meta = "\n  ".join(
        f'event_metadata {{ key: {i} value {{ id: {i} name: "%fusion.{i} = '
        f'f32[8]{{0}} fusion(f32[8]{{0}} %x)" '
        + ("" if stack == NEAR else
           f'stats {{ metadata_id: 1 ref_value: 9 }}' if stack == BACKWARD
           else f'stats {{ metadata_id: 1 str_value: "{stack}" }}')
        + " } }" for stack, i in op_id.items())
    return f"""
planes {{
  id: 1 name: "/device:TPU:0"
  lines {{
    id: 1 name: "XLA Ops" timestamp_ns: 1000000
    {ops}
  }}
  {op_meta}
  stat_metadata {{ key: 1 value {{ id: 1 name: "tf_op" }} }}
  stat_metadata {{ key: 9 value {{ id: 9 name: "{BACKWARD}" }} }}
}}
planes {{
  id: 2 name: "/host:CPU"
  lines {{
    id: 3 name: "python" timestamp_ns: 1000000
    {host}
  }}
  {host_meta}
}}
"""


@pytest.fixture
def traced(tmp_path, monkeypatch):
    from jax.profiler import ProfileData
    run = tmp_path / "plugins" / "profile" / "run1"
    run.mkdir(parents=True)
    (run / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(_trace()))
    for mod in (program_spans, op_scopes):
        monkeypatch.setattr(mod, "TRACE_DIR", str(tmp_path))
        monkeypatch.setattr(mod, "_cache", {})
    return {"trace": {"busy_s": 8.5e-3}}


def _metric(name):
    return harness.load_module(os.path.join(os.path.dirname(HERE), "metrics",
                                            name + ".py"), "metric_" + name)


def test_name_stacks_from_the_ops_stat(traced, capsys):
    ops = op_scopes.load(traced)
    assert ops.stat == "tf_op"
    assert [stack for _, _, stack in ops.ops] == [o[2] for o in OPS]
    assert ops.seconds_in("hybrid.shared_block", 0.0, 1.0) == \
        pytest.approx(5.5e-3)
    assert "tf_op" in capsys.readouterr().err


def test_a_scope_is_a_whole_component_of_the_stack():
    assert op_scopes.in_scope(SCOPED, "hybrid.shared_block")
    assert not op_scopes.in_scope(NEAR, "hybrid.shared_block")
    assert not op_scopes.in_scope(OTHER, "hybrid.shared_block")


def test_shared_block_seconds_per_compiled_step(traced):
    # compiled steps read 1.5 ms and 3.0 ms; the sampled step is left out
    assert _metric("shared_block_s").read(traced) == pytest.approx(2.25e-3)


def test_nothing_read_untraced_on_a_cpu_or_without_the_scope(
        traced, monkeypatch):
    assert op_scopes.load({}) is None
    assert op_scopes.load({"trace": None}) is None
    assert _metric("shared_block_s").read({"trace": None}) is None
    monkeypatch.setattr(_metric("shared_block_s"), "SCOPE", "absent.scope")
    assert _metric("shared_block_s").read(traced) is None


def test_abnormal_kernel_ms_per_cycle():
    read = _metric("abnormal_kernel_ms").read
    trace = {"device_ops": [["jit_ab_fused_kernel/%detect_abnormal.1", 0.3],
                            ["jit_ns_fused_kernel/%detect_non_scalable", 0.2],
                            ["jit_scatter_rows/%fusion", 0.1]]}
    assert read({"trace": trace, "cycles": 150}) == pytest.approx(2.0)
    assert read({"trace": None, "cycles": 150}) is None
    assert read({"trace": {"device_ops": []}, "cycles": 150}) is None
