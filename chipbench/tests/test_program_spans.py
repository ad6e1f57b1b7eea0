"""The program's spans read from a small recorded trace: one TPU plane
with its ``XLA Ops`` line and a host plane with the benchmark's
``window`` and ``apply`` spans and the program's nested ``scalana.*``
spans with stats, written as an XSpace text proto (times in picoseconds
from the line's start), over two diagnosis cycles.  Then the trainer
grouping over spans given directly."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
pytest.importorskip("jax")
import harness  # noqa: E402
import program_spans  # noqa: E402

MS = 1_000_000_000                    # picoseconds in a millisecond


def _event(meta: int, start_ms: float, end_ms: float, **stats) -> str:
    keys = {"blocks": 1, "dirty_blocks": 2, "operands": 3, "rows": 4,
            "shards": 5}
    body = " ".join(f"stats {{ metadata_id: {keys[k]} int64_value: {v} }}"
                    for k, v in stats.items())
    return (f"events {{ metadata_id: {meta} offset_ps: "
            f"{round(start_ms * MS)} duration_ps: "
            f"{round((end_ms - start_ms) * MS)} {body} }}")


HOST = [  # (name, start ms, end ms, stats)
    ("window", 0, 20, {}),
    ("apply", 1.0, 1.5, {}),                        # cycle 1
    ("scalana.store.apply_rows", 1.1, 1.4, {"rows": 4}),
    ("scalana.detect.non_scalable", 2, 7, {}),
    ("scalana.feed.refresh", 2, 3, {"blocks": 4, "dirty_blocks": 1}),
    ("scalana.detect.concat", 3, 4, {"operands": 2}),
    ("scalana.detect.readback", 5, 6, {}),
    ("apply", 11.0, 11.5, {}),                      # cycle 2
    ("scalana.store.apply_rows", 11.1, 11.4, {"rows": 4}),
    ("scalana.detect.abnormal", 12, 16, {}),
    ("scalana.feed.refresh", 12, 14, {"blocks": 4, "dirty_blocks": 1}),
    ("scalana.detect.readback", 15, 16, {}),
    ("scalana.backtrack", 16.5, 19, {}),
    ("scalana.store.stack", 17, 18, {"shards": 2}),
    ("scalana.not_a_program_span", 8, 9, {}),
    ("PjitFunction", 3.6, 3.7, {}),
]
OPS = [(3.5, 4.5), (5.2, 5.6), (13.0, 13.5)]        # device busy, ms


def _trace() -> str:
    names = sorted({n for n, _, _, _ in HOST})
    meta = {n: i + 1 for i, n in enumerate(names)}
    host_events = "\n    ".join(_event(meta[n], s, e, **st)
                                for n, s, e, st in HOST)
    host_meta = "\n  ".join(
        f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
        for n, i in meta.items())
    stat_meta = "\n  ".join(
        f'stat_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
        for n, i in (("blocks", 1), ("dirty_blocks", 2), ("operands", 3),
                     ("rows", 4), ("shards", 5)))
    ops = "\n    ".join(_event(1, s, e) for s, e in OPS)
    return f"""
planes {{
  id: 1 name: "/device:TPU:0"
  lines {{
    id: 1 name: "XLA Ops" timestamp_ns: 1000000
    {ops}
  }}
  event_metadata {{ key: 1 value {{ id: 1 name: "%fusion.1 = f32[8]{{0}} fusion(f32[8]{{0}} %x)" }} }}
}}
planes {{
  id: 2 name: "/host:CPU"
  lines {{
    id: 3 name: "python" timestamp_ns: 1000000
    {host_events}
  }}
  {host_meta}
  {stat_meta}
}}
"""


@pytest.fixture
def traced(tmp_path, monkeypatch):
    from jax.profiler import ProfileData
    run = tmp_path / "plugins" / "profile" / "run1"
    run.mkdir(parents=True)
    (run / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(_trace()))
    monkeypatch.setattr(program_spans, "TRACE_DIR", str(tmp_path))
    monkeypatch.setattr(program_spans, "_cache", {})
    return {"trace": {"busy_s": 1.9e-3}, "cycles": 2}


def _metric(name):
    return harness.load_module(os.path.join(os.path.dirname(HERE), "metrics",
                                            name + ".py"), "metric_" + name)


def test_per_cycle_sums(traced):
    spans = program_spans.load(traced)
    assert spans.n_cycles == 2
    assert spans.per_cycle_s("feed.refresh") == pytest.approx([1e-3, 2e-3])
    assert spans.per_cycle_s("detect.concat") == pytest.approx([1e-3, 0.0])
    assert _metric("refresh_ms").read(traced) == pytest.approx(1.5)
    assert _metric("concat_ms").read(traced) == pytest.approx(0.5)
    assert _metric("readback_ms").read(traced) == pytest.approx(1.0)
    assert _metric("store_stack_ms").read(traced) == pytest.approx(0.5)
    assert _metric("refresh_blocks_per_cycle").read(traced) == 4.0
    assert {n for _, _, n, _ in spans.spans} <= set(
        program_spans.program_names())
    assert spans.named("feed.refresh")[0][3] == {"blocks": 4.0,
                                                  "dirty_blocks": 1.0}


def test_idle_goes_to_the_innermost_open_program_span(traced, capsys):
    idle = program_spans.load(traced).idle_by_span()
    ms = {k: v * 1e3 for k, v in idle.items()}
    assert ms == pytest.approx({
        "store.apply_rows": 0.6, "feed.refresh": 2.5, "detect.concat": 0.5,
        "detect.non_scalable": 1.5, "detect.readback": 1.6,
        "detect.abnormal": 1.0, "backtrack": 1.5, "store.stack": 1.0,
        "none": 7.9}, abs=1e-6)
    assert sum(ms.values()) == pytest.approx(20 - 1.9)
    assert "feed.refresh" in capsys.readouterr().err    # the table


def test_untraced_run_and_a_program_without_spans_read_nothing(
        traced, monkeypatch):
    assert program_spans.load({"cycles": 2}) is None
    # a run whose trace has no device plane keeps the counts, not times
    cpu = {"trace": None, "cycles": 2}
    assert _metric("refresh_blocks_per_cycle").read(cpu) == 4.0
    assert _metric("refresh_ms").read(cpu) is None
    monkeypatch.setattr(program_spans, "_cache", {})
    monkeypatch.setattr(program_spans, "program_names", lambda: None)
    assert program_spans.load(traced) is None
    assert _metric("refresh_blocks_per_cycle").read(traced) is None


def test_trainer_steps_sampled_and_compiled(monkeypatch):
    spans = program_spans.Spans(
        (0.0, 10.0), [],
        [(0.0, 4.0, "trainer.step", {"step": 0.0}),
         (0.0, 0.5, "trainer.batch", {}),
         (0.5, 3.5, "profiler.sampled_step", {"eqns": 2.0}),
         (1.0, 1.5, "profiler.fence", {"vid": 1.0}),
         (2.0, 2.5, "profiler.fence", {"vid": 2.0}),
         (5.0, 8.0, "trainer.step", {"step": 1.0}),
         (5.5, 7.5, "profiler.compiled_step", {})],
        [(1.0, 1.5), (2.0, 2.5), (6.0, 7.0)])
    monkeypatch.setattr(program_spans, "load", lambda raw: spans)
    raw = {"trace": {}}
    assert _metric("sampled_dispatch_s").read(raw) == pytest.approx(2.0)
    assert _metric("idle_per_sampled_step_s").read(raw) == pytest.approx(3.0)
    assert _metric("idle_per_compiled_step_s").read(raw) == \
        pytest.approx(2.0)
    idle = spans.idle_by_span()
    assert idle == pytest.approx({"trainer.batch": 0.5,
                                  "profiler.sampled_step": 2.0,
                                  "trainer.step": 1.5,
                                  "profiler.compiled_step": 1.0,
                                  "none": 3.0})
