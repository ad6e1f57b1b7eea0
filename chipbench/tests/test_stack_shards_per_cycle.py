"""The metric ``stack_shards_per_cycle`` over spans given directly: the
stat ``restacked`` of ``store.stack`` summed over the window's cycles,
over their number, and nothing for a program whose stacked reads carry
no such stat."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
import harness  # noqa: E402
import program_spans  # noqa: E402

STACKS = [  # (start s, end s, stats): two cycles, starting at 1.0 and 11.0
    (0.5, 0.6, {"shards": 2.0, "restacked": 2.0}),      # before the cycles
    (2.0, 2.1, {"shards": 2.0, "restacked": 2.0}),      # a full first read
    (3.0, 3.1, {"shards": 2.0, "restacked": 0.0}),
    (12.0, 12.1, {"shards": 2.0, "restacked": 1.0}),
    (13.0, 13.1, {"shards": 2.0, "restacked": 0.0}),
]


def _spans(stacks):
    return program_spans.Spans(
        (0.0, 20.0), [1.0, 11.0],
        [(s, e, "store.stack", st) for s, e, st in stacks], None)


def _read(monkeypatch, spans):
    metric = harness.load_module(
        os.path.join(os.path.dirname(HERE), "metrics",
                     "stack_shards_per_cycle.py"), "metric_stack_shards")
    monkeypatch.setattr(metric, "load", lambda raw: spans)
    return metric.read({"trace": None, "cycles": 2})


@pytest.mark.parametrize("stacks, expected", [
    (STACKS, 1.5),
    ([(s, e, {k: v for k, v in st.items() if k != "restacked"})
      for s, e, st in STACKS], None),
    ([], None),
], ids=["restacked", "stat_absent", "no_stacked_reads"])
def test_stack_shards_per_cycle(monkeypatch, stacks, expected):
    """Counted per cycle where the spans carry ``restacked``; None where
    they do not (a program that stacks every block on every read)."""
    assert _read(monkeypatch, _spans(stacks)) == expected


def test_untraced_run_reads_nothing(monkeypatch):
    assert _read(monkeypatch, None) is None
    no_cycles = program_spans.Spans((0.0, 20.0), [], [], None)
    assert _read(monkeypatch, no_cycles) is None
