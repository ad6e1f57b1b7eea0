"""The trace reduction on a small recorded trace: one TPU plane with its
``XLA Ops`` line and a host plane with the benchmark's spans, written as
an XSpace text proto (times in picoseconds from the line's start)."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
pytest.importorskip("jax")
import trace_reduce  # noqa: E402

# window 0-10 ms; device ops 1-3 ms (fusion.1), 5-6 ms (custom-call.2)
# and 5.5-6.5 ms (fusion.1, overlapping); host spans: detect 4-7 ms,
# backtrack 7-9 ms; a line of another plane that is not a device
TRACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines {
    id: 1 name: "XLA Ops" timestamp_ns: 1000000
    events { metadata_id: 1 offset_ps: 1000000000 duration_ps: 2000000000 }
    events { metadata_id: 2 offset_ps: 5000000000 duration_ps: 1000000000 }
    events { metadata_id: 1 offset_ps: 5500000000 duration_ps: 1000000000 }
  }
  lines {
    id: 2 name: "XLA Modules" timestamp_ns: 1000000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 9000000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %x)" } }
  event_metadata { key: 2 value { id: 2 name: "%custom-call.2 = f32[8]{0} custom-call(f32[8]{0} %y)" } }
  event_metadata { key: 3 value { id: 3 name: "jit_step(123)" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines {
    id: 3 name: "python" timestamp_ns: 1000000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000000 }
    events { metadata_id: 2 offset_ps: 4000000000 duration_ps: 3000000000 }
    events { metadata_id: 3 offset_ps: 7000000000 duration_ps: 2000000000 }
    events { metadata_id: 4 offset_ps: 100000000 duration_ps: 100000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "window" } }
  event_metadata { key: 2 value { id: 2 name: "detect" } }
  event_metadata { key: 3 value { id: 3 name: "backtrack" } }
  event_metadata { key: 4 value { id: 4 name: "PjitFunction" } }
}
"""


@pytest.fixture(scope="module")
def summary(tmp_path_factory):
    from jax.profiler import ProfileData
    d = tmp_path_factory.mktemp("trace")
    run = d / "plugins" / "profile" / "run1"
    run.mkdir(parents=True)
    xspace = ProfileData.from_text_proto(TRACE)
    # ProfileData reads files; write the parsed planes back as a file by
    # re-serialising through the text proto's binary form
    path = run / "host.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(TRACE))
    assert xspace.find_plane_with_name("/device:TPU:0") is not None
    return trace_reduce.reduce_trace(str(d), ("window", "detect",
                                              "backtrack"))


def test_busy_is_the_union_of_op_intervals(summary):
    assert summary["window_s"] == pytest.approx(10e-3)
    assert summary["busy_s"] == pytest.approx(2e-3 + 1.5e-3)


def test_device_ops_by_printed_name(summary):
    ops = dict(summary["device_ops"])
    assert ops == pytest.approx({"jit_step/%fusion.1": 3e-3,
                                 "jit_step/%custom-call.2": 1e-3})
    assert summary["device_ops"][0][0] == "jit_step/%fusion.1"


def test_idle_gaps_labelled_by_open_span(summary):
    gaps = dict(summary["idle_gaps"])
    # 0-1 and 3-4 ms: no span; 4-5 ms: detect; 6.5-7: detect;
    # 7-9: backtrack; 9-10: none
    assert gaps == pytest.approx({"none": 3e-3, "detect": 1.5e-3,
                                  "backtrack": 2e-3})


def test_device_time_sums_every_op(summary):
    assert summary["device_s"] == pytest.approx(4e-3)


def test_no_device_plane_reads_nothing():
    assert trace_reduce.reduce_events({}, [(0.0, 1.0, "window")]) is None
