"""The trace reduction on a hand-made trace with known answers, and on a
small trace recorded on a TPU v5e (one second of lone boolean queries
served from the 2 GiB posting index)."""

import gzip
import re
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import harness  # noqa: E402

trace = harness.trace_red
RECORDED = (Path(__file__).resolve().parent
            / "v5e_lone_boolean.xplane.pb.gz")

SEGMENT = "%segment_reduce.1 = (u32[1]) custom-call(s32[2])"
# how a Pallas call of the program's segment kernel is named in a trace
SEGMENT_CALL = re.compile(r"^%segment_reduce(\.\d+)? = .*custom-call\(")
TEXT = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 1500000 }
    events { metadata_id: 3 offset_ps: 5000000 duration_ps: 1000000 }
    events { metadata_id: 3 offset_ps: 30000000 duration_ps: 1000000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 4 offset_ps: 0 duration_ps: 19000000 } }
  event_metadata { key: 1 value { id: 1 name: "%s" } }
  event_metadata { key: 2 value { id: 2 name: "%%segment_reduce.7 = (u32[2]) custom-call(s32[3])" } }
  event_metadata { key: 3 value { id: 3 name: "%%fusion.3 = u32[8] fusion(u32[8])" } }
  event_metadata { key: 4 value { id: 4 name: "jit_step" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python3" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 20000000 }
    events { metadata_id: 2 offset_ps: 2500000 duration_ps: 4000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.step" } }
}
""".replace("%s", SEGMENT, 1).replace("%%", "%")


@pytest.fixture(scope="module")
def made():
    from jax.profiler import ProfileData
    return trace.reduce_data(ProfileData.from_text_proto(TEXT))


def test_window_busy_and_idle(made):
    # window = the bench.window span, 1000..21000 ns; ops overlap at
    # 1000..3500 and run at 6000..7000; the op at 31000 lies outside
    assert made.window_s == pytest.approx(20e-6)
    assert made.busy_s == pytest.approx(3.5e-6)
    assert made.idle_share() == pytest.approx(1 - 3.5 / 20)
    assert made.n_devices == 1


def test_ops_and_kernel_match(made):
    sec, calls = made.op_time(SEGMENT_CALL.match)
    assert calls == 2 and sec == pytest.approx(3.5e-6)
    fusion_s, n = made.op_time(lambda name: "fusion(" in name)
    assert n == 1 and fusion_s == pytest.approx(1e-6)


def test_gaps_are_labelled_by_the_open_span(made):
    assert made.gaps[0] == (pytest.approx(14e-6), "bench.window")
    assert made.gaps[1] == (pytest.approx(2.5e-6), "bench.step")
    b = made.breakdown()
    assert b["device_ops"][0] == ["segment_reduce", pytest.approx(3.5e-6)]
    assert b["idle_gaps"][0] == ["bench.window", pytest.approx(14e-6)]


def test_short_names():
    assert trace.short_name(SEGMENT) == "segment_reduce"
    assert trace.short_name("%copy-done = s32[4] copy-done(x)") == "copy-done"
    assert trace.short_name("%fusion = u32[8] fusion(u32)") == "fusion"


def test_no_device_plane_reads_nothing():
    from jax.profiler import ProfileData
    host_only = TEXT[TEXT.index("planes {\n  id: 2"):]
    red = trace.reduce_data(ProfileData.from_text_proto(host_only))
    assert red.n_devices == 0 and red.idle_share() is None
    assert red.op_time(lambda n: True) == (0.0, 0)


def test_recorded_v5e_trace():
    from jax.profiler import ProfileData
    red = trace.reduce_data(ProfileData.from_serialized_xspace(
        gzip.decompress(RECORDED.read_bytes())))
    assert red.n_devices == 1
    assert 0.5 < red.window_s < 2.0
    assert 0 < red.busy_s < red.window_s
    assert red.spans["bench.submit"] and red.spans["bench.step"]
    sec, calls = red.op_time(SEGMENT_CALL.match)
    assert calls > 0 and 0 < sec < red.busy_s
    names = [n for n, _ in red.breakdown()["device_ops"]]
    assert "segment_reduce" in names


# a tick whose idle device time falls inside the program's own spans:
# ops at 0..1 us and 9..10 us, the gap 1..9 us inside serve.revalidate
# (2..8 us), which sits in serve.tick inside bench.step
PROGRAM_SPANS = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 1000000 }
    events { metadata_id: 1 offset_ps: 9000000 duration_ps: 1000000 } }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = u32[8] fusion(u32[8])" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python3" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 500000 duration_ps: 9000000 }
    events { metadata_id: 3 offset_ps: 600000 duration_ps: 8800000 }
    events { metadata_id: 4 offset_ps: 700000 duration_ps: 8000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.step" } }
  event_metadata { key: 3 value { id: 3 name: "serve.tick" } }
  event_metadata { key: 4 value { id: 4 name: "serve.revalidate" } }
}
"""


def test_a_gap_is_labelled_by_the_innermost_program_span():
    from jax.profiler import ProfileData
    red = trace.reduce_data(ProfileData.from_text_proto(PROGRAM_SPANS))
    assert red.window_s == pytest.approx(10e-6)
    assert red.busy_s == pytest.approx(2e-6)
    assert red.gaps == [(pytest.approx(8e-6), "serve.revalidate")]
    assert red.breakdown()["idle_gaps"] == [
        ["serve.revalidate", pytest.approx(8e-6)]]


def test_labels_pick_the_shortest_open_span():
    spans = {"bench.step": [(0, 100)], "serve.tick": [(10, 90)],
             "serve.lookup": [(20, 30), (50, 60)]}
    assert trace.labels([-1, 0, 15, 25, 30, 55, 95, 100], spans) == [
        "none", "bench.step", "serve.tick", "serve.lookup", "serve.tick",
        "serve.lookup", "bench.step", "none"]


# one gap from the fetch of a tick's last answer into the next tick's
# revalidation: ops at 0..1 us and 9..10 us; engine.fetch 0.5..2.5 us,
# then serve.revalidate 3..8.5 us; the gap 1..9 us begins in the fetch,
# and 5.5 of its 8 us lie in the revalidation
CROSSING = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 1000000 }
    events { metadata_id: 1 offset_ps: 9000000 duration_ps: 1000000 } }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = u32[8] fusion(u32[8])" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python3" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 500000 duration_ps: 2000000 }
    events { metadata_id: 3 offset_ps: 3000000 duration_ps: 5500000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "engine.fetch" } }
  event_metadata { key: 3 value { id: 3 name: "serve.revalidate" } }
}
"""


def test_a_gap_across_spans_is_labelled_by_the_span_holding_most():
    from jax.profiler import ProfileData
    red = trace.reduce_data(ProfileData.from_text_proto(CROSSING))
    assert red.busy_s == pytest.approx(2e-6)
    assert red.gaps == [(pytest.approx(8e-6), "serve.revalidate")]


def test_cut_splits_at_every_span_edge_inside():
    spans = {"engine.fetch": [(5, 25)], "serve.revalidate": [(30, 85)]}
    assert trace.cut([(10, 90), (0, 5), (40, 50)], spans) == [
        (0, 10, 25), (0, 25, 30), (0, 30, 85), (0, 85, 90), (1, 0, 5),
        (2, 40, 50)]
    assert trace.held_by([(10, 90), (10, 40), (86, 95)], spans) == [
        "serve.revalidate", "engine.fetch", "none"]


# what the recorded trace reduced to when gaps were labelled by the
# benchmark's spans alone: only the labels may change
RECORDED_WINDOW_S = 1.027072809
RECORDED_BUSY_S = 0.000813323
RECORDED_OPS_SHA256 = \
    "cdaf1d109c1ad8f25c6901edd2a24f4a135b385a73eb60eafe37f917e311acb1"


def test_recorded_v5e_trace_reduces_as_before():
    import hashlib
    import json

    from jax.profiler import ProfileData
    red = trace.reduce_data(ProfileData.from_serialized_xspace(
        gzip.decompress(RECORDED.read_bytes())))
    assert red.window_s == RECORDED_WINDOW_S
    assert red.busy_s == RECORDED_BUSY_S
    ops = json.dumps(sorted(red.ops.items())).encode()
    assert hashlib.sha256(ops).hexdigest() == RECORDED_OPS_SHA256
    assert len(red.gaps) == 1230
