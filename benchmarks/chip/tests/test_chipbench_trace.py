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
