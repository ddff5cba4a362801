"""``tick_idle_ms.closed`` on a hand-made trace with known answers: the
device's idle time inside each ``serve.tick`` of the window, and its
split by the innermost program span."""

import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import harness  # noqa: E402

tick_idle_ms = harness.reader("tick_idle_ms.closed")


def _event(meta, start_ns, end_ns, t0_ns=0):
    return (f"events {{ metadata_id: {meta} offset_ps: "
            f"{(start_ns - t0_ns) * 1000} duration_ps: "
            f"{(end_ns - start_ns) * 1000} }}")


def _proto(ops, spans):
    """One chip running ``ops`` [(start_ns, end_ns)] and one host thread
    holding ``spans`` [(name, start_ns, end_ns)]."""
    names = sorted({n for n, _, _ in spans})
    meta = {n: i + 1 for i, n in enumerate(names)}
    op_events = " ".join(_event(1, s, e) for s, e in ops)
    span_events = " ".join(_event(meta[n], s, e) for n, s, e in spans)
    span_meta = " ".join(
        f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
        for n, i in meta.items())
    return f"""
planes {{
  id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0 {op_events} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "%fusion.1 = u32[8] fusion(u32[8])" }} }}
}}
planes {{
  id: 2 name: "/host:CPU"
  lines {{ id: 1 name: "python3" timestamp_ns: 0 {span_events} }}
  {span_meta}
}}
"""


WINDOW = ("bench.window", 1000, 101000)
TICK_SPANS = [
    WINDOW,
    ("serve.tick", 500, 1500),              # starts before the window
    ("serve.tick", 2000, 42000),
    ("serve.revalidate", 2000, 12000),
    ("serve.score", 14000, 40000),
    ("engine.query_block", 14000, 16000),
    ("engine.dispatch", 16000, 17000),
    ("engine.fetch", 17000, 40000),
    ("serve.resolve", 40000, 41000),
    ("serve.tick", 50000, 70000),
    ("serve.score", 50000, 69000),
    ("serve.tick", 102000, 104000),         # starts after the window
]
# overlapping ops inside the fetch, one in the second tick, one that
# runs past the first tick's end
OPS = [(18000, 38000), (30000, 39000), (60000, 65000), (40500, 45000)]


def _data(ops, spans):
    from jax.profiler import ProfileData
    return ProfileData.from_text_proto(_proto(ops, spans))


def test_idle_time_inside_the_windows_ticks():
    mean_s, split, ticks = tick_idle_ms.tick_idle(_data(OPS, TICK_SPANS))
    # first tick: 40,000 ns, busy 18,000-39,000 and 40,500-42,000;
    # second tick: 20,000 ns, busy 60,000-65,000
    assert ticks == 2
    assert mean_s == pytest.approx((17500 + 15000) / 2 * 1e-9)
    want = {"serve.revalidate": 10000, "serve.tick": 2000 + 1000,
            "engine.query_block": 2000, "engine.dispatch": 1000,
            "engine.fetch": 2000, "serve.resolve": 500,
            "serve.score": 10000 + 4000}
    assert split == pytest.approx({k: v * 1e-9 for k, v in want.items()})


def test_a_trace_without_program_spans_reads_nothing():
    bench_only = [WINDOW, ("bench.step", 2000, 42000)]
    assert tick_idle_ms.tick_idle(_data(OPS, bench_only)) is None


def test_a_run_without_a_device_trace_reads_nothing(tmp_path):
    run = SimpleNamespace(trace=True, trace_dir=tmp_path)
    assert tick_idle_ms.read(run) is None
    assert tick_idle_ms.read(SimpleNamespace(trace=False,
                                             trace_dir=None)) is None
