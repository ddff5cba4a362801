"""Reduce a JAX profiler trace (``.xplane.pb``) to device metrics.

Read with ``jax.profiler.ProfileData`` and nothing else.  Device planes
are those named ``/device:TPU:<n>``; their ``XLA Ops`` line holds one
event per operation run on the chip.  Host spans are the benchmark's own
``TraceAnnotation`` events (names starting ``bench.``) on the host plane,
on the same clock.

An op event's name is its HLO instruction's text, ``%name.N = shape
op(...)``: a Pallas kernel is a ``custom-call`` named after the jitted
function that holds it (``%segment_reduce.1 = ... custom-call(...)``).
``short_name`` strips that to ``segment_reduce``.

``reduce(path, window)`` gives, over the traced window ``(start_ns,
end_ns)``: ``busy_s`` (the union of op intervals, averaged over the
chips), ``window_s``, the device time and count of each op's text
(``ops``), and the longest idle gaps, each labelled with the benchmark
span open when it began.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
_INSTR = re.compile(r"^%?([A-Za-z_][\w-]*?)(?:\.\d+)?(?: = |$)")


def short_name(op: str) -> str:
    """``%segment_reduce.1 = (...) custom-call(...)`` -> ``segment_reduce``."""
    m = _INSTR.match(op)
    return m.group(1) if m else op.split(" ")[0]


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float                 # mean over chips of the busy union
    n_devices: int
    ops: dict                     # op name -> [device seconds, calls]
    gaps: list                    # [(seconds, label)], longest first
    spans: dict                   # span name -> [(start_ns, end_ns)]

    def idle_share(self) -> float | None:
        if self.n_devices == 0 or self.window_s <= 0:
            return None
        return 1.0 - self.busy_s / self.window_s

    def op_time(self, match) -> tuple[float, int]:
        """Seconds and calls of every op whose name ``match`` accepts."""
        s, n = 0.0, 0
        for name, (sec, calls) in self.ops.items():
            if match(name):
                s += sec
                n += calls
        return s, n

    def breakdown(self, top: int = 10) -> dict:
        """The ops that took most device time, summed by short name, and
        the longest idle gaps with what the host was doing."""
        by_name: dict = {}
        for name, (sec, _) in self.ops.items():
            key = short_name(name)
            by_name[key] = by_name.get(key, 0.0) + sec
        ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[name, sec] for name, sec in ops],
                "idle_gaps": [[label, sec] for sec, label in
                              self.gaps[:top]]}


def start(trace_dir: str) -> None:
    """Start the profiler with host tracing at the level of annotations
    (the ``bench.*`` spans) and no Python call tracing, which would slow
    the host loop it is meant to observe."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def find_xplane(trace_dir: str) -> str | None:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return found[-1] if found else None


def _union(intervals: list) -> tuple[int, list]:
    """Total covered length and the gaps between merged intervals."""
    total, gaps, cur_s, cur_e = 0, [], None, None
    for s, e in sorted(intervals):
        if cur_e is None:
            cur_s, cur_e = s, e
        elif s > cur_e:
            total += cur_e - cur_s
            gaps.append((cur_e, s))
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total, gaps


def _label(t: int, spans: dict) -> str:
    """The innermost benchmark span open at ``t`` (``none`` if none)."""
    best, best_len = "none", None
    for name, ivs in spans.items():
        for s, e in ivs:
            if s <= t < e and (best_len is None or e - s < best_len):
                best, best_len = name, e - s
    return best


def reduce(path: str, window: tuple[int, int] | None = None) -> Reduced:
    """Reduce the trace file at ``path`` (see ``reduce_data``)."""
    from jax.profiler import ProfileData
    return reduce_data(ProfileData.from_file(path), window)


def reduce_data(data, window: tuple[int, int] | None = None) -> Reduced:
    """Reduce a ``ProfileData`` over ``window`` (ns), by default the
    ``bench.window`` span, else the span of the device's ops."""
    spans: dict = {}
    devices: list = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            devices.append(plane)
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    spans.setdefault(ev.name, []).append(
                        (int(ev.start_ns), int(ev.start_ns + ev.duration_ns)))
    if window is None:
        win = spans.get(SPAN_PREFIX + "window")
        window = win[0] if win else None
    ops: dict = {}
    busy, all_gaps, lo, hi = [], [], None, None
    for plane in devices:
        intervals = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                s, e = int(ev.start_ns), int(ev.start_ns + ev.duration_ns)
                if window is not None:
                    s, e = max(s, window[0]), min(e, window[1])
                    if e <= s:
                        continue
                intervals.append((s, e))
                rec = ops.setdefault(ev.name, [0.0, 0])
                rec[0] += (e - s) * 1e-9
                rec[1] += 1
        total, gaps = _union(intervals)
        busy.append(total)
        if intervals:
            lo = min(x[0] for x in intervals) if lo is None else lo
            hi = max(x[1] for x in intervals) if hi is None else hi
            if window is not None:
                first, last = min(intervals)[0], max(x[1] for x in intervals)
                gaps = [(window[0], first)] + gaps + [(last, window[1])]
            all_gaps.extend(gaps)
    if window is None:
        window = (lo or 0, hi or 0)
    labelled = sorted(((e - s) * 1e-9, _label(s, spans))
                      for s, e in all_gaps if e > s)[::-1]
    return Reduced(window_s=(window[1] - window[0]) * 1e-9,
                   busy_s=(sum(busy) / len(busy) * 1e-9) if busy else 0.0,
                   n_devices=len(devices), ops=ops, gaps=labelled,
                   spans=spans)
