"""Reduce a JAX profiler trace (``.xplane.pb``) to device metrics.

Read with ``jax.profiler.ProfileData`` and nothing else.  Device planes
are those named ``/device:TPU:<n>``; their ``XLA Ops`` line holds one
event per operation run on the chip.  Host spans are ``TraceAnnotation``
events on the host plane, on the same clock: the benchmark's own
(``bench.*``) and the program's (``serve.*``, ``engine.*``).

An op event's name is its HLO instruction's text, ``%name.N = shape
op(...)``: a Pallas kernel is a ``custom-call`` named after the jitted
function that holds it (``%segment_reduce.1 = ... custom-call(...)``).
``short_name`` strips that to ``segment_reduce``.

``reduce(path, window)`` gives, over the traced window ``(start_ns,
end_ns)``: ``busy_s`` (the union of op intervals, averaged over the
chips), ``window_s``, the device time and count of each op's text
(``ops``), and the longest idle gaps, each labelled with the host span
that holds most of it: the gap is cut at every span edge inside it, each
piece takes the innermost span open over it, and the span with the most
of the gap's length names it.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import heapq
import os
import re

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."          # the benchmark's spans; bench.window
SPAN_PREFIXES = (SPAN_PREFIX, "serve.", "engine.")
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


def labels(times: list, spans: dict) -> list:
    """The innermost (shortest) span of ``spans`` (name -> intervals)
    open at each of ``times`` (``none`` if none); of equally long ones,
    the first.  One sweep: spans enter a heap by length as they open, and
    leave it from the top once they have closed."""
    flat = [(name, s, e) for name, ivs in spans.items() for s, e in ivs]
    opened = sorted((s, e - s, n, e, name)
                    for n, (name, s, e) in enumerate(flat))
    out, heap, i = {}, [], 0
    for t in sorted(set(times)):
        while i < len(opened) and opened[i][0] <= t:
            _, length, n, e, name = opened[i]
            heapq.heappush(heap, (length, n, e, name))
            i += 1
        while heap and heap[0][2] <= t:
            heapq.heappop(heap)
        out[t] = heap[0][3] if heap else "none"
    return [out[t] for t in times]


def cut(intervals: list, spans: dict) -> list:
    """``(k, start, end)``: each of ``intervals[k]`` cut at every edge of
    ``spans`` (name -> intervals) inside it, so that one set of spans is
    open over each piece."""
    edges = sorted({t for ivs in spans.values() for iv in ivs for t in iv})
    out = []
    for k, (s, e) in enumerate(intervals):
        points = [s, *edges[bisect.bisect_right(edges, s):
                            bisect.bisect_left(edges, e)], e]
        out.extend((k, a, b) for a, b in zip(points[:-1], points[1:]))
    return out


def held_by(intervals: list, spans: dict) -> list:
    """For each interval, the span of ``spans`` that holds most of its
    length: the innermost one over each piece (see ``cut``), summed by
    name; of equal sums, the one met first."""
    pieces = cut(intervals, spans)
    held: list = [{} for _ in intervals]
    for (k, a, b), name in zip(pieces, labels([a for _, a, _ in pieces],
                                               spans)):
        held[k][name] = held[k].get(name, 0) + b - a
    return [max(h, key=h.get) for h in held]


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
                if ev.name.startswith(SPAN_PREFIXES):
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
    all_gaps = [(s, e) for s, e in all_gaps if e > s]
    labelled = sorted(zip([(e - s) * 1e-9 for s, e in all_gaps],
                          held_by(all_gaps, spans)))[::-1]
    return Reduced(window_s=(window[1] - window[0]) * 1e-9,
                   busy_s=(sum(busy) / len(busy) * 1e-9) if busy else 0.0,
                   n_devices=len(devices), ops=ops, gaps=labelled,
                   spans=spans)
