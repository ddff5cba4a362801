"""tick_idle_ms.closed (device layer): mean time (ms) per server tick in
which the chip ran nothing.

For each ``serve.tick`` span of the run's trace that starts inside the
``bench.window`` span: the span's length minus the union of the
device's op intervals clipped to it, averaged over the chips and then
over the ticks.  The same idle time, split by the innermost program span
(``serve.*``, ``engine.*``) open at each moment, and the share of it that
falls inside a span below ``serve.tick``, go to stderr.  Nothing to read
from a program that opens no ``serve.tick`` span, or without a chip.

Reads the run's ``.xplane.pb`` once, with ``trace.py``'s functions."""

import sys

import harness

trace_red = harness.trace_red

TICK = "serve.tick"
PROGRAM = ("serve.", "engine.")


def tick_idle(data):
    """``(mean idle seconds per tick, {span: idle seconds over all ticks},
    ticks)`` of a ``jax.profiler.ProfileData``, or None when it holds no
    device, no ``bench.window`` or no tick in it."""
    window, spans, devices = None, [], []
    for plane in data.planes:
        if plane.name.startswith(trace_red.DEVICE_PREFIX):
            devices.append(sorted(
                (int(ev.start_ns), int(ev.start_ns + ev.duration_ns))
                for line in plane.lines if line.name == trace_red.OPS_LINE
                for ev in line.events))
            continue
        for line in plane.lines:
            for ev in line.events:
                s, e = int(ev.start_ns), int(ev.start_ns + ev.duration_ns)
                if ev.name.startswith(PROGRAM):
                    spans.append((ev.name, s, e))
                elif ev.name == trace_red.SPAN_PREFIX + "window" \
                        and window is None:
                    window = (s, e)
    if not devices or window is None:
        return None
    ticks = [(s, e) for name, s, e in spans
             if name == TICK and window[0] <= s < window[1]]
    if not ticks:
        return None
    idle_s, split = 0.0, {}
    for lo, hi in ticks:
        by_name: dict = {}
        for name, s, e in spans:
            if s < hi and e > lo:
                by_name.setdefault(name, []).append((s, e))
        idle = []
        for ops in devices:
            clipped = [(max(s, lo), min(e, hi)) for s, e in ops
                       if s < hi and e > lo]
            busy, gaps = trace_red._union(clipped)
            if clipped:
                first, last = clipped[0][0], max(e for _, e in clipped)
                idle += [(lo, first)] + gaps + [(last, hi)]
            else:
                idle.append((lo, hi))
            idle_s += (hi - lo - busy) * 1e-9 / len(devices)
        pieces = trace_red.cut([x for x in idle if x[1] > x[0]], by_name)
        for (_, s, e), label in zip(pieces, trace_red.labels(
                [s for _, s, _ in pieces], by_name)):
            split[label] = split.get(label, 0.0) \
                + (e - s) * 1e-9 / len(devices)
    return idle_s / len(ticks), split, len(ticks)


def read(run):
    trace_dir = getattr(run, "trace_dir", None)
    path = trace_red.find_xplane(str(trace_dir)) if run.trace \
        and trace_dir is not None else None
    if path is None:
        return None
    from jax.profiler import ProfileData
    got = tick_idle(ProfileData.from_file(path))
    if got is None:
        return None
    mean_s, split, n = got
    total = sum(split.values())
    named = total - split.get(TICK, 0.0)
    parts = ", ".join(f"{name} {sec / n * 1e3}" for name, sec in
                      sorted(split.items(), key=lambda kv: -kv[1]))
    print(f"tick_idle_ms.closed: {n} ticks; idle ms per tick by the "
          f"innermost span: {parts}; inside a span below {TICK}: "
          f"{named / total * 100 if total else 0.0} %", file=sys.stderr)
    return mean_s * 1e3
