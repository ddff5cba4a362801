#!/usr/bin/env python3
"""Chip benchmark of the served query path: one cell, one seed, one run.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <run_seconds> --trace <0|1>

Run from the root of a checkout on a machine with the chips the cell asks
for (``BENCHMARK.json``).  Makes the cell's data from ``--seed``, builds
the arena-backed index through the program, warms up on the cell's own
traffic, measures for ``--seconds``, compares a sample of the answers
with the plain numpy reference, and prints one JSON line last: with
``--trace 0`` the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics from a profiler trace of the window.  The numbers it
compared, each with its limit, are the last lines on stderr and the last
key of that line.  Without a TPU, or with fewer chips than the cell
asks for, it exits 2 and prints no result.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
if str(HERE) not in sys.path:
    sys.path.insert(0, str(HERE))


def start(workload: str) -> int | None:
    """Check the cell and the chips and turn the compile cache on; return
    the chips the cell asks for, or None (with the reason on stderr)."""
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        print(f"unknown workload {workload!r}", file=sys.stderr)
        return None
    chips = int(cells[workload]["chips"])
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"needs {chips} TPU chip(s); JAX found {devices}",
              file=sys.stderr)
        return None
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import enable_compile_cache
    # $JAX_COMPILATION_CACHE_DIR when it is set, else .jax_cache/ here
    cache = enable_compile_cache(ROOT)
    # every program goes to the persistent cache, however fast it compiled
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    print(f"compile cache: {cache}", flush=True)
    print(f"device: {devices[0].platform} {devices[0].device_kind} "
          f"x{len(devices)}", flush=True)
    return chips


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    chips = start(args.workload)
    if chips is None:
        return 2
    import harness
    line = harness.run(ROOT, args.workload, args.seed, args.seconds,
                       bool(args.trace), t_start=T_START, chips=chips)
    for name, c in line["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
