#!/usr/bin/env python3
"""The control of ``correct``: the reference put in the program's place,
one step below the exactness the configuration states, must fail.

    python3 benchmarks/chip/control.py --workload <cell> --seconds 5 \\
        --seeds 1 2 3

For each seed it makes one run of the cell as ``run.py`` does (the same
data, set-up, warm-up and window, at the cell's own size and load), and
then ``Run.check`` compares the window's own sample with the control's
answers in place of the served ones: each query's kind module gives its
``control`` (``kinds/similar.py``: Jaccard scores divided in bfloat16
on the default device, the chip when run there, instead of float32;
``kinds/bool.py``: the exact answer without its largest value).  It
prints each run's line; ``correct`` has to come out false.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

import run as bench  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    chips = bench.start(args.workload)
    if chips is None:
        return 2
    import gc

    import harness
    for seed in args.seeds:
        gc.collect()            # the previous seed's index and engine
        line = harness.run(bench.ROOT, args.workload, seed, args.seconds,
                           False, t_start=T_START, chips=chips,
                           control=True)
        print(json.dumps({"control_seed": seed, **line}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
