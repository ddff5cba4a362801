"""segment_roofline (kernels layer): the AND-of-ORs segment kernel's
share (%) of its HBM roofline.

The kernel (``kernels/segment_ops.py`` ``segment_reduce_cnf``) makes one
pass over a tick's gathered rows: it reads each row once and writes one
row per (query, chunk) segment.  Its Pallas call appears in a v5e trace
as the ``custom-call`` named after the jit that holds it,
``%segment_reduce_cnf.N = ... custom-call(...)``.

Over the window the least time is ``bytes_moved`` / the HBM peak of the
device kind (``peaks.json``): 8,192 bytes for each row the device
reduced and each segment row written -- ``ServerStats`` ``seg_rows`` and
``seg_groups``.  The metric is that least time over the summed device
time of the calls.  Rows and segments padded to powers of two count as
no work.  Nothing to read without a chip, or from a program that keeps
no such counters."""

import json
import re
from pathlib import Path

PATTERN = re.compile(r"^%segment_reduce_cnf(\.\d+)? = .*custom-call\(")
ROW_BYTES = 8192


def bytes_moved(rows: int, segments: int) -> int:
    """HBM bytes of the window's calls: each row read once, each
    segment row written once."""
    return ROW_BYTES * (rows + segments)


def read(run):
    s0, s1 = run.stats0, run.stats1
    if run.reduced is None or not hasattr(s1, "seg_rows"):
        return None
    sec, calls = run.reduced.op_time(PATTERN.match)
    if not calls or sec <= 0:
        return None
    peaks = json.loads((Path(__file__).resolve().parents[1]
                        / "peaks.json").read_text())
    peak = peaks[run.device_kind]["hbm_bytes_per_s"]
    moved = bytes_moved(s1.seg_rows - s0.seg_rows,
                        s1.seg_groups - s0.seg_groups)
    return moved / peak / sec * 100.0
