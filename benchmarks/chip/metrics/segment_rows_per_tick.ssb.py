"""segment_rows_per_tick.ssb (kernels layer): mean slab rows per
dispatched batch in the window that the device reduced -- the rows of
the batches' segments, ``ServerStats.seg_rows``, over the change in
batches.  The (query, chunk) groups reduced on the device and those the
planner resolved on the host (``seg_groups``, ``host_groups``) go to
stderr.  Nothing to read from a server that keeps no such counter."""

import sys


def read(run):
    s0, s1 = run.stats0, run.stats1
    batches = s1.batches - s0.batches
    if not batches or not hasattr(s1, "seg_rows"):
        return None
    print(f"segment_rows_per_tick.ssb: {batches} batches; per batch "
          f"{(s1.seg_groups - s0.seg_groups) / batches} groups on the "
          f"device, {(s1.host_groups - s0.host_groups) / batches} on the "
          f"host", file=sys.stderr)
    return (s1.seg_rows - s0.seg_rows) / batches
