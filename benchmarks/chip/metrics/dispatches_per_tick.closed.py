"""dispatches_per_tick.closed (kernels layer): mean device top-k
dispatches per dispatched batch in the window -- the change in
``ServerStats.sim_dispatches`` (the engine's ``dispatches`` counter
across each ``topk_batch`` call) over the change in batches.  One per
(k, metric) class where the queries are vmapped into one dispatch, one
per query on the Pallas path, none on the host sweep.  Nothing to read
from a server that keeps no such counter."""


def read(run):
    s0, s1 = run.stats0, run.stats1
    batches = s1.batches - s0.batches
    if not batches or not hasattr(s1, "sim_dispatches"):
        return None
    return (s1.sim_dispatches - s0.sim_dispatches) / batches
