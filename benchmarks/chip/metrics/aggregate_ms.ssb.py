"""aggregate_ms.ssb (planner layer): mean time (ms) per dispatched batch
in the window of the server's ``aggregate.execute_plans`` call: staging
the tick's segments, the device reduction and its answer on the host,
and the repack into containers -- ``ServerStats.aggregate_s``, on the
server's clock, in the ``serve.aggregate`` span.  Nothing to read from a
server that keeps no such counter."""


def read(run):
    s0, s1 = run.stats0, run.stats1
    batches = s1.batches - s0.batches
    if not batches or not hasattr(s1, "aggregate_s"):
        return None
    return (s1.aggregate_s - s0.aggregate_s) / batches * 1e3
