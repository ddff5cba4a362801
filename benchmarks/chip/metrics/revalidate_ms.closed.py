"""revalidate_ms.closed (engine layer): mean time (ms) per dispatched
batch in the window that the server spent getting its similarity engine
from the index (``InvertedIndex._sim_engine``: the snapshot of every
posting that checks the cached engine, and a refresh or rebuild when a
posting moved) -- ``ServerStats.revalidate_s``, on the server's clock,
in the ``serve.revalidate`` span.  Nothing to read from a server that
keeps no such counter."""


def read(run):
    s0, s1 = run.stats0, run.stats1
    batches = s1.batches - s0.batches
    if not batches or not hasattr(s1, "revalidate_s"):
        return None
    return (s1.revalidate_s - s0.revalidate_s) / batches * 1e3
