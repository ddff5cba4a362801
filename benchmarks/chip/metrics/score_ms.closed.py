"""score_ms.closed (engine layer): mean time (ms) per dispatched batch in
the window of the server's ``SimilarityEngine.topk_batch`` calls: the
query blocks, the top-k dispatches and the host's wait for their answers
-- ``ServerStats.score_s``, on the server's clock, in the ``serve.score``
span.  Nothing to read from a server that keeps no such counter."""


def read(run):
    s0, s1 = run.stats0, run.stats1
    batches = s1.batches - s0.batches
    if not batches or not hasattr(s1, "score_s"):
        return None
    return (s1.score_s - s0.score_s) / batches * 1e3
