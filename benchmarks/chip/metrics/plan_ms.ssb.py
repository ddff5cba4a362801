"""plan_ms.ssb (planner layer): mean time (ms) per query sent in the
window that the server spent planning it at admission
(``aggregate.plan_wide``, with the arena's adoption check of its
bitmaps) -- ``ServerStats.plan_s``, on the server's clock, in the
``serve.plan`` span, over the change in queries submitted.  Nothing to
read from a server that keeps no such counter."""


def read(run):
    s0, s1 = run.stats0, run.stats1
    n = s1.submitted - s0.submitted
    if not n or not hasattr(s1, "plan_s"):
        return None
    return (s1.plan_s - s0.plan_s) / n * 1e3
