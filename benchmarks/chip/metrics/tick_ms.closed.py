"""tick_ms.closed (server layer): mean wall time (ms) of one
``QueryServer.step`` in the window, timed by the benchmark around each
call, which sits in a ``bench.step`` trace span."""


def read(run):
    return sum(run.ticks) / len(run.ticks) * 1e3 if run.ticks else None
