"""The query server's phase spans and counters: each phase's time on the
server's own clock, the engine's device dispatches, and the spans'
nesting in a real profiler trace."""

import glob
import os

import jax
import numpy as np
import pytest

from repro.data.index import InvertedIndex
from repro.serve import FakeClock, Query, QueryServer

VOCAB = [f"t{i}" for i in range(24)]
REVALIDATE_S = 0.25           # binary fractions: the sums stay exact
SCORE_S = 1.5


@pytest.fixture(scope="module")
def index():
    rng = np.random.default_rng(7)
    docs = [[VOCAB[j] for j in rng.choice(len(VOCAB),
                                          size=int(rng.integers(2, 8)),
                                          replace=False)]
            for _ in range(600)]
    return InvertedIndex().build(docs)


class _Engine:
    """``topk_batch`` that takes ``SCORE_S`` on the clock and dispatches
    once per call."""

    def __init__(self, clock):
        self.clock = clock
        self.dispatches = 0

    def topk_batch(self, queries, k, metric, backend=None):
        self.clock.sleep(SCORE_S)
        self.dispatches += 1
        return [(np.array([q]), np.array([0.5], np.float32), np.array([1]))
                for q in queries]


class _Index:
    """An index whose engine check takes ``REVALIDATE_S`` on the clock."""
    arena = None

    def __init__(self, clock):
        self.clock = clock
        self.postings = {t: None for t in VOCAB}
        self.engine = _Engine(clock)

    def _sim_engine(self, mesh=None):
        self.clock.sleep(REVALIDATE_S)
        return list(self.postings), self.engine


def test_phases_read_the_servers_clock_and_snapshots_stay_put():
    clock = FakeClock()
    srv = QueryServer(_Index(clock), clock=clock)
    for t in VOCAB[:3]:
        srv.submit(Query.similar(t, k=2))
    srv.step()
    first = srv.stats()
    assert first.revalidate_s == REVALIDATE_S
    assert first.score_s == SCORE_S
    assert first.lookup_s == 0.0 and first.resolve_s == 0.0
    assert first.sim_dispatches == 1 and first.batches == 1
    srv.submit(Query.similar(VOCAB[0], k=2))
    srv.submit(Query.similar(VOCAB[1], k=3))     # a second class
    srv.step()
    second = srv.stats()
    assert second.revalidate_s == 2 * REVALIDATE_S
    assert second.score_s == 3 * SCORE_S
    assert second.sim_dispatches == 3
    # the first snapshot is a copy: later ticks leave it as it was
    assert (first.revalidate_s, first.score_s, first.sim_dispatches) == (
        REVALIDATE_S, SCORE_S, 1)


def test_a_phase_that_raises_still_counts_its_time():
    clock = FakeClock()
    ix = _Index(clock)

    def broken(queries, k, metric, backend=None):
        clock.sleep(SCORE_S)
        raise RuntimeError("dispatch failed")

    ix.engine.topk_batch = broken
    srv = QueryServer(ix, clock=clock, max_retries=0)
    srv.submit(Query.similar(VOCAB[0], k=2))
    srv.step()
    st = srv.stats()
    assert st.score_s == SCORE_S and st.sim_dispatches == 0
    assert st.host_fallbacks == 1


def _queries():
    return ([Query.similar(t, k=3) for t in VOCAB[:5]]
            + [Query.similar(t, k=4, metric="cosine") for t in VOCAB[5:8]])


@pytest.mark.parametrize("backend, dispatches", [
    ("ref", 2),           # one vmapped dispatch per (k, metric) class
    ("pallas", 8),        # one dispatch per query
    (None, 0),            # the host sweep, on a CPU
])
def test_sim_dispatches_count_the_engines_device_calls(index, backend,
                                                       dispatches):
    srv = QueryServer(index, backend=backend, clock=FakeClock())
    tickets = [srv.submit(q) for q in _queries()]
    srv.step()
    st = srv.stats()
    assert st.batches == 1
    assert st.sim_dispatches == dispatches
    for t in tickets:
        q = t.query
        assert t.result.ok and not t.telemetry.degraded
        assert t.result.value == index.similar(q.terms[0], q.k, q.metric)


def _events(path):
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(("serve.", "engine.")):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns))
    return out


def test_a_traced_tick_holds_its_phases(index, tmp_path):
    srv = QueryServer(index, backend="ref")
    srv.submit(Query.similar(VOCAB[0], k=3))
    srv.run_until_idle()                          # compiled before the trace
    srv.submit(Query.similar(VOCAB[1], k=3))
    srv.submit(Query.similar(VOCAB[2], k=3))
    jax.profiler.start_trace(str(tmp_path))
    try:
        srv.step()
    finally:
        jax.profiler.stop_trace()
    found = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    assert found
    events = _events(found[0])
    ticks = [(s, e) for name, s, e in events if name == "serve.tick"]
    assert len(ticks) == 1
    lo, hi = ticks[0]
    inside = {name for name, s, e in events if lo <= s and e <= hi}
    assert {"serve.revalidate", "serve.lookup", "serve.score",
            "serve.resolve", "engine.query_block", "engine.dispatch",
            "engine.fetch"} <= inside
    score = [(s, e) for name, s, e in events if name == "serve.score"]
    fetch = [(s, e) for name, s, e in events if name == "engine.fetch"]
    assert len(fetch) == 1
    assert score[0][0] <= fetch[0][0] and fetch[0][1] <= score[0][1]


# ---------------------------------------------------------------------------
# the boolean path: serve.plan, serve.aggregate and the segment counters
# ---------------------------------------------------------------------------

PLAN_S = 0.125
AGGREGATE_S = 0.5


def _cnf_index():
    """``a`` and ``b`` overlap sparsely in chunk 0; ``a``, ``b``, ``c``
    are dense in chunk 1; ``b`` alone reaches chunk 2."""
    from repro.core import BitmapArena, RoaringBitmap
    dense = np.random.default_rng(3).integers(0, 1 << 16, (3, 30000))
    vals = {"a": [np.arange(0, 100), (1 << 16) + dense[0]],
            "b": [np.arange(50, 250), (1 << 16) + dense[1],
                  (2 << 16) + dense[2]],
            "c": [(1 << 16) + dense[2]]}
    postings = {k: RoaringBitmap.from_values(
        np.concatenate(v).astype(np.uint32)) for k, v in vals.items()}
    return InvertedIndex.from_postings(postings, 3 << 16,
                                       arena=BitmapArena())


# (a) & (b | c): chunk 0 anchored on a's 100 values, 50 of them in b;
# chunk 1 one segment of 3 rows in 2 clauses.  (c) & (a | b): chunk 1
# alone, 3 rows in 2 clauses.
CNF = [Query.and_of_or(("a",), ("b", "c")),
       Query.and_of_or(("c",), ("a", "b"))]


def test_boolean_phases_and_segment_counters(monkeypatch):
    from repro.core import aggregate
    clock = FakeClock()
    real_plan, real_exec = aggregate.plan_wide, aggregate.execute_plans

    def plan(*a, **kw):
        clock.sleep(PLAN_S)
        return real_plan(*a, **kw)

    def execute(*a, **kw):
        clock.sleep(AGGREGATE_S)
        return real_exec(*a, **kw)

    monkeypatch.setattr(aggregate, "plan_wide", plan)
    monkeypatch.setattr(aggregate, "execute_plans", execute)
    srv = QueryServer(_cnf_index(), clock=clock)
    tickets = [srv.submit(q) for q in CNF]
    srv.step()
    st = srv.stats()
    assert all(t.result.ok for t in tickets)
    assert tickets[0].result.value.cardinality >= 50
    assert st.plan_s == 2 * PLAN_S and st.aggregate_s == AGGREGATE_S
    assert (st.seg_rows, st.seg_groups, st.host_groups) == (6, 2, 1)
    srv.submit(CNF[0])
    srv.step()
    st = srv.stats()
    assert st.plan_s == 3 * PLAN_S and st.aggregate_s == 2 * AGGREGATE_S
    assert (st.seg_rows, st.seg_groups, st.host_groups) == (9, 3, 2)


def test_a_traced_boolean_tick_holds_its_phases(tmp_path):
    srv = QueryServer(_cnf_index())
    srv.submit(CNF[0])
    srv.run_until_idle()                          # compiled before the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        for q in CNF:
            srv.submit(q)
        srv.step()
    finally:
        jax.profiler.stop_trace()
    found = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    events = _events(found[0])
    names = {name for name, _, _ in events}
    assert "serve.plan" in names
    (agg,) = [(s, e) for name, s, e in events if name == "serve.aggregate"]
    inside = {name for name, s, e in events
              if agg[0] <= s and e <= agg[1]}
    assert {"engine.stage", "engine.reduce", "engine.repack"} <= inside
