"""Query server: coalesced multi-query dispatch, admission control,
deadlines, telemetry.  Fault-path coverage lives in test_faults.py.

The load-bearing assertion throughout: every server result is
bit-identical to direct single-query execution against the same index
-- coalescing, batching, and degradation may change HOW a query runs,
never WHAT it returns.
"""

import numpy as np
import pytest

from repro.data.index import InvertedIndex
from repro.serve import (DEADLINE, INVALID, OK, OVERLOADED, FakeClock,
                         Query, QueryServer)

VOCAB = [f"t{i}" for i in range(40)]


@pytest.fixture(scope="module")
def index():
    rng = np.random.default_rng(42)
    docs = [[VOCAB[j] for j in
             rng.choice(len(VOCAB), size=int(rng.integers(3, 10)),
                        replace=False)]
            for _ in range(1500)]
    return InvertedIndex().build(docs)


def direct(ix, q: Query):
    """Single-query reference execution through the index surface."""
    if q.kind == "and":
        return ix.query_and(*q.terms)
    if q.kind == "or":
        return ix.query_or(*q.terms)
    if q.kind == "xor":
        return ix.query_xor(*q.terms)
    if q.kind == "andnot":
        return ix.query_andnot(q.terms[0], *q.terms[1:])
    if q.kind == "threshold":
        return ix.query_threshold(list(q.terms), q.t, weights=q.weights)
    return ix.similar(q.terms[0], q.k, q.metric)


def mixed_queries(n, seed=0):
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        kind = ["and", "or", "xor", "andnot", "threshold",
                "similar"][int(rng.integers(6))]
        terms = tuple(VOCAB[j] for j in
                      rng.choice(len(VOCAB), size=int(rng.integers(2, 6)),
                                 replace=False))
        if kind == "threshold":
            out.append(Query.threshold(terms, int(rng.integers(
                1, len(terms) + 1))))
        elif kind == "similar":
            out.append(Query.similar(terms[0], k=int(rng.integers(1, 8)),
                                     metric=["jaccard", "cosine",
                                             "containment"][i % 3]))
        else:
            out.append(Query(kind, terms))
    return out


def test_coalesced_batch_bit_identical(index):
    """One tick serves a mixed batch; results match per-query direct
    execution exactly (boolean bitmaps AND similarity score lists)."""
    srv = QueryServer(index, backend="ref", clock=FakeClock())
    qs = mixed_queries(24, seed=1)
    tickets = [srv.submit(q) for q in qs]
    n = srv.step()
    assert n == len(qs)
    st = srv.stats()
    assert st.batches == 1 and st.max_batch == len(qs)
    for t, q in zip(tickets, qs):
        assert t.done and t.result.status == OK
        assert t.result.value == direct(index, q)
        assert t.telemetry.batch_size == len(qs)
        assert not t.telemetry.degraded


def test_single_query_tick(index):
    srv = QueryServer(index, backend="ref", clock=FakeClock())
    t = srv.submit(Query.and_("t1", "t2", "t3"))
    assert not t.done and srv.pending == 1
    srv.run_until_idle()
    assert t.result.value == index.query_and("t1", "t2", "t3")


def test_unknown_terms_resolve_empty(index):
    srv = QueryServer(index, backend="ref", clock=FakeClock())
    tickets = [srv.submit(Query.or_("nope", "also-nope")),
               srv.submit(Query.similar("nope", k=3))]
    srv.run_until_idle()
    assert tickets[0].result.status == OK
    assert tickets[0].result.value.cardinality == 0
    assert tickets[1].result.status == OK
    assert tickets[1].result.value == index.similar("nope", 3)


def test_invalid_queries_rejected_at_admission(index):
    srv = QueryServer(index, backend="ref", clock=FakeClock())
    bad = [Query("threshold", ("t1",), 0),            # t < 1
           Query("threshold", ("t1", "t2"), 1, weights=(1,)),
           Query("nonsense", ("t1",)),
           Query.similar("t1", metric="not-a-metric")]
    for q in bad:
        t = srv.submit(q)
        assert t.done and t.result.status == INVALID and t.result.error
    assert srv.pending == 0
    assert srv.stats().rejected_invalid == len(bad)


def test_overload_shedding_is_structured(index):
    srv = QueryServer(index, backend="ref", clock=FakeClock(),
                      max_queue=3)
    tickets = [srv.submit(Query.or_("t1")) for _ in range(6)]
    shed = [t for t in tickets if t.done]
    assert len(shed) == 3
    assert all(t.result.status == OVERLOADED for t in shed)
    srv.run_until_idle()
    assert all(t.done for t in tickets)
    assert srv.stats().rejected_overloaded == 3


def test_deadline_at_admission_and_in_queue(index):
    clock = FakeClock()
    srv = QueryServer(index, backend="ref", clock=clock)
    expired = srv.submit(Query.or_("t1"), deadline_s=-0.5)
    assert expired.result.status == DEADLINE
    queued = srv.submit(Query.or_("t1"), deadline_s=1.0)
    survivor = srv.submit(Query.or_("t2"), deadline_s=50.0)
    clock.advance(2.0)                 # deadline passes while queued
    srv.run_until_idle()
    assert queued.result.status == DEADLINE
    assert survivor.result.status == OK
    assert srv.stats().deadline_expired == 2


def test_max_batch_splits_ticks(index):
    srv = QueryServer(index, backend="ref", clock=FakeClock(),
                      max_batch=4)
    tickets = [srv.submit(q) for q in mixed_queries(10, seed=2)]
    srv.run_until_idle()
    st = srv.stats()
    assert st.batches == 3 and st.max_batch == 4
    for t in tickets:
        assert t.result.status == OK
        assert t.result.value == direct(index, t.query)


def test_max_bytes_policy_admits_at_least_one():
    # dense postings (> 4096 docs) promote to bitset containers, so each
    # OR plan carries one 2-row slab segment = 16 KiB of batch budget
    dense = InvertedIndex().build([["a", "b"]] * 5000)
    srv = QueryServer(dense, backend="ref", clock=FakeClock(),
                      max_batch_bytes=16384)
    tickets = [srv.submit(Query.or_("a", "b")) for _ in range(3)]
    assert tickets[0]._plan.slab_bytes() == 16384
    srv.run_until_idle()
    # a 16 KiB budget fits exactly one such ticket per tick -- but every
    # tick still admits at least one, so nothing can wedge the queue
    assert srv.stats().batches == 3
    assert all(t.result.status == OK for t in tickets)
    assert all(t.result.value.cardinality == 5000 for t in tickets)


def test_telemetry_times_use_injected_clock(index):
    clock = FakeClock(start=100.0)
    srv = QueryServer(index, backend="ref", clock=clock)
    t = srv.submit(Query.or_("t1"))
    clock.advance(3.0)                 # queued for 3 virtual seconds
    srv.step()
    assert t.telemetry.submitted_at == 100.0
    assert t.telemetry.dispatched_at == 103.0
    assert t.telemetry.queue_time == pytest.approx(3.0)
    assert t.telemetry.latency >= 3.0


def test_stats_snapshot_is_a_copy(index):
    srv = QueryServer(index, backend="ref", clock=FakeClock())
    snap = srv.stats()
    srv.submit(Query.or_("t1"))
    srv.run_until_idle()
    assert snap.submitted == 0 and srv.stats().submitted == 1


def test_sim_batch_groups_by_k_and_metric(index):
    """Similarity tickets with heterogeneous (k, metric) coalesce per
    class and still match direct execution exactly."""
    srv = QueryServer(index, backend="ref", clock=FakeClock())
    qs = [Query.similar("t1", k=3), Query.similar("t2", k=3),
          Query.similar("t3", k=7, metric="cosine"),
          Query.similar("t4", k=3, metric="containment")]
    tickets = [srv.submit(q) for q in qs]
    assert srv.step() == 4
    for t, q in zip(tickets, qs):
        assert t.result.value == index.similar(q.terms[0], q.k, q.metric)


# ---------------------------------------------------------------------------
# AND of ORs served over an arena-backed index
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def cnf_index():
    """Postings over three chunks: sparse in chunk 0 (host anchors),
    dense in chunk 1 (device segments), mixed in chunk 2; members of one
    attribute overlap."""
    from repro.core import BitmapArena, RoaringBitmap
    rng = np.random.default_rng(16)
    postings = {}
    for i in range(12):
        parts = [rng.integers(0, 1 << 16, int(rng.integers(10, 1500))),
                 (1 << 16) + rng.integers(0, 1 << 16, 25000)]
        if i % 3:
            parts.append((2 << 16) + rng.integers(
                0, 1 << 16, int(rng.integers(100, 20000))))
        postings[f"a{i}"] = RoaringBitmap.from_values(
            np.concatenate(parts).astype(np.uint32))
    return InvertedIndex.from_postings(postings, 3 << 16,
                                       arena=BitmapArena())


def _cnf_sets(ix, q):
    out = None
    for clause in q.terms:
        u = set()
        for term in clause:
            u |= set(ix._get(term).to_array().tolist())
        out = u if out is None else out & u
    return sorted(out)


def test_and_of_or_served_matches_sets_and_index(cnf_index, monkeypatch):
    """CNF queries through submit/step with no backend override: one
    device call a tick for all of them, answers equal to Python-set
    algebra and to ``InvertedIndex.query_and_of_or``."""
    from repro.kernels import ops as kops
    real = kops.segment_reduce_cnf
    calls = []
    monkeypatch.setattr(kops, "segment_reduce_cnf",
                        lambda *a, **kw: calls.append(1) or real(*a, **kw))
    qs = [Query.and_of_or(("a0", "a1"), ("a2",)),
          Query.and_of_or(("a3",), ("a4", "a5", "a4"), ("a6", "a7")),
          Query.and_of_or(("a8", "nope"), ("a9", "a10", "a11")),
          Query.and_of_or(("a1",)),
          Query.and_of_or(("a2",), ("nope",))]
    srv = QueryServer(cnf_index, clock=FakeClock())
    tickets = [srv.submit(q) for q in qs]
    assert srv.step() == len(qs)
    assert len(calls) == 1
    for t, q in zip(tickets, qs):
        assert t.result.status == OK and not t.telemetry.degraded
        assert t.result.value.to_array().tolist() == _cnf_sets(cnf_index, q)
        assert t.result.value == cnf_index.query_and_of_or(*q.terms)
    st = srv.stats()
    assert st.seg_groups > 0 and st.host_groups > 0


def test_and_of_or_malformed_clauses_are_invalid(cnf_index):
    srv = QueryServer(cnf_index, clock=FakeClock())
    bad = [Query.and_of_or(),                       # no clause
           Query.and_of_or(("a1",), ()),            # an empty clause
           Query("and_of_or", ("a1", "a2")),        # terms, not clauses
           Query("and_of_or", (("a1", 7),)),        # a term not a name
           Query("and_of_or", (None,))]
    for q in bad:
        t = srv.submit(q)
        assert t.done and t.result.status == INVALID and t.result.error
    assert srv.pending == 0


def test_and_of_or_is_refused_on_a_multi_device_mesh(cnf_index):
    """CNF plans reduce on one device: a server over a wider mesh
    refuses them at admission, and ``execute_plans`` raises."""
    from types import SimpleNamespace

    from repro.core import aggregate
    wide = SimpleNamespace(axis_names=("wide",), devices=np.empty(4))
    srv = QueryServer(cnf_index, clock=FakeClock(), mesh=wide)
    t = srv.submit(Query.and_of_or(("a1",), ("a2",)))
    assert t.result.status == INVALID and "one device" in t.result.error
    plan = aggregate.plan_wide(
        "and_of_or", [[cnf_index._get("a1")], [cnf_index._get("a2")]])
    with pytest.raises(ValueError, match="one device"):
        aggregate.execute_plans([plan], mesh=wide)
