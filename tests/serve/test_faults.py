"""Fault-injection coverage: the server's recovery ladder under scripted
failure sequences, with a fake clock so nothing sleeps in CI.

The acceptance contract (ISSUE 6): under injected dispatch failures,
every admitted ticket resolves with a result bit-identical to direct
single-query execution or a structured error -- zero lost or hung
tickets, ever.
"""

import numpy as np
import pytest

from repro.data.index import InvertedIndex
from repro.serve import (DEADLINE, OK, FakeClock, FaultInjector, Query,
                         QueryServer)
from repro.serve.faults import SITES, AllocPressure, DispatchFault

VOCAB = [f"t{i}" for i in range(30)]


@pytest.fixture(scope="module")
def index():
    rng = np.random.default_rng(7)
    docs = [[VOCAB[j] for j in
             rng.choice(len(VOCAB), size=int(rng.integers(3, 9)),
                        replace=False)]
            for _ in range(800)]
    return InvertedIndex().build(docs)


def make_server(index, script=None, **kw):
    clock = FakeClock()
    srv = QueryServer(index, backend="ref", clock=clock,
                      faults=FaultInjector.script(script or {}), **kw)
    return srv, clock


# ----------------------------------------------------------- the harness
def test_injector_scripted_sequence_is_exact():
    inj = FaultInjector.script({"dispatch_raise": [True, False, True]})
    hits = [inj.fire("dispatch_raise") for _ in range(5)]
    assert hits == [True, False, True, False, False]
    assert inj.fired == ["dispatch_raise", "dispatch_raise"]


def test_injector_always_and_unknown_site():
    inj = FaultInjector.script({"alloc_pressure": "always"})
    assert all(inj.fire("alloc_pressure") for _ in range(10))
    assert not inj.fire("dispatch_raise")
    with pytest.raises(ValueError, match="unknown fault site"):
        FaultInjector.script({"not-a-site": [True]})


def test_injector_seeded_random_is_reproducible():
    a = FaultInjector.random(123, {"dispatch_raise": 0.5})
    b = FaultInjector.random(123, {"dispatch_raise": 0.5})
    seq_a = [bool(a.fire("dispatch_raise")) for _ in range(50)]
    seq_b = [bool(b.fire("dispatch_raise")) for _ in range(50)]
    assert seq_a == seq_b and any(seq_a) and not all(seq_a)


def test_fake_clock_sleep_advances_and_records():
    clk = FakeClock(start=5.0)
    clk.sleep(1.5)
    clk.sleep(0.25)
    assert clk.now() == 6.75 and clk.sleeps == [1.5, 0.25]


# ----------------------------------------------------- fail once, succeed
def test_fail_once_then_succeed_retries_on_kernel(index):
    srv, clock = make_server(index, {"dispatch_raise": [True]})
    t = srv.submit(Query.and_("t1", "t2"))
    srv.run_until_idle()
    assert t.result.status == OK
    assert t.result.value == index.query_and("t1", "t2")
    assert t.telemetry.retries == 1
    assert not t.telemetry.degraded          # kernel, not host
    assert srv.stats().dispatch_retries == 1
    assert srv.stats().host_fallbacks == 0
    assert clock.sleeps == [srv.backoff_s]   # one backoff, fake clock


def test_backoff_is_exponential(index):
    srv, clock = make_server(index, {"dispatch_raise": [True, True]},
                             max_retries=3)
    t = srv.submit(Query.or_("t1"))
    srv.run_until_idle()
    assert t.result.status == OK and t.telemetry.retries == 2
    assert clock.sleeps == [srv.backoff_s, 2 * srv.backoff_s]


# ----------------------------------------------------------- fail always
def test_fail_always_degrades_to_host_bit_identical(index):
    srv, clock = make_server(index, {"dispatch_raise": "always"})
    qs = [Query.and_("t1", "t2"), Query.or_("t3", "t4", "t5"),
          Query.xor_("t6", "t7"), Query.andnot("t1", "t8"),
          Query.threshold(["t1", "t2", "t3"], 2),
          Query.similar("t2", k=5),
          Query.similar("t3", k=4, metric="cosine")]
    tickets = [srv.submit(q) for q in qs]
    srv.run_until_idle()
    direct = [index.query_and("t1", "t2"),
              index.query_or("t3", "t4", "t5"),
              index.query_xor("t6", "t7"),
              index.query_andnot("t1", "t8"),
              index.query_threshold(["t1", "t2", "t3"], 2),
              index.similar("t2", 5),
              index.similar("t3", 4, metric="cosine")]
    for t, d in zip(tickets, direct):
        assert t.result.status == OK
        assert t.result.value == d            # host path: bit-identical
        assert t.telemetry.degraded
        assert t.telemetry.retries == srv.max_retries
    st = srv.stats()
    assert st.host_fallbacks == 1
    assert st.resolved_ok == len(qs) and st.resolved_error == 0


# ------------------------------------------------------- deadline overrun
def test_hang_overruns_deadline_structured(index):
    srv, clock = make_server(index, {"dispatch_hang": [10.0]})
    doomed = srv.submit(Query.or_("t1"), deadline_s=2.0)
    patient = srv.submit(Query.or_("t2"))     # no deadline: survives
    srv.run_until_idle()
    assert doomed.result.status == DEADLINE
    assert "overrun" in doomed.result.error
    assert patient.result.status == OK
    assert patient.result.value == index.query_or("t2")
    assert srv.stats().deadline_expired == 1


def test_hang_without_deadline_just_slows(index):
    srv, clock = make_server(index, {"dispatch_hang": [60.0]})
    t = srv.submit(Query.or_("t1"))
    srv.run_until_idle()
    assert t.result.status == OK and t.telemetry.latency >= 60.0


# ------------------------------------------------------- alloc pressure
def test_alloc_pressure_splits_batch(index):
    srv, clock = make_server(index, {"alloc_pressure": [True]})
    tickets = [srv.submit(Query.or_(v)) for v in VOCAB[:8]]
    srv.run_until_idle()
    assert all(t.result.status == OK for t in tickets)
    assert srv.stats().batch_splits == 1
    assert all(t.telemetry.splits == 1 for t in tickets)
    assert srv.stats().host_fallbacks == 0    # halves fit: still kernel


def test_alloc_pressure_always_falls_back_to_host(index):
    srv, clock = make_server(index, {"alloc_pressure": "always"})
    tickets = [srv.submit(Query.or_(v)) for v in VOCAB[:4]]
    srv.run_until_idle()
    for t in tickets:
        assert t.result.status == OK and t.telemetry.degraded
        assert t.result.value == index.query_or(t.query.terms[0])
    assert srv.stats().host_fallbacks == 4    # each singleton degraded


@pytest.mark.parametrize("site,cause", [
    ("dispatch_raise", "DispatchFault: injected dispatch failure"),
    ("alloc_pressure", "AllocPressure: batch of 1 refused"),
])
def test_degraded_batch_records_first_exception(index, site, cause):
    """A degraded batch keeps the exception that started its failures,
    so an operator sees why the device path was left; a healthy server
    records none."""
    srv, _ = make_server(index, {site: "always"})
    srv.submit(Query.or_("t1"))
    srv.run_until_idle()
    assert srv.stats().host_fallbacks == 1
    assert srv.stats().fallback_cause == cause
    ok, _ = make_server(index)
    ok.submit(Query.or_("t1"))
    ok.run_until_idle()
    assert ok.stats().fallback_cause == ""


# -------------------------------------------------------- slab mismatch
def test_slab_mismatch_replans_and_succeeds(index):
    srv, clock = make_server(index, {"slab_mismatch": [True]})
    t1 = srv.submit(Query.and_("t1", "t2"))
    t2 = srv.submit(Query.similar("t1", k=3))
    srv.run_until_idle()
    assert t1.result.status == OK
    assert t1.result.value == index.query_and("t1", "t2")
    assert t2.result.value == index.similar("t1", 3)
    assert srv.stats().replans == 1
    assert t1.telemetry.replans == 1 and t2.telemetry.replans == 1


# --------------------------------------------- zero lost tickets, period
def test_zero_lost_tickets_under_random_fault_storm(index):
    """Seeded random faults at every site at once, a mixed workload,
    deadlines on half the tickets: every admitted ticket must resolve
    (value bit-identical to direct execution, or a structured error)."""
    rng = np.random.default_rng(99)
    inj = FaultInjector.random(
        4242, {s: 0.3 for s in SITES}, hang_s=0.5)
    clock = FakeClock()
    srv = QueryServer(index, backend="ref", clock=clock, faults=inj,
                      max_batch=8, max_retries=1, max_queue=64)
    tickets = []
    for i in range(60):
        if rng.random() < 0.3:
            q = Query.similar(VOCAB[int(rng.integers(len(VOCAB)))],
                              k=int(rng.integers(1, 6)))
        else:
            kind = ["and", "or", "xor"][int(rng.integers(3))]
            terms = tuple(VOCAB[j] for j in
                          rng.choice(len(VOCAB), 3, replace=False))
            q = Query(kind, terms)
        dl = float(rng.uniform(0.1, 3.0)) if rng.random() < 0.5 else None
        tickets.append(srv.submit(q, deadline_s=dl))
    srv.run_until_idle()
    assert all(t.done for t in tickets), "lost tickets"
    st = srv.stats()
    assert st.resolved_error == 0             # faults are transient
    n_ok = 0
    for t in tickets:
        assert t.result.status in (OK, DEADLINE, "overloaded")
        if t.result.status == OK:
            n_ok += 1
            if t.query.kind == "similar":
                assert t.result.value == index.similar(
                    t.query.terms[0], t.query.k, t.query.metric)
            else:
                got = t.result.value
                want = {"and": index.query_and, "or": index.query_or,
                        "xor": index.query_xor}[t.query.kind](
                            *t.query.terms)
                assert got == want
    assert n_ok > 0                           # the storm didn't kill all
    assert inj.fired                          # ... and faults did fire


def test_step_never_raises_even_on_unexpected_error(index, monkeypatch):
    """A real (non-injected) bug inside dispatch must still resolve the
    ticket -- as a structured ERROR after host fallback also fails."""
    from repro.core import aggregate
    srv, clock = make_server(index, max_retries=0)

    def boom(*a, **k):
        raise RuntimeError("real bug")
    monkeypatch.setattr(aggregate, "execute_plans", boom)
    monkeypatch.setattr(aggregate, "execute_plan_host", boom)
    t = srv.submit(Query.or_("t1"))
    srv.run_until_idle()                      # must not raise
    assert t.result.status == "error"
    assert "real bug" in t.result.error
    assert srv.stats().resolved_error == 1


def test_fault_errors_are_distinguishable():
    assert issubclass(DispatchFault, Exception)
    assert issubclass(AllocPressure, Exception)
    with pytest.raises(DispatchFault):
        raise DispatchFault("x")
