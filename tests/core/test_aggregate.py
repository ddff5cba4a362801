"""Wide-aggregation planner vs a Python-set oracle.

Property-style tests (seeded rng sweeps; hypothesis is not available in this
environment) across adversarial distributions: dense runs, sparse arrays,
the 4096/4097 array<->bitset boundary, disjoint key ranges, and the K=0/K=1
edges.  Every op is checked against functools.reduce over Python sets and
threshold against an occurrence Counter."""

import operator
from collections import Counter
from functools import reduce

import numpy as np
import pytest

from repro.core import RoaringBitmap
from repro.core import aggregate


def bm(values):
    return RoaringBitmap.from_values(np.asarray(list(values), np.uint32))


# ---------------------------------------------------------------------------
# adversarial input distributions
# ---------------------------------------------------------------------------

def dense_runs(rng, k):
    """Heavily overlapping intervals -> run/bitset containers."""
    out = []
    for _ in range(k):
        parts = []
        for _ in range(int(rng.integers(1, 4))):
            lo = int(rng.integers(0, 1 << 18))
            parts.append(np.arange(lo, lo + int(rng.integers(1, 70000)),
                                   dtype=np.uint32))
        out.append(np.unique(np.concatenate(parts)))
    return out


def sparse_arrays(rng, k):
    """Small scattered arrays across many chunks."""
    return [rng.integers(0, 1 << 20, int(rng.integers(1, 500)),
                         dtype=np.uint32) for _ in range(k)]


def boundary_4096(rng, k):
    """Exactly 4096 / 4097 values inside one chunk: the array<->bitset
    result-kind boundary."""
    out = []
    for i in range(k):
        n = 4096 + (i % 2)
        out.append(rng.choice(1 << 16, n, replace=False).astype(np.uint32))
    return out


def disjoint_keys(rng, k):
    """Each bitmap owns its own key range -> all singleton groups."""
    return [(np.uint32(i << 16) +
             rng.integers(0, 1 << 16, int(rng.integers(1, 3000)),
                          dtype=np.uint32))
            for i in range(k)]


def mixed(rng, k):
    """Runs + arrays + bitsets overlapping in the same chunks."""
    gens = [dense_runs, sparse_arrays, boundary_4096]
    return [gens[i % len(gens)](rng, 1)[0] for i in range(k)]


DISTS = [dense_runs, sparse_arrays, boundary_4096, disjoint_keys, mixed]


def _check_invariants(r):
    assert r.keys == sorted(r.keys)
    for c in r.containers:
        assert c.card > 0
        if c.kind == "array":
            assert c.card <= 4096
            assert np.all(np.diff(c.values.astype(np.int64)) > 0)
        elif c.kind == "run":
            assert c.num_runs() <= 2047


@pytest.mark.parametrize("dist", DISTS, ids=lambda d: d.__name__)
@pytest.mark.parametrize("k", [2, 3, 7])
def test_wide_ops_vs_set_oracle(rng, dist, k):
    vals = dist(rng, k)
    bms = [bm(v) for v in vals]
    sets = [set(v.tolist()) for v in vals]
    for name, wide, op in [("or", RoaringBitmap.or_many, operator.or_),
                           ("and", RoaringBitmap.and_many, operator.and_),
                           ("xor", RoaringBitmap.xor_many, operator.xor)]:
        want = sorted(reduce(op, sets))
        got = wide(bms)
        assert got.to_array().tolist() == want, (name, dist.__name__, k)
        _check_invariants(got)


@pytest.mark.parametrize("dist", DISTS, ids=lambda d: d.__name__)
@pytest.mark.parametrize("k,t", [(3, 2), (5, 3), (7, 7), (4, 1)])
def test_threshold_vs_counter_oracle(rng, dist, k, t):
    vals = dist(rng, k)
    bms = [bm(v) for v in vals]
    cnt = Counter()
    for v in vals:
        cnt.update(set(v.tolist()))
    want = sorted(x for x, c in cnt.items() if c >= t)
    got = RoaringBitmap.threshold_many(bms, t)
    assert got.to_array().tolist() == want, (dist.__name__, k, t)
    _check_invariants(got)


def test_wide_matches_pairwise(rng):
    """The planner must agree with the two-by-two merge operators."""
    for _ in range(5):
        bms = [bm(rng.integers(0, 1 << 19, int(rng.integers(0, 20000)),
                               dtype=np.uint32)) for _ in range(4)]
        assert RoaringBitmap.or_many(bms) == reduce(operator.or_, bms)
        assert RoaringBitmap.and_many(bms) == reduce(operator.and_, bms)
        assert RoaringBitmap.xor_many(bms) == reduce(operator.xor, bms)


def test_threshold_endpoints(rng):
    """T=1 is union, T=K intersection, T>K empty, T<1 rejected."""
    bms = [bm(rng.integers(0, 1 << 18, 5000, dtype=np.uint32))
           for _ in range(5)]
    assert RoaringBitmap.threshold_many(bms, 1) == RoaringBitmap.or_many(bms)
    assert RoaringBitmap.threshold_many(bms, 5) == RoaringBitmap.and_many(bms)
    assert not RoaringBitmap.threshold_many(bms, 6)
    with pytest.raises(ValueError):
        RoaringBitmap.threshold_many(bms, 0)


def test_k0_and_k1_edges(rng):
    for wide in (RoaringBitmap.or_many, RoaringBitmap.and_many,
                 RoaringBitmap.xor_many):
        assert wide([]).cardinality == 0
    assert RoaringBitmap.threshold_many([], 1).cardinality == 0
    x = bm(rng.integers(0, 1 << 20, 10000, dtype=np.uint32))
    for wide in (RoaringBitmap.or_many, RoaringBitmap.and_many,
                 RoaringBitmap.xor_many):
        assert wide([x]) == x
    assert RoaringBitmap.threshold_many([x], 1) == x
    assert RoaringBitmap.threshold_many([x], 2).cardinality == 0


def test_full_chunk_or_short_circuit():
    """A full 2^16 chunk in any input forces a full result chunk."""
    a = RoaringBitmap.from_range(0, 1 << 16)
    b = bm([5, 70000])
    r = RoaringBitmap.or_many([a, b, b])
    assert r.cardinality == (1 << 16) + 1
    assert r.containers[0].card == 1 << 16


def test_and_empty_key_early_exit():
    """Disjoint key sets make AND exit before touching containers."""
    a = bm(range(0, 1000))
    c = bm(range(1 << 17, (1 << 17) + 1000))
    assert RoaringBitmap.and_many([a, c, a]).cardinality == 0


def test_aggregate_duplicates_of_same_bitmap(rng):
    """The same bitmap object repeated K times: OR/AND are idempotent and
    XOR follows parity."""
    x = bm(rng.integers(0, 1 << 19, 30000, dtype=np.uint32))
    assert RoaringBitmap.or_many([x, x, x]) == x
    assert RoaringBitmap.and_many([x, x, x]) == x
    assert RoaringBitmap.xor_many([x, x, x]) == x
    assert RoaringBitmap.xor_many([x, x]).cardinality == 0
    assert RoaringBitmap.threshold_many([x, x, x], 3) == x


def test_planner_module_direct_backend(rng):
    """The planner accepts an explicit backend and the ref backend agrees
    with the default dispatch."""
    vals = [rng.integers(0, 1 << 18, 20000, dtype=np.uint32)
            for _ in range(3)]
    bms = [bm(v) for v in vals]
    assert aggregate.or_many(bms, backend="ref") == \
        RoaringBitmap.or_many(bms)
    assert aggregate.threshold_many(bms, 2, backend="ref") == \
        RoaringBitmap.threshold_many(bms, 2)


def test_result_mutation_does_not_corrupt_inputs(rng):
    """Pass-through keys share containers zero-copy; point updates on the
    result must copy-on-write instead of corrupting the inputs."""
    vals = rng.choice(1 << 16, 10000, replace=False).astype(np.uint32) \
        + np.uint32(3 << 16)
    a = bm(vals)                          # single bitset container, key 3
    b = bm([1, 2])
    want = a.to_array().copy()
    u = RoaringBitmap.or_many([a, b])
    u.add(int((3 << 16) + 1))
    u.remove(int(want[0]))
    assert np.array_equal(a.to_array(), want)


def test_tensor_reduce_or_matches_host(rng):
    from repro.core.tensor import RoaringTensor
    bms = [bm(rng.integers(0, 1 << 19, int(rng.integers(1, 15000)),
                           dtype=np.uint32)) for _ in range(5)]
    rt = RoaringTensor.from_bitmaps(bms)
    assert rt.reduce_or().to_bitmaps()[0] == RoaringBitmap.or_many(bms)


@pytest.mark.parametrize("dist", DISTS, ids=lambda d: d.__name__)
@pytest.mark.parametrize("k", [1, 3, 6])
def test_andnot_many_vs_set_oracle(rng, dist, k):
    """a - (b1 | ... | bk) against the Python-set oracle and the pairwise
    two-by-two chain, across every adversarial distribution."""
    vals = dist(rng, k + 1)
    a, subs = bm(vals[0]), [bm(v) for v in vals[1:]]
    want = sorted(set(vals[0].tolist()) -
                  set().union(*(set(v.tolist()) for v in vals[1:])))
    got = RoaringBitmap.andnot_many(a, subs)
    assert got.to_array().tolist() == want, (dist.__name__, k)
    _check_invariants(got)
    assert got == reduce(operator.sub, [a] + subs)


def test_andnot_many_edges(rng):
    a = bm(rng.integers(0, 1 << 19, 20000, dtype=np.uint32))
    assert RoaringBitmap.andnot_many(a, []) == a
    assert RoaringBitmap.andnot_many(a, [a]).cardinality == 0
    assert RoaringBitmap.andnot_many(RoaringBitmap(), [a]).cardinality == 0
    # a full subtrahend chunk wipes the minuend's chunk entirely
    full = RoaringBitmap.from_range(0, 1 << 16)
    r = RoaringBitmap.andnot_many(bm([5, 70000]), [full])
    assert r.to_array().tolist() == [70000]
    # empty subtrahends are no-ops
    assert RoaringBitmap.andnot_many(a, [RoaringBitmap()] * 3) == a


@pytest.mark.parametrize("dist", DISTS, ids=lambda d: d.__name__)
@pytest.mark.parametrize("k,t", [(3, 4), (5, 7), (4, 2)])
def test_threshold_weighted_vs_counter_oracle(rng, dist, k, t):
    """Weighted T-occurrence against a weighted Counter oracle."""
    vals = dist(rng, k)
    bms = [bm(v) for v in vals]
    w = [int(x) for x in rng.integers(1, 6, k)]
    cnt = Counter()
    for v, wi in zip(vals, w):
        for x in set(v.tolist()):
            cnt[x] += wi
    want = sorted(x for x, c in cnt.items() if c >= t)
    got = RoaringBitmap.threshold_many(bms, t, weights=w)
    assert got.to_array().tolist() == want, (dist.__name__, k, t, w)
    _check_invariants(got)


def test_threshold_weight_one_degenerates(rng):
    """weights=[1]*k must agree with the unweighted plan exactly."""
    for dist in DISTS:
        vals = dist(rng, 4)
        bms = [bm(v) for v in vals]
        for t in (1, 2, 4):
            assert RoaringBitmap.threshold_many(bms, t, weights=[1] * 4) \
                == RoaringBitmap.threshold_many(bms, t), (dist.__name__, t)


def test_threshold_weighted_edges(rng):
    bms = [bm(rng.integers(0, 1 << 18, 5000, dtype=np.uint32))
           for _ in range(3)]
    w = [5, 3, 2]
    # t above the total weight is empty without touching containers
    assert RoaringBitmap.threshold_many(bms, 11, weights=w).cardinality == 0
    # t == total weight is the intersection
    assert RoaringBitmap.threshold_many(bms, 10, weights=w) == \
        RoaringBitmap.and_many(bms)
    # t == 1 is the union
    assert RoaringBitmap.threshold_many(bms, 1, weights=w) == \
        RoaringBitmap.or_many(bms)
    # a single heavy bitmap can satisfy t alone
    got = RoaringBitmap.threshold_many(bms, 5, weights=w)
    for x in bms[0].to_array()[:100].tolist():
        assert x in got
    with pytest.raises(ValueError):
        RoaringBitmap.threshold_many(bms, 2, weights=[1, 2])   # wrong len
    with pytest.raises(ValueError):
        RoaringBitmap.threshold_many(bms, 2, weights=[1, 0, 2])  # w < 1


def test_index_query_andnot_chain(rng):
    from repro.data.index import InvertedIndex
    docs = [[f"t{t}" for t in rng.choice(10, rng.integers(1, 5),
                                         replace=False)]
            for _ in range(200)]
    idx = InvertedIndex().build(docs)
    got = idx.query_andnot("t0", "t1", "t2")
    for d in range(len(docs)):
        want = "t0" in docs[d] and "t1" not in docs[d] and \
            "t2" not in docs[d]
        assert (d in got) == want, d


def test_index_query_threshold(rng):
    from repro.data.index import InvertedIndex
    docs = [[f"t{t}" for t in rng.choice(20, rng.integers(1, 8),
                                         replace=False)]
            for _ in range(300)]
    idx = InvertedIndex().build(docs)
    terms = [f"t{i}" for i in range(6)]
    got = idx.query_threshold(terms, 3)
    for d in range(len(docs)):
        n_match = sum(t in docs[d] for t in terms)
        assert (d in got) == (n_match >= 3)


# ---------------------------------------------------------------------------
# multi-query planning (plan_wide / execute_plans / execute_plan_host)
# ---------------------------------------------------------------------------

def _random_query(rng, dist):
    """One random wide query as (op, bitmaps, t, weights)."""
    k = int(rng.integers(2, 7))
    vals = dist(rng, k)
    bms = [bm(v) for v in vals]
    op = ["or", "and", "xor", "andnot", "threshold"][int(rng.integers(5))]
    t, w = 0, None
    if op == "threshold":
        t = int(rng.integers(1, k + 1))
        if rng.random() < 0.5:
            w = [int(x) for x in rng.integers(1, 5, k)]
    return op, bms, t, w


def _direct(op, bms, t, w, backend):
    if op == "or":
        return aggregate.or_many(bms, backend=backend)
    if op == "and":
        return aggregate.and_many(bms, backend=backend)
    if op == "xor":
        return aggregate.xor_many(bms, backend=backend)
    if op == "andnot":
        return aggregate.andnot_many(bms[0], bms[1:], backend=backend)
    return aggregate.threshold_many(bms, t, weights=w, backend=backend)


@pytest.mark.parametrize("seed", [0, 1])
def test_execute_plans_coalesced_bit_identical(seed):
    """N queries coalesced into one dispatch per op class must equal N
    direct executions exactly -- container kinds included (a query id is
    just another segment coordinate)."""
    rng = np.random.default_rng(seed)
    dists = [dense_runs, sparse_arrays, boundary_4096, disjoint_keys]
    queries = [_random_query(rng, dists[i % 4]) for i in range(12)]
    plans = [aggregate.plan_wide(op, b, t, w, backend="ref")
             for op, b, t, w in queries]
    batch = aggregate.execute_plans(plans, backend="ref")
    for got, (op, b, t, w) in zip(batch, queries):
        want = _direct(op, b, t, w, "ref")
        assert got == want, op
        assert [c.kind for c in got.containers] == \
               [c.kind for c in want.containers], op


@pytest.mark.parametrize("seed", [3, 4])
def test_execute_plan_host_is_bit_identical_and_jax_free(seed):
    """The degradation path: numpy-only execution of a plan matches the
    kernel dispatch bit for bit (same rows, same repack)."""
    rng = np.random.default_rng(seed)
    dists = [dense_runs, sparse_arrays, boundary_4096, disjoint_keys]
    for i in range(8):
        op, b, t, w = _random_query(rng, dists[i % 4])
        host = aggregate.execute_plan_host(
            aggregate.plan_wide(op, b, t, w, backend="ref"))
        want = _direct(op, b, t, w, "ref")
        assert host == want, op
        assert [c.kind for c in host.containers] == \
               [c.kind for c in want.containers], op


def test_per_segment_thresholds_share_one_dispatch(rng):
    """Threshold queries with DIFFERENT t values coalesce into one
    dispatch via the kernel's per-segment threshold vector."""
    k = 6
    vals = dense_runs(rng, k)
    bms = [bm(v) for v in vals]
    plans = [aggregate.plan_wide("threshold", bms, t, backend="ref")
             for t in range(2, k + 1)]
    batch = aggregate.execute_plans(plans, backend="ref")
    sets = [set(np.concatenate(vals).tolist()) for _ in range(1)]
    counts = Counter()
    for v in vals:
        counts.update(v.tolist())
    for t, got in zip(range(2, k + 1), batch):
        want = {x for x, c in counts.items() if c >= t}
        assert set(got.to_array().tolist()) == want, t


def test_plan_wide_validates_at_admission():
    with pytest.raises(ValueError, match="threshold"):
        aggregate.plan_wide("threshold", [bm([1])], 0)
    with pytest.raises(ValueError, match="weight"):
        aggregate.plan_wide("threshold", [bm([1]), bm([2])], 1,
                            weights=[1])
    with pytest.raises(ValueError, match="minuend"):
        aggregate.plan_wide("andnot", [])
    with pytest.raises(ValueError, match="unknown wide op"):
        aggregate.plan_wide("nand", [bm([1])])


def test_plan_slab_bytes_accounting(rng):
    a = bm(np.arange(0, 50000, dtype=np.uint32))          # bitset/run mix
    b_ = bm(np.arange(25000, 70000, dtype=np.uint32))
    plan = aggregate.plan_wide("or", [a, b_], backend="ref")
    assert plan.slab_bytes() == \
        sum(len(r) for r in plan.seg_rows) * 8192
    empty = aggregate.plan_wide("or", [], backend="ref")
    assert empty.slab_bytes() == 0


# ---------------------------------------------------------------------------
# AND of ORs (conjunctive normal form): one plan, host-anchored or device
# ---------------------------------------------------------------------------

def _cnf_values(rng):
    """2-4 clauses of 1-4 value sets over chunks 0-4.  Chunk 0 is sparse
    in every clause (a host anchor), chunk 1 dense in every clause (a
    device segment), chunks 2-4 a mix of sparse, dense and runs; the
    members of a clause draw from the same chunks, so they overlap."""
    out = []
    for _ in range(int(rng.integers(2, 5))):
        clause = []
        for _ in range(int(rng.integers(1, 5))):
            parts = [rng.integers(0, 1 << 16, int(rng.integers(1, 900))),
                     (1 << 16) + rng.integers(0, 1 << 16, 30000)]
            for key in rng.choice([2, 3, 4], int(rng.integers(1, 4)),
                                  replace=False):
                kind = int(rng.integers(3))
                if kind == 0:
                    n = int(rng.integers(1, 3000))
                    vals = rng.integers(0, 1 << 16, n)
                elif kind == 1:
                    vals = rng.integers(0, 1 << 16, 20000)
                else:
                    lo = int(rng.integers(0, 30000))
                    vals = np.arange(lo, lo + int(rng.integers(1, 30000)))
                parts.append((int(key) << 16) + vals)
            clause.append(np.unique(np.concatenate(parts)).astype(np.uint32))
        out.append(clause)
    return out


def _cnf_want(clause_vals) -> list:
    return sorted(reduce(operator.and_, [
        reduce(operator.or_, [set(v.tolist()) for v in c])
        for c in clause_vals]))


def _cnf_bitmaps(clause_vals, arena=None):
    out = [[bm(v) for v in c] for c in clause_vals]
    if arena is not None:
        for c in out:
            for b in c:
                b.run_optimize()
                arena.adopt(b)
    return out


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("resident", [False, True],
                         ids=["staged", "arena"])
def test_and_of_or_vs_set_oracle(seed, resident):
    from repro.core import BitmapArena
    rng = np.random.default_rng(seed)
    arena = BitmapArena() if resident else None
    queries = [_cnf_values(rng) for _ in range(4)]
    plans = [aggregate.plan_wide("and_of_or", _cnf_bitmaps(q, arena),
                                 arena=arena) for q in queries]
    # both sides of the host/device choice in the one batch: chunk 0 is
    # anchored on the host, chunk 1 goes to the device
    assert all(0 not in p.seg_keys and 1 in p.seg_keys for p in plans)
    for got, q in zip(aggregate.execute_plans(plans, backend="ref"),
                      queries):
        assert got.to_array().tolist() == _cnf_want(q)
        _check_invariants(got)


def test_and_of_or_kernel_and_host_twin_bit_identical(rng):
    """The Pallas path (interpret mode), the jnp oracle and the numpy
    host twin give the same containers, kinds included."""
    from repro.core import BitmapArena
    arena = BitmapArena()
    queries = [_cnf_values(rng) for _ in range(3)]
    plans = [aggregate.plan_wide("and_of_or", _cnf_bitmaps(q, arena),
                                 arena=arena) for q in queries]
    pallas = aggregate.execute_plans(plans, backend="pallas")
    oracle = aggregate.execute_plans(plans, backend="ref")
    for p, a, b, q in zip(plans, pallas, oracle, queries):
        host = aggregate.execute_plan_host(p)
        assert a == b == host
        assert [c.kind for c in a.containers] == \
            [c.kind for c in host.containers]
        assert a.to_array().tolist() == _cnf_want(q)


def test_and_of_or_coalesces_into_one_device_call(monkeypatch, rng):
    from repro.kernels import ops as kops
    real = kops.segment_reduce_cnf
    calls = []

    def counting(*a, **kw):
        calls.append(kw)
        return real(*a, **kw)

    monkeypatch.setattr(kops, "segment_reduce_cnf", counting)
    queries = [_cnf_values(rng) for _ in range(5)]
    plans = [aggregate.plan_wide("and_of_or", _cnf_bitmaps(q))
             for q in queries]
    out = aggregate.execute_plans(plans, backend="ref")
    assert len(calls) == 1
    assert [o.to_array().tolist() for o in out] == \
        [_cnf_want(q) for q in queries]


def test_and_of_or_overlapping_members_and_literals():
    a = np.arange(0, 40000, dtype=np.uint32)            # bitset, chunk 0
    b = np.arange(30000, 70000, dtype=np.uint32)        # overlaps a
    c = np.array([5, 35000, 65600, 1 << 20], np.uint32)
    cases = [
        [[a, a], [b]],                                  # a member twice
        [[a, b], [b, c]],                               # overlap, anchor
        [[c]],                                          # one literal
        [[a], [b], [c]],                                # single literals
        [[a, b]],                                       # one clause: OR
    ]
    for vals in cases:
        plan = aggregate.plan_wide("and_of_or", _cnf_bitmaps(vals))
        got = aggregate.execute_plans([plan], backend="ref")[0]
        assert got.to_array().tolist() == _cnf_want(vals)
        assert aggregate.execute_plan_host(plan) == got


def test_and_of_or_empty_clauses_keys_and_full_chunks():
    a = bm(range(0, 100))
    far = bm(range(5 << 16, (5 << 16) + 10))
    empty = RoaringBitmap()
    # a clause of empty bitmaps (unknown terms): empty, no segment
    plan = aggregate.plan_wide("and_of_or", [[a], [empty, empty]])
    assert plan.seg_keys == [] and plan.merged == {}
    # disjoint keys: the key intersection exits early
    plan = aggregate.plan_wide("and_of_or", [[a], [far]])
    assert plan.seg_keys == [] and plan.merged == {}
    # a full chunk is the AND identity
    full = bm(range(0, 1 << 16))
    got = aggregate.execute_plans(
        [aggregate.plan_wide("and_of_or", [[full], [a, far]])])[0]
    assert got.to_array().tolist() == list(range(100))
    got = aggregate.execute_plans(
        [aggregate.plan_wide("and_of_or", [[full], [full]])])[0]
    assert got.cardinality == 1 << 16


def test_and_of_or_validates_at_admission():
    with pytest.raises(ValueError, match="at least one clause"):
        aggregate.plan_wide("and_of_or", [])
    with pytest.raises(ValueError, match="at least one bitmap"):
        aggregate.plan_wide("and_of_or", [[bm([1])], []])


def test_and_of_or_splits_calls_past_the_row_chunk(monkeypatch, rng):
    from repro.kernels import ops as kops
    real = kops.segment_reduce_cnf
    calls = []

    def counting(*a, **kw):
        calls.append(kw)
        return real(*a, **kw)

    monkeypatch.setattr(kops, "segment_reduce_cnf", counting)
    monkeypatch.setattr(aggregate, "CNF_ROW_CHUNK", 8)
    queries = [_cnf_values(rng) for _ in range(3)]
    plans = [aggregate.plan_wide("and_of_or", _cnf_bitmaps(q))
             for q in queries]
    out = aggregate.execute_plans(plans, backend="pallas")
    assert len(calls) > 1
    assert [o.to_array().tolist() for o in out] == \
        [_cnf_want(q) for q in queries]
