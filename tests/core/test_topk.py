"""Device-resident top-k similarity engine: edge cases the kernel must
preserve (ISSUE 5 satellite coverage).

The contract under test: ``InvertedIndex.similar`` / ``SimilarityEngine
.topk`` return bit-identical results on the pruned host path and on the
fused kernel path (backend="ref"/"pallas") -- including tie ordering at
the k boundary -- and the kernel path is ONE engine dispatch."""

import numpy as np
import pytest

from repro.core import BitmapArena, RoaringBitmap
from repro.core.pairwise import METRICS, SimilarityEngine, _scores_host
from repro.data.index import InvertedIndex


def build_index(rng, n_terms=20, n_docs=600):
    hi = min(9, n_terms + 1)
    docs = [[f"t{t}" for t in rng.choice(n_terms, rng.integers(2, hi),
                                         replace=False)]
            for _ in range(n_docs)]
    return InvertedIndex().build(docs)


def brute_force(idx, term, k, metric):
    """Numpy oracle: float32 scores over every other term + stable
    argsort -- the definition the engine must reproduce exactly."""
    q = idx.postings.get(term, RoaringBitmap())
    terms = [t for t in idx.postings if t != term]
    inter = np.array([q.and_card(idx.postings[t]) for t in terms],
                     np.int64)
    cards = np.array([idx.postings[t].cardinality for t in terms],
                     np.int64)
    score = _scores_host(inter, q.cardinality, cards, metric)
    order = np.argsort(-score, kind="stable")[:k]
    return [(terms[i], float(score[i])) for i in order.tolist()]


@pytest.mark.parametrize("metric", METRICS)
def test_similar_matches_numpy_oracle(rng, metric):
    idx = build_index(rng)
    for term in ("t0", "t7", "t19"):
        want = brute_force(idx, term, 6, metric)
        assert idx.similar(term, 6, metric) == want
        assert idx.similar(term, 6, metric, backend="ref") == want
    assert idx.similar("t3", 6, metric, backend="pallas") == \
        brute_force(idx, "t3", 6, metric)


def test_score_ties_at_k_boundary(rng):
    """Duplicate posting lists produce exact score ties; ties must order
    by term insertion index on every backend."""
    base = rng.integers(0, 5000, 800, dtype=np.uint32)
    docs_of = {"q": base,
               "a": base[:500], "b": base[:500], "c": base[:500],
               "d": base[:500], "e": base[:100]}
    idx = InvertedIndex()
    for t, vals in docs_of.items():
        idx.postings[t] = RoaringBitmap.from_values(vals)
    idx.n_docs = 5000
    # a..d tie exactly; k=2 cuts through the tie group
    got = idx.similar("q", top_k=2)
    assert [t for t, _ in got] == ["a", "b"]
    assert got[0][1] == got[1][1]
    for backend in ("ref", "pallas"):
        assert idx.similar("q", top_k=2, backend=backend) == got
    got4 = idx.similar("q", top_k=4)
    assert [t for t, _ in got4] == ["a", "b", "c", "d"]
    assert idx.similar("q", top_k=4, backend="ref") == got4


def test_k_larger_than_candidate_set(rng):
    idx = build_index(rng, n_terms=7)
    got = idx.similar("t0", top_k=100)
    assert len(got) == len(idx.postings) - 1
    assert got == idx.similar("t0", top_k=100, backend="ref")
    assert [t for t, _ in got] == \
        [t for t, _ in brute_force(idx, "t0", 100, "jaccard")]
    assert idx.similar("t0", top_k=0) == []


def test_empty_term_and_empty_index(rng):
    idx = build_index(rng, n_terms=8)
    # unknown term: queries as an empty posting list, scores still total
    got = idx.similar("nope", top_k=3)
    assert len(got) == 3 and all(s == 0.0 for _, s in got)
    assert got == idx.similar("nope", top_k=3, backend="ref")
    # containment with an empty query: zero denominator scores 1.0
    c = idx.similar("nope", top_k=3, metric="containment")
    assert all(s == 1.0 for _, s in c)
    assert c == idx.similar("nope", top_k=3, metric="containment",
                            backend="ref")
    assert InvertedIndex().similar("x", top_k=5) == []


def test_engine_bitmap_query_and_all_empty(rng):
    bms = [RoaringBitmap.from_values(
        rng.integers(0, 1 << 17, 3000, dtype=np.uint32))
        for _ in range(6)]
    eng = SimilarityEngine(bms)
    q = RoaringBitmap.from_values(
        rng.integers(0, 1 << 17, 3000, dtype=np.uint32))
    idx_h, sc_h, in_h = eng.topk(q, 4)
    idx_r, sc_r, in_r = eng.topk(q, 4, backend="ref")
    assert np.array_equal(idx_h, idx_r)
    assert np.array_equal(sc_h, sc_r)
    assert np.array_equal(in_h, in_r)
    for i, inter in zip(idx_h.tolist(), in_h.tolist()):
        assert inter == q.and_card(bms[i])
    # member query excludes itself
    idx_m, _, _ = eng.topk(2, 10)
    assert 2 not in idx_m.tolist() and idx_m.size == 5
    # out-of-range member indices raise instead of slicing garbage
    # (negative python indexing would silently mix candidates)
    for bad in (-1, -2, len(bms)):
        with pytest.raises(IndexError):
            eng.topk(bad, 3)
    # an engine of empty bitmaps never dispatches and never crashes
    eng0 = SimilarityEngine([RoaringBitmap(), RoaringBitmap()])
    i0, s0, n0 = eng0.topk(RoaringBitmap(), 5)
    assert i0.size == 2 and np.all(n0 == 0)


def test_similar_is_one_dispatch(rng, monkeypatch):
    """The acceptance contract: score + select execute as ONE engine
    dispatch on kernel backends; the host path issues none."""
    from repro.kernels import ops as kops
    calls = []
    for name in ("similarity_topk", "bitset_pair_card", "bitset_pair_op",
                 "array_intersect_card", "array_bitset_probe",
                 "array_pair_masks", "bitset_op_card", "segment_reduce"):
        real = getattr(kops, name)

        def spy(*a, _real=real, _name=name, **k):
            calls.append(_name)
            return _real(*a, **k)

        monkeypatch.setattr(kops, name, spy)
    idx = build_index(rng)
    for backend in ("ref", "pallas"):
        calls.clear()
        idx.similar("t0", top_k=5, backend=backend)
        assert calls == ["similarity_topk"], (backend, calls)
    calls.clear()
    idx.similar("t1", top_k=5)                   # host path
    assert calls == [], calls


def test_engine_cache_invalidation(rng):
    idx = build_index(rng, n_terms=6)
    idx.similar("t0", top_k=3)
    assert idx._sim is not None
    idx.add_document(idx.n_docs, ["t0", "t5"])
    assert idx._sim is None                      # mutation drops the slab
    # rebuilt engine answers for the NEW postings, not the stale slab
    assert idx.similar("t0", top_k=3) == \
        brute_force(idx, "t0", 3, "jaccard")
    # direct edits of the public postings dict are caught by the
    # snapshot revalidation (no index-API call involved)
    idx.postings["clone"] = RoaringBitmap.from_values(
        idx.postings["t0"].to_array())
    got = idx.similar("t0", top_k=1)
    assert got[0] == ("clone", 1.0)
    idx.postings["t1"].add(5_000_000)            # in-place point update
    assert idx.similar("t1", top_k=3) == \
        brute_force(idx, "t1", 3, "jaccard")
    # content change that preserves BOTH object identity and
    # cardinality: caught by the bitmap mutation counter
    idx2 = InvertedIndex()
    idx2.postings["a"] = RoaringBitmap.from_values([0, 1])
    idx2.postings["b"] = RoaringBitmap.from_values([0, 1])
    idx2.n_docs = 10
    assert idx2.similar("a", 1)[0] == ("b", 1.0)
    idx2.postings["b"].remove(0)
    idx2.postings["b"].remove(1)
    idx2.postings["b"].add(2)
    idx2.postings["b"].add(3)
    assert idx2.similar("a", 1)[0][1] == 0.0


def test_pruning_never_changes_results(rng):
    """The bound-pruning planner must be invisible: heavy cardinality
    skew (the prunable regime) still matches the unpruned oracle."""
    bms = []
    for r in range(24):
        size = max(20, int(60_000 / (r + 1) ** 2))
        bms.append(RoaringBitmap.from_values(
            rng.integers(0, 1 << 18, size, dtype=np.uint32)))
    eng = SimilarityEngine(bms)
    for qi in (0, 5, 23):
        for metric in METRICS:
            idx_h, sc_h, in_h = eng.topk(qi, 6, metric)
            idx_r, sc_r, in_r = eng.topk(qi, 6, metric, backend="ref")
            assert np.array_equal(idx_h, idx_r), (qi, metric)
            assert np.array_equal(sc_h, sc_r), (qi, metric)
            assert np.array_equal(in_h, in_r), (qi, metric)


@pytest.mark.parametrize("metric", sorted(METRICS))
def test_topk_batch_matches_per_query_topk(rng, metric):
    """The server's similarity coalescing path: a vmapped batch on the
    kernel backend and the host loop must both equal per-query ``topk``
    exactly (indices, float32 scores, intersections)."""
    cands = [RoaringBitmap.from_values(
        rng.choice(1 << 17, int(rng.integers(30, 3000)),
                   replace=False).astype(np.uint32)) for _ in range(25)]
    eng = SimilarityEngine(cands)
    queries = [0, 7, 24,
               RoaringBitmap.from_values(
                   rng.choice(1 << 17, 500,
                              replace=False).astype(np.uint32)),
               RoaringBitmap()]
    for backend in ("ref", None, "host"):
        got = eng.topk_batch(queries, 6, metric, backend=backend)
        for q, (gi, gs, gn) in zip(queries, got):
            wi, ws, wn = eng.topk(q, 6, metric, backend=backend)
            assert np.array_equal(gi, wi)
            assert np.array_equal(gs, ws)
            assert np.array_equal(gn, wn)


def test_topk_batch_edge_cases(rng):
    eng = SimilarityEngine([RoaringBitmap.from_values(
        np.arange(100, dtype=np.uint32))])
    # member query of a 1-candidate engine: nothing left after exclusion
    out = eng.topk_batch([0], 5, backend="ref")
    assert out[0][0].size == 0
    assert eng.topk_batch([], 5, backend="ref") == []
    with pytest.raises(ValueError):
        eng.topk_batch([0], 5, metric="bogus", backend="ref")
    with pytest.raises(IndexError):
        eng.topk_batch([3], 5, backend="ref")


def test_arena_pallas_segment_map(rng):
    """The served layout: an arena-backed engine on the Pallas kernel
    sums per-row counts by its cached row-to-candidate map.  Answers
    equal the host sweep bit for bit, one row per candidate and after an
    edit gives one candidate a second row; the map is built once per
    layout, never per query, and a no-op refresh keeps it."""
    bms = [RoaringBitmap.from_values(
        rng.choice(1 << 16, int(rng.integers(50, 4000)),
                   replace=False).astype(np.uint32)) for _ in range(12)]
    eng = SimilarityEngine(bms, arena=BitmapArena())
    assert eng.segment_map_builds == 1
    assert np.array_equal(eng.seg, np.arange(12))
    q = RoaringBitmap.from_values(
        rng.choice(1 << 17, 3000, replace=False).astype(np.uint32))
    q.add((1 << 16) + 7)                         # meets the edit below
    queries = [0, 5, 11, q, 4]

    def same_as_host():
        got = eng.topk_batch(queries, 4, "jaccard", backend="pallas")
        for query, res in zip(queries, got):
            want = eng.topk(query, 4, "jaccard", backend="host")
            assert all(np.array_equal(a, b) for a, b in zip(res, want))

    same_as_host()
    for i in range(20):
        eng.topk(i % 12, 3, "cosine", backend="pallas")
    assert eng.segment_map_builds == 1
    bms[4].add((1 << 16) + 7)                    # new chunk: a second row
    assert eng.refresh() is True
    assert eng.segment_map_builds == 2
    assert eng.seg.tolist() == [0, 1, 2, 3, 4, 4, 5, 6, 7, 8, 9, 10, 11]
    same_as_host()
    assert eng.refresh() is False
    assert eng.segment_map_builds == 2
