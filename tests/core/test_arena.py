"""Device-resident bitmap arena: lifecycle, bit-identity, and the
zero-transfer contract.

The arena's correctness claim is structural (container identity, not
generation counters, gates row reuse), so the tests here hammer exactly
the places that could silently go wrong: adopt/patch/free accounting,
every wide op with and without an arena across mixed container kinds,
seeded mutation/query interleaving against the cold host path, the
warm-query ZERO host->device row transfer assertion, the single-row
peel fix (resident singletons must stay on device), SimilarityEngine
arena views with in-place refresh, and the query server's generation-
revalidating ``slab_mismatch`` rung."""

import numpy as np
import pytest

from repro.core import BitmapArena, RoaringBitmap
from repro.core import aggregate
from repro.core import containers as C
from repro.core.pairwise import SimilarityEngine
from repro.core.tensor import RoaringTensor
from repro.data.index import InvertedIndex
from repro.serve.faults import FaultInjector
from repro.serve.query_server import Query, QueryServer


def bm(values):
    return RoaringBitmap.from_values(np.asarray(list(values), np.uint32))


def mixed_bitmaps(rng, k=8):
    """Array/bitset/run mix across overlapping chunk keys."""
    out = []
    for i in range(k):
        kind = ("array", "bitset", "run")[i % 3]
        if kind == "array":
            out.append(bm(rng.choice(1 << 18, 300, replace=False)))
        elif kind == "bitset":
            out.append(bm(rng.choice(1 << 17, 30000, replace=False)))
        else:
            starts = rng.choice(1 << 17, 20) & ~np.uint32(0)
            vals = np.unique(np.concatenate(
                [np.arange(s, s + 400) for s in starts]))
            out.append(bm(vals))
    return out


# ---------------------------------------------------------------------------
# lifecycle: adopt / lookup / patch / free
# ---------------------------------------------------------------------------

class TestLifecycle:
    def test_adopt_and_lookup_content(self):
        rng = np.random.default_rng(0)
        bms = mixed_bitmaps(rng)
        arena = BitmapArena(capacity=2)          # forces growth
        n = arena.adopt_many(bms)
        assert n == sum(len(b.containers) for b in bms) or n > 0
        for b in bms:
            assert arena.resident(b)
            for c in b.containers:
                rid = arena.lookup(c)
                assert rid is not None and rid > 0
                assert np.array_equal(arena.host_row(rid),
                                      C.container_words64(c))
        # row 0 is the reserved all-zero padding target
        assert not arena.host_row(0).any()
        # warm re-adopt is a no-op
        assert arena.adopt_many(bms) == 0

    def test_incremental_patch_is_minimal(self):
        rng = np.random.default_rng(1)
        bms = mixed_bitmaps(rng)
        arena = BitmapArena()
        arena.adopt_many(bms)
        arena.device_slab()
        up0 = arena.stats.rows_uploaded
        # one value added to one container -> exactly one row repatches
        bms[1].add(3)                            # bitset container edit
        changed = arena.adopt(bms[1])
        assert changed == 1
        arena.device_slab()
        assert arena.stats.rows_uploaded == up0 + 1
        assert arena.stats.rows_patched == 1
        # the device slab matches the host mirror after the patch
        dev = np.asarray(arena.device_slab())[: arena._n]
        host = arena._host[: arena._n].view(np.uint32).reshape(-1, 2048)
        assert np.array_equal(dev, host)

    def test_copy_on_write_patch(self):
        """In-flight consumers keep the pre-patch slab (functional
        update allocates a fresh device buffer)."""
        arena = BitmapArena()
        b = bm(range(70000, 90000))
        arena.adopt(b)
        slab_before = arena.device_slab()
        snapshot = np.asarray(slab_before).copy()
        b.add(1)                                  # new chunk 0 row
        arena.adopt(b)
        slab_after = arena.device_slab()
        assert slab_after is not slab_before
        assert np.array_equal(np.asarray(slab_before), snapshot)

    def test_release_and_row_reuse(self):
        arena = BitmapArena()
        a = bm(range(100))
        arena.adopt(a)
        rows = arena.n_rows
        # removing the only chunk frees its row
        for v in range(100):
            a.remove(v)
        arena.adopt(a)
        assert arena.n_rows == rows - 1
        assert arena.stats.rows_freed == 1
        # a new adoption reuses the freed row
        b = bm(range(50))
        arena.adopt(b)
        assert arena.n_rows == rows
        rid = arena.lookup(b.containers[0])
        assert np.array_equal(arena.host_row(rid),
                              C.container_words64(b.containers[0]))
        arena.release(a)
        arena.release(b)
        assert arena.n_rows == 1                 # only the zero row left

    def test_shared_container_refcount(self):
        """Two bitmaps sharing a container object share one row."""
        a = bm(range(5000, 9000))
        shared = a.containers[0]
        b = RoaringBitmap([0], [shared])
        arena = BitmapArena()
        arena.adopt(a)
        rows = arena.n_rows
        arena.adopt(b)
        assert arena.n_rows == rows              # no second promotion
        arena.release(a)
        assert arena.lookup(shared) is not None  # b still holds the row
        arena.release(b)
        assert arena.lookup(shared) is None


# ---------------------------------------------------------------------------
# bulk frozen adoption (PR 8): one batched conversion, one transfer
# ---------------------------------------------------------------------------

class TestAdoptFrozen:
    def _frozen_twins(self, rng, k=9):
        """(eager bitmaps, frozen view-backed deserialized twins)."""
        from repro.core import deserialize_frozen, serialize_frozen
        bms = [b.run_optimize() for b in mixed_bitmaps(rng, k)]
        froz = [deserialize_frozen(serialize_frozen(b)) for b in bms]
        return bms, froz

    def test_bulk_rows_match_per_container_promotion(self):
        rng = np.random.default_rng(5)
        bms, froz = self._frozen_twins(rng)
        bulk, eager = BitmapArena(), BitmapArena()
        n_bulk = bulk.adopt_frozen(froz)
        eager.adopt_many(bms)
        assert n_bulk == sum(len(b.containers) for b in froz)
        assert bulk.n_rows == eager.n_rows
        for b in froz:
            assert bulk.resident(b)
            for c in b.containers:
                rid = bulk.lookup(c)
                assert np.array_equal(bulk.host_row(rid),
                                      C.container_words64(c))

    def test_upload_accounting_and_warm_requery(self):
        """Cold start = exactly ONE slab upload; the first and every
        later query move zero additional rows."""
        rng = np.random.default_rng(6)
        bms, froz = self._frozen_twins(rng)
        arena = BitmapArena()
        arena.adopt_frozen(froz)
        arena.sync()
        up0 = arena.stats.rows_uploaded
        assert up0 == arena._n                   # one bulk upload
        want = RoaringBitmap.or_many(bms)
        for _ in range(2):
            got = aggregate.or_many(froz, backend="ref", arena=arena)
            assert got == want
            assert arena.stats.rows_uploaded == up0
        # re-adopting the same snapshot is a no-op
        assert arena.adopt_frozen(froz) == 0

    def test_batched_after_slab_exists_is_one_scatter(self):
        rng = np.random.default_rng(7)
        bms, froz = self._frozen_twins(rng, k=4)
        arena = BitmapArena()
        arena.adopt_frozen(froz[:2])
        arena.sync()
        patched0 = arena.stats.rows_patched
        arena.adopt_frozen(froz[2:])             # second wave
        arena.sync()
        n_new = sum(len(b.containers) for b in froz[2:])
        assert arena.stats.rows_patched == patched0 + n_new
        got = aggregate.xor_many(froz, backend="ref", arena=arena)
        assert got == RoaringBitmap.xor_many(bms)

    def test_bulk_of_many_multi_container_bitmaps_equals_adopt(self):
        """Hundreds of bitmaps over several chunks -- arrays, runs and
        bitsets, more than one conversion block of array and run rows --
        promote in one call to the same rows as adopting each bitmap."""
        rng = np.random.default_rng(11)
        bms = []
        for i in range(240):
            parts = []
            for key in rng.choice(8, int(rng.integers(2, 6)), replace=False):
                if (i + key) % 3 == 0:
                    lo = int(rng.integers(0, 60000))
                    vals = np.arange(lo, lo + int(rng.integers(1, 5000)))
                else:
                    vals = rng.integers(0, 1 << 16, int(rng.choice(
                        [3, 700, 4000, 9000])))
                parts.append((int(key) << 16) + vals)
            bms.append(bm(np.concatenate(parts)).run_optimize())
        kinds = {c.kind for b in bms for c in b.containers}
        assert kinds == {"array", "run", "bitset"}
        assert sum(c.kind != "bitset" for b in bms
                   for c in b.containers) > 2 * 256
        bulk, each = BitmapArena(), BitmapArena()
        bulk.adopt_frozen(bms)
        for b in bms:
            each.adopt(b)
        for b in bms:
            for c in b.containers:
                assert np.array_equal(bulk.host_row(bulk.lookup(c)),
                                      each.host_row(each.lookup(c)))

    def test_single_bitmap_and_shared_rows(self):
        arena = BitmapArena()
        a = bm(range(5000, 9000))
        assert arena.adopt_frozen(a) == 1        # single-bitmap form
        shared = a.containers[0]
        b = RoaringBitmap([0], [shared])
        assert arena.adopt_frozen([b]) == 0      # row already resident
        arena.release(a)
        assert arena.lookup(shared) is not None  # refcounted by b
        arena.release(b)
        assert arena.lookup(shared) is None


# ---------------------------------------------------------------------------
# wide ops: bit-identity with and without an arena
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", [None, "ref"])
class TestWideOpParity:
    def test_all_ops(self, backend):
        rng = np.random.default_rng(2)
        bms = mixed_bitmaps(rng)
        arena = BitmapArena()
        arena.adopt_many(bms)
        assert aggregate.or_many(bms, backend=backend, arena=arena) == \
            aggregate.or_many(bms, backend=backend)
        assert aggregate.xor_many(bms, backend=backend, arena=arena) == \
            aggregate.xor_many(bms, backend=backend)
        assert aggregate.and_many(bms[:4], backend=backend,
                                  arena=arena) == \
            aggregate.and_many(bms[:4], backend=backend)
        assert aggregate.andnot_many(bms[1], bms[2:6], backend=backend,
                                     arena=arena) == \
            aggregate.andnot_many(bms[1], bms[2:6], backend=backend)
        for t in (2, 3, len(bms)):
            assert aggregate.threshold_many(
                bms, t, backend=backend, arena=arena) == \
                aggregate.threshold_many(bms, t, backend=backend)

    def test_weighted_threshold(self, backend):
        rng = np.random.default_rng(3)
        bms = mixed_bitmaps(rng, 6)
        w = [1, 3, 2, 1, 5, 2]
        arena = BitmapArena()
        arena.adopt_many(bms)
        for t in (3, 7):
            assert aggregate.threshold_many(
                bms, t, weights=w, backend=backend, arena=arena) == \
                aggregate.threshold_many(bms, t, weights=w,
                                         backend=backend)

    def test_cold_containers_stage_correctly(self, backend):
        """Bitmaps never adopted still compute correctly through an
        arena-planned dispatch (mixed resident + staged rows)."""
        rng = np.random.default_rng(4)
        bms = mixed_bitmaps(rng)
        arena = BitmapArena()
        arena.adopt_many(bms[:4])                # half resident, half cold
        assert aggregate.or_many(bms, backend=backend, arena=arena) == \
            aggregate.or_many(bms, backend=backend)
        assert aggregate.threshold_many(
            bms, 3, backend=backend, arena=arena) == \
            aggregate.threshold_many(bms, 3, backend=backend)

    def test_execute_plans_mixed_arenas(self, backend):
        """Coalesced plan batches only group plans sharing an arena."""
        rng = np.random.default_rng(5)
        bms = mixed_bitmaps(rng)
        arena = BitmapArena()
        arena.adopt_many(bms)
        plans = [
            aggregate.plan_wide("or", bms[:5], backend=backend,
                                arena=arena),
            aggregate.plan_wide("or", bms[3:], backend=backend),
            aggregate.plan_wide("threshold", bms, 2, backend=backend,
                                arena=arena),
        ]
        got = aggregate.execute_plans(plans, backend=backend)
        assert got[0] == aggregate.or_many(bms[:5], backend=backend)
        assert got[1] == aggregate.or_many(bms[3:], backend=backend)
        assert got[2] == aggregate.threshold_many(bms, 2,
                                                  backend=backend)

    def test_execute_plan_host_resolves_ids(self, backend):
        """The server's host-degradation twin resolves arena row ids
        through the HOST mirror (no jax) and stays bit-identical."""
        rng = np.random.default_rng(6)
        bms = mixed_bitmaps(rng)
        arena = BitmapArena()
        arena.adopt_many(bms)
        plan = aggregate.plan_wide("or", bms, backend=backend,
                                   arena=arena)
        assert aggregate.execute_plan_host(plan) == \
            aggregate.or_many(bms, backend=backend)


# ---------------------------------------------------------------------------
# the zero-transfer contract + the single-row peel fix
# ---------------------------------------------------------------------------

def dense_postings(n, seed=29):
    """Single-chunk dense bitsets (the serving-shaped worst case for
    per-call staging)."""
    rng = np.random.default_rng(seed)
    return [bm(rng.choice(1 << 16, 20000, replace=False))
            for _ in range(n)]


class TestZeroTransfer:
    def test_warm_requery_moves_no_rows(self):
        bms = dense_postings(16)
        arena = BitmapArena()
        arena.adopt_many(bms)
        first = aggregate.or_many(bms, backend="ref", arena=arena)
        uploaded = arena.stats.rows_uploaded
        staged = arena.stats.host_rows_staged
        for _ in range(3):
            again = aggregate.or_many(bms, backend="ref", arena=arena)
            assert again == first
        # the dispatch-count contract: warm re-queries perform ZERO
        # host->device row transfers and stage no host rows
        assert arena.stats.rows_uploaded == uploaded
        assert arena.stats.host_rows_staged == staged == 0
        assert arena.stats.device_gathers >= 4

    def test_peel_keeps_resident_singletons_on_device(self):
        """A single-row segment whose row is arena-resident must NOT
        fall back to the host popcount peel (the PR 4 peel bypassed a
        warm arena); host-ndarray singletons still peel."""
        b = bm(np.arange(0, 50000, 3))           # one dense chunk 0 bitset
        arena = BitmapArena()
        arena.adopt(b)
        rid = arena.lookup(b.containers[0])
        arena.device_slab()
        up0 = arena.stats.rows_uploaded
        out = aggregate._dispatch([0], [[rid]], "or", 0, "ref",
                                  arena=arena)
        assert arena.stats.rows_uploaded == up0      # nothing re-staged
        assert arena.stats.host_rows_staged == 0
        assert arena.stats.device_gathers == 1       # device path taken
        got = RoaringBitmap([0], [out[0]])
        assert got == b
        # the host twin still peels (no dispatch)
        g0 = arena.stats.device_gathers
        row = C.container_words64(b.containers[0])
        out2 = aggregate._dispatch([0], [[row]], "or", 0, "ref",
                                   arena=arena)
        assert RoaringBitmap([0], [out2[0]]) == b
        assert arena.stats.device_gathers == g0      # peeled on host


# ---------------------------------------------------------------------------
# seeded mutation/query interleaving vs the cold host path
# ---------------------------------------------------------------------------

class TestMutationQueryInterleaving:
    @pytest.mark.parametrize("seed", [7, 19, 43])
    def test_arena_index_tracks_cold_index(self, seed):
        rng = np.random.default_rng(seed)
        docs = [[f"t{j}" for j in rng.choice(24, rng.integers(2, 8))]
                for _ in range(2000)]
        cold = InvertedIndex().build(docs)
        warm = InvertedIndex(arena=BitmapArena()).build(docs)
        terms = [f"t{j}" for j in range(24)]
        for step in range(40):
            action = rng.integers(0, 5)
            if action == 0:                      # add a document
                doc = int(rng.integers(0, 4000))
                ts = [terms[j] for j in rng.choice(24, 3)]
                cold.add_document(doc, ts)
                warm.add_document(doc, ts)
            elif action == 1:                    # point removal
                t = terms[int(rng.integers(0, 24))]
                if cold.postings.get(t) and len(cold.postings[t]):
                    v = cold.postings[t].to_array()[0]
                    cold.postings[t].remove(int(v))
                    warm.postings[t].remove(int(v))
            elif action == 2:                    # run_optimize sweep
                cold.optimize()
                warm.optimize()
            qt = [terms[j] for j in rng.choice(24, 4, replace=False)]
            assert cold.query_and(*qt[:2]) == warm.query_and(*qt[:2])
            assert cold.query_or(*qt) == warm.query_or(*qt)
            assert cold.query_xor(*qt[:3]) == warm.query_xor(*qt[:3])
            assert cold.query_threshold(qt, 2) == \
                warm.query_threshold(qt, 2)
            assert cold.query_andnot(qt[0], *qt[1:3]) == \
                warm.query_andnot(qt[0], *qt[1:3])
            if step % 10 == 0:
                assert cold.similar(qt[0], 5) == warm.similar(qt[0], 5)

    def test_warm_index_requery_zero_transfer(self):
        rng = np.random.default_rng(8)
        docs = [[f"t{j}" for j in rng.choice(16, 6, replace=False)]
                for _ in range(30000)]            # dense bitset postings
        ix = InvertedIndex(arena=BitmapArena()).build(docs)
        want = ix.query_or("t0", "t1", "t2", "t3")
        up = ix.arena.stats.rows_uploaded
        staged = ix.arena.stats.host_rows_staged
        for _ in range(3):
            assert ix.query_or("t0", "t1", "t2", "t3") == want
            assert len(ix.query_and("t0", "t1"))
        assert ix.arena.stats.rows_uploaded == up
        assert ix.arena.stats.host_rows_staged == staged


# ---------------------------------------------------------------------------
# SimilarityEngine arena views
# ---------------------------------------------------------------------------

class TestEngineArenaView:
    def test_parity_and_refresh(self):
        rng = np.random.default_rng(9)
        bms = [bm(rng.choice(1 << 17, 4000 + 300 * i, replace=False))
               for i in range(10)]
        arena = BitmapArena()
        cold = SimilarityEngine(bms)
        warm = SimilarityEngine(bms, arena=arena)
        assert np.array_equal(cold.rows, warm.rows)
        q = bm(rng.choice(1 << 17, 2500, replace=False))
        for backend in (None, "ref"):
            for query in (3, q):
                a = cold.topk(query, 5, "jaccard", backend=backend)
                b = warm.topk(query, 5, "jaccard", backend=backend)
                assert all(np.array_equal(x, y) for x, y in zip(a, b))
        # refresh: only the edited row repatches; results track a fresh
        # engine bit for bit
        warm._device()
        up0 = arena.stats.rows_uploaded
        bms[2].add(1 << 18)                      # new chunk: exactly 1 row
        assert warm.refresh() is True
        assert warm.refresh() is False
        warm._device()
        assert arena.stats.rows_uploaded == up0 + 1
        fresh = SimilarityEngine(bms)
        a = fresh.topk(q, 5, backend="ref")
        b = warm.topk(q, 5, backend="ref")
        assert all(np.array_equal(x, y) for x, y in zip(a, b))

    def test_refresh_requires_arena(self):
        eng = SimilarityEngine([bm(range(10))])
        with pytest.raises(ValueError):
            eng.refresh()

    def test_index_preserves_engine_across_mutation(self):
        rng = np.random.default_rng(10)
        docs = [[f"t{j}" for j in rng.choice(12, 4, replace=False)]
                for _ in range(1500)]
        ix = InvertedIndex(arena=BitmapArena()).build(docs)
        before = ix._sim_engine()[1]
        ix.add_document(9000, ["t1", "t2"])      # existing terms only
        after = ix._sim_engine()[1]
        assert after is before                   # refreshed in place
        cold = InvertedIndex().build(docs)
        cold.add_document(9000, ["t1", "t2"])
        assert cold.similar("t1", 5) == ix.similar("t1", 5)
        ix.add_document(9001, ["brand_new"])     # term set changed
        assert ix._sim_engine()[1] is not before


# ---------------------------------------------------------------------------
# query server: generation revalidation replaces whole-slab drops
# ---------------------------------------------------------------------------

class TestServerRevalidation:
    def _indices(self, seed=11):
        rng = np.random.default_rng(seed)
        docs = [[f"t{j}" for j in rng.choice(40, rng.integers(2, 10))]
                for _ in range(4000)]
        cold = InvertedIndex().build(docs)
        warm = InvertedIndex(arena=BitmapArena()).build(docs)
        return cold, warm

    def test_slab_mismatch_repatches_rows(self):
        cold_ix, warm_ix = self._indices()
        faults = FaultInjector.script({"slab_mismatch": [True]})
        srv = QueryServer(warm_ix, backend="ref", faults=faults)
        ref = QueryServer(cold_ix, backend="ref")
        assert srv.arena is warm_ix.arena        # picked up from the index
        qs = [Query.and_("t1", "t2"), Query.or_("t3", "t4", "t5"),
              Query.threshold(("t1", "t2", "t3"), 2),
              Query.similar("t2", 5)]
        ta = [srv.submit(q) for q in qs]
        tb = [ref.submit(q) for q in qs]
        eng = warm_ix._sim_engine()[1]
        # concurrent mutation between admission and dispatch
        warm_ix.postings["t1"].add(4999)
        cold_ix.postings["t1"].add(4999)
        srv.run_until_idle()
        ref.run_until_idle()
        for a, b in zip(ta, tb):
            assert a.result.ok and b.result.ok
            assert a.result.value == b.result.value
        st = srv.stats()
        assert st.replans == 1
        assert st.rows_repatched >= 1            # incremental, not a drop
        assert warm_ix._sim_engine()[1] is eng   # engine never dropped

    def test_no_arena_keeps_drop_semantics(self):
        cold_ix, _ = self._indices(12)
        faults = FaultInjector.script({"slab_mismatch": [True]})
        srv = QueryServer(cold_ix, backend="ref", faults=faults)
        t = srv.submit(Query.similar("t1", 3))
        cold_ix._sim_engine()
        assert cold_ix._sim is not None
        srv.run_until_idle()
        assert t.result.ok
        assert srv.stats().replans == 1
        assert srv.stats().rows_repatched == 0


# ---------------------------------------------------------------------------
# RoaringTensor bridge
# ---------------------------------------------------------------------------

class TestTensorBridge:
    def test_to_arena_roundtrip(self):
        rng = np.random.default_rng(13)
        bms = mixed_bitmaps(rng, 5)
        rt = RoaringTensor.from_bitmaps(bms)
        arena, twins = rt.to_arena()
        assert len(twins) == 5
        for orig, twin in zip(bms, twins):
            assert orig == twin
            assert arena.resident(twin)
        assert aggregate.or_many(twins, backend="ref", arena=arena) == \
            aggregate.or_many(bms, backend="ref")
