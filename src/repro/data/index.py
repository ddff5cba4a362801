"""A small inverted index on Roaring bitmaps -- the paper's motivating
application (section 1: "inverted indexes map query terms to document
identifiers").  Used by examples/analytics_index.py and the benchmarks.
"""

from __future__ import annotations

import numpy as np

from repro.core import RoaringBitmap


class InvertedIndex:
    """Term -> document-id postings on Roaring bitmaps.

    Every query routes through a batched planner: the boolean surface
    (``query_and`` .. ``query_andnot``) plans ONE segmented-kernel
    dispatch per query via ``repro.core.aggregate``; the similarity
    surface (``similar``) runs on a cached ``SimilarityEngine`` slab,
    one fused score+select dispatch per query on kernel backends.  See
    docs/ARCHITECTURE.md for the paper-section -> module map.

    Unknown-term / empty-input contract (uniform across EVERY query
    entry point, relied on by the query server's admission path):
    a term absent from the index queries as an EMPTY posting list --
    never a ``KeyError`` -- and an empty term list yields an empty
    result.  Consequences: ``query_and``/``query_or``/``query_xor``
    with no or only-unknown terms return the empty bitmap;
    ``query_andnot`` with an unknown ``keep`` is empty and unknown
    ``drops`` subtract nothing; ``query_threshold`` prunes unknown
    terms' (zero) contributions; ``count_and`` returns 0;
    ``jaccard`` follows the set convention (two empty sets -> 1.0,
    empty vs non-empty -> 0.0); ``similar`` scores an unknown term as
    an empty query (all scores 0) and returns a full-length, validly
    ordered list.

    ``arena``: an optional ``core.arena.BitmapArena``.  When present,
    query entry points adopt their term postings into it first, so
    container rows live device-resident across queries (warm re-queries
    ship no container payloads over PCIe) and the cached
    ``SimilarityEngine`` becomes an arena view whose ``slab_mismatch``
    recovery is a generation revalidation -- only edited rows repatch --
    instead of a full slab rebuild (docs/MEMORY.md has the lifecycle).
    Results are bit-identical with or without an arena."""

    def __init__(self, *, arena=None):
        self.postings: dict[str, RoaringBitmap] = {}
        self.n_docs = 0
        self.arena = arena
        # cached (snapshot, terms, SimilarityEngine); the snapshot
        # revalidates against direct postings edits -- see _sim_engine
        self._sim = None

    @classmethod
    def from_postings(cls, postings, n_docs: int, *,
                      arena=None) -> "InvertedIndex":
        """Wrap pre-built posting lists -- the snapshot cold-start
        constructor (``load_index`` / ``StreamingIndexBuilder.finalize``
        route through here).

        Args: ``postings`` a mapping of term -> RoaringBitmap.  A lazy
        ``serde.LazyBitmaps`` mapping (what ``read_snapshot`` returns)
        is kept AS the postings store, so entries stay unmaterialized
        until a query touches them; any other mapping is copied into a
        plain dict.  ``n_docs`` is the document-id space size.
        ``arena``: an optional BitmapArena -- when given, ALL postings
        are materialized and bulk-promoted via ``arena.adopt_frozen``
        (one batched conversion + one device transfer) so every query
        is warm from the start; without one, cold start defers
        per-entry work entirely (the lazy first-query path the
        ``cold_start`` benchmark gates).

        Returns the index.  Complexity: O(1) without an arena; with
        one, O(total payload bytes) host work + one host->device
        transfer.  See docs/FORMAT.md for the on-disk layouts this
        pairs with.
        """
        from repro.core import serde
        idx = cls(arena=arena)
        idx.postings = (postings if isinstance(postings, serde.LazyBitmaps)
                        else dict(postings))
        idx.n_docs = int(n_docs)
        if arena is not None:
            arena.adopt_frozen(idx.postings.values())
        return idx

    def add_document(self, doc_id: int, terms) -> None:
        if self.arena is None:
            self._sim = None                      # postings changed
        # with an arena, _sim_engine revalidates generations instead
        self.n_docs = max(self.n_docs, doc_id + 1)
        for t in set(terms):
            bm = self.postings.get(t)
            if bm is None:
                bm = self.postings[t] = RoaringBitmap()
            bm.add(doc_id)

    def build(self, docs: list[list[str]]) -> "InvertedIndex":
        # columnar build: term -> sorted doc ids, one from_values each
        self._sim = None
        by_term: dict[str, list[int]] = {}
        for i, terms in enumerate(docs):
            for t in set(terms):
                by_term.setdefault(t, []).append(i)
        self.n_docs = len(docs)
        for t, ids in by_term.items():
            self.postings[t] = RoaringBitmap.from_values(
                np.asarray(ids, np.uint32))
        return self

    def optimize(self):
        if self.arena is None:
            self._sim = None
        for bm in self.postings.values():
            bm.run_optimize()
        return self

    # query surface ------------------------------------------------------
    def _get(self, term: str) -> RoaringBitmap:
        """Postings for ``term``; an unknown term is an empty posting
        list (the class-level contract: no KeyError, ever)."""
        return self.postings.get(term, RoaringBitmap())

    def _adopt(self, bms: list[RoaringBitmap]) -> list[RoaringBitmap]:
        """Adopt query operands into the arena (no-op without one).
        Only non-empty bitmaps register: the fresh empties ``_get``
        returns for unknown terms are per-call temporaries that must not
        pin arena rows."""
        if self.arena is not None:
            for bm in bms:
                if bm.containers:
                    self.arena.adopt(bm)
        return bms

    # query_and/query_or/query_xor/query_threshold all route through the
    # wide-aggregation planner (repro.core.aggregate): one fused kernel
    # dispatch per query regardless of the number of terms.
    def query_and(self, *terms) -> RoaringBitmap:
        """Documents matching ALL ``terms``: one fused dispatch with
        cardinality-ascending pruning (docs/ARCHITECTURE.md section 3).
        Unknown terms are empty postings, so the result is empty."""
        return RoaringBitmap.and_many(
            self._adopt([self._get(t) for t in terms]), arena=self.arena)

    def query_or(self, *terms) -> RoaringBitmap:
        return RoaringBitmap.or_many(
            self._adopt([self._get(t) for t in terms]), arena=self.arena)

    def query_xor(self, *terms) -> RoaringBitmap:
        return RoaringBitmap.xor_many(
            self._adopt([self._get(t) for t in terms]), arena=self.arena)

    def query_threshold(self, terms, t: int, weights=None) -> RoaringBitmap:
        """Documents whose matched terms reach a total score of ``t``
        (T-occurrence query, Kaser & Lemire); optional per-term integer
        ``weights`` rank terms without leaving the one-dispatch plan."""
        return RoaringBitmap.threshold_many(
            self._adopt([self._get(term) for term in terms]), t,
            weights=weights, arena=self.arena)

    def query_andnot(self, keep: str, *drops: str) -> RoaringBitmap:
        """Documents matching ``keep`` and none of ``drops`` -- a
        difference chain planned as one fused dispatch (the union of the
        dropped postings is never materialized)."""
        ops = self._adopt([self._get(keep)] + [self._get(d) for d in drops])
        return RoaringBitmap.andnot_many(ops[0], ops[1:],
                                         arena=self.arena)

    def query_and_of_or(self, *clauses) -> RoaringBitmap:
        """Documents that hold a term of every clause: the AND of the
        clauses' ORs (conjunctive normal form, e.g. a star-schema
        WHERE clause), planned once -- host-anchored where a clause is
        sparse, one device reduction for the rest
        (``repro.core.aggregate.plan_wide("and_of_or", ...)``).  Each
        clause is a non-empty iterable of terms."""
        from repro.core import aggregate
        plan = aggregate.plan_wide(
            "and_of_or", [self._adopt([self._get(t) for t in c])
                          for c in clauses], arena=self.arena)
        return aggregate.execute_plans([plan])[0]

    def count_and(self, a: str, b: str) -> int:
        return self._get(a).and_card(self._get(b))  # fast count, sec 5.9

    def jaccard(self, a: str, b: str) -> float:
        return self._get(a).jaccard(self._get(b))

    def _sim_engine(self, mesh=None):
        """Cached similarity engine over every posting list, rebuilt
        lazily after any postings mutation.  Mutations through the index
        API drop the cache eagerly; direct edits of the public
        ``postings`` dict (replaced bitmaps, new terms, point updates)
        are caught by an O(terms) snapshot of term names plus each
        bitmap's identity, mutation counter (``RoaringBitmap._version``,
        bumped by every add/remove/run_optimize), and cardinality.
        Only hand-assembled aliasing -- a DIFFERENT bitmap object
        recycled at the same address with equal version and cardinality
        -- could escape revalidation.

        With an arena, a stale snapshot over the SAME term set and
        bitmap objects refreshes the engine in place (``refresh()``:
        the arena repatches only the edited rows) instead of rebuilding
        the slab; term-set or object changes still rebuild.

        ``mesh``: optional 1-D ``("wide",)`` mesh.  With more than one
        device the engine runs the sharded per-shard-slab path (requires
        an arena-backed index); engines are cached per mesh, so sharded
        and single-device engines over the same postings coexist."""
        key = None
        if mesh is not None:
            from repro.dist import ctx
            m, size, _ = ctx.resolve_wide(mesh)
            if size > 1:
                if self.arena is None:
                    raise ValueError(
                        "similar(mesh=) requires an arena-backed index")
                key = m
        snap = tuple((t, id(bm), bm._version, bm.cardinality)
                     for t, bm in self.postings.items())
        cache = self._sim if isinstance(self._sim, dict) else {}
        ent = cache.get(key)
        if ent is None or ent[0] != snap:
            from repro.core.pairwise import SimilarityEngine
            terms = list(self.postings)
            if (self.arena is not None and ent is not None
                    and ent[1] == terms
                    and all(self.postings[t] is bm for t, bm in
                            zip(terms, ent[2]._bitmaps))):
                eng = ent[2]
                eng.refresh()
                ent = (snap, terms, eng)
            else:
                ent = (snap, terms,
                       SimilarityEngine((self.postings[t] for t in terms),
                                        arena=self.arena, mesh=key))
            cache[key] = ent
            self._sim = cache
        return ent[1], ent[2]

    def similar(self, term: str, top_k: int = 10,
                metric: str = "jaccard", *,
                backend: str | None = None,
                mesh=None) -> list[tuple[str, float]]:
        """Top-k terms most similar to ``term``: one fused score+select
        kernel dispatch over a device-resident candidate slab (kernel
        backends) or a bound-pruned vectorized sweep (CPU) -- see
        ``repro.core.pairwise.SimilarityEngine`` and docs/ARCHITECTURE.md.
        The slab is cached across queries and rebuilt after mutations.

        Args: ``term`` query term (an unknown term queries as an empty
        posting list); ``top_k`` results wanted (clamped to the term
        count); ``metric`` is "jaccard" (|A∩B| / |A∪B|), "cosine"
        (|A∩B| / sqrt(|A||B|)) or "containment" (|A∩B| / |A|, the query
        side); ``backend`` forces the kernel ("pallas"/"ref") or host
        (CPU default) path -- results are bit-identical either way;
        ``mesh`` a 1-D ``("wide",)`` mesh to run the sharded per-shard-
        slab path (requires an arena-backed index; a 1-device mesh
        degrades to the single-device engine) -- results stay
        bit-identical, including tie order.

        Returns [(term, score)] best-first; score ties order by index
        insertion order.  Complexity: one dispatch per query; host path
        skips every candidate whose cardinality bound cannot reach the
        running k-th score."""
        from repro.core.pairwise import METRICS
        if metric not in METRICS:
            raise ValueError(metric)
        terms, eng = self._sim_engine(mesh=mesh)
        if term in self.postings:
            query = terms.index(term)
        else:
            query = self._get(term)
        idx, score, _ = eng.topk(query, top_k, metric, backend=backend)
        return [(terms[i], float(s)) for i, s in zip(idx.tolist(),
                                                     score.tolist())]

    def memory_bytes(self) -> int:
        return sum(bm.memory_bytes() for bm in self.postings.values())


def load_index(path, *, arena=None, mmap: bool = True) -> InvertedIndex:
    """Map an on-disk snapshot archive straight into a queryable index.

    The cold-start path (docs/FORMAT.md section 3): the archive written
    by ``StreamingIndexBuilder.finalize`` (or ``serde.write_snapshot``)
    is mapped read-only, every posting list becomes numpy views over
    the mapped buffer (zero payload copies, pages fault in on first
    touch), and -- when ``arena`` is given -- the whole set is promoted
    to the device slab in one batched transfer.

    Args: ``path`` the snapshot file; ``arena`` optional BitmapArena
    for device-warm queries; ``mmap=False`` reads the file into memory
    instead (same views, private buffer).

    Returns an InvertedIndex whose ``n_docs`` is the archive's ``meta``
    field.  Raises ``ValueError`` on a corrupt archive.  Complexity:
    O(terms + containers) directory work; payload bytes are only
    touched by queries (or the arena promotion).
    """
    from repro.core import serde
    snap = serde.read_snapshot(path, mmap=mmap)
    return InvertedIndex.from_postings(snap.bitmaps, snap.meta,
                                       arena=arena)
