#!/usr/bin/env python3
"""Chip smoke test: serve a warm, arena-backed inverted index from a TPU.

Drives the serving path a user calls -- ``InvertedIndex(arena=BitmapArena())``
-> ``QueryServer`` -> ``core.aggregate`` / ``core.pairwise`` -> the Pallas
kernels -- once, end to end, in ONE process, and checks every answer bit
for bit against the jax-free host twin (``aggregate.execute_plan_host``,
``SimilarityEngine.topk(backend="host")``) and a sample against numpy
sets.

    python chip_smoke.py             # one chip: >= 262,144 resident rows (2 GiB)
    python chip_smoke.py --chips 4   # only the sharded path, on 4 chips
    python chip_smoke.py --rehearse  # tiny data on the CPU

Default: Zipfian posting lists (term r matches ~300k/(r+1)^1.1 docs, half
clustered, half uniform; bitset, array and run containers) until the arena
holds at least 262,144 container rows, then a few ticks of a few hundred
mixed queries: and, or, xor, andnot, threshold (weighted and unweighted)
and similar (jaccard, k = 10).

``--chips 4``: a similar_sharded-shaped index of 1,048,576 single-chunk
candidates (8 GiB over four per-shard slabs), sharded top-k, sharded
``or_many`` / ``threshold_many`` with ``arena=``, and coalesced sharded
boolean tickets through ``QueryServer(mesh=)``; each shard's slab must sit
on its own device, and warm repeats must upload no rows.

Earlier stdout lines report sizes, times, peak device memory and the
server's stats.  The last line is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``, printed only
when every phase passed on a TPU (``--rehearse`` reports the CPU, never
a TPU).  Any wrong answer, any degraded batch, or no TPU exits non-zero
without that line.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

ONE_CHIP_ROWS = 1 << 18          # resident container rows: 2 GiB of slab
SHARDED_CANDIDATES = 1 << 20     # single-chunk candidates: 8 GiB
ROW_BYTES = 8192


class SmokeFailure(Exception):
    """A phase produced a wrong answer or left the device path."""


def _report(name: str, value) -> None:
    print(f"{name}: {value}", flush=True)


# ---------------------------------------------------------------------------
# data (made from the seed, in bulk)
# ---------------------------------------------------------------------------

def zipf_postings(rng, min_rows: int, n_docs: int = 1 << 20) -> dict:
    """Term ``r`` matches ~300k/(r+1)^1.1 docs: half clustered in a 2^16
    window (contiguous for every fourth term, so run containers appear
    beside the dense bitsets), half uniform over the ``n_docs`` universe
    (array containers for the tail).  Terms are added until the postings
    hold ``min_rows`` containers."""
    from repro.core import RoaringBitmap
    out: dict = {}
    rows = 0
    r = 0
    while rows < min_rows:
        size = max(50, int(300_000 / (r + 1) ** 1.1))
        n_hot = size // 2
        center = int(rng.integers(0, n_docs - (1 << 16)))
        if r % 4 == 3:
            hot = center + np.arange(n_hot) % (1 << 16)
        else:
            hot = center + rng.integers(0, 1 << 16, n_hot)
        cold = rng.integers(0, n_docs, size - n_hot)
        vals = np.unique(np.concatenate([hot, cold]).astype(np.uint32))
        bm = RoaringBitmap.from_values(vals).run_optimize()
        out[f"t{r}"] = bm
        rows += len(bm.containers)
        r += 1
    return out


def single_chunk_postings(rng, n: int) -> dict:
    """The similar_sharded shape: candidate ``r`` matches ~50k/(r+1)^1.1
    docs of a 2^16 universe (sampled with replacement, deduped), so every
    candidate is exactly one container row.  All values are drawn, sorted
    and deduplicated in bulk; only the container objects are made one by
    one."""
    from repro.core import RoaringBitmap
    from repro.core.containers import container_from_values
    sizes = np.maximum(4, (50_000 / np.arange(1, n + 1) ** 1.1)
                       .astype(np.int64))
    owner = np.repeat(np.arange(n, dtype=np.int64), sizes)
    keyed = np.unique((owner << 16)
                      | rng.integers(0, 1 << 16, owner.size, np.int64))
    bounds = np.searchsorted(keyed >> 16, np.arange(n + 1))
    vals = (keyed & 0xFFFF).astype(np.uint16)
    return {f"c{r}": RoaringBitmap(
        [0], [container_from_values(vals[bounds[r]:bounds[r + 1]])])
        for r in range(n)}


def container_mix(postings) -> dict:
    mix: dict = {}
    for bm in postings.values():
        for c in bm.containers:
            kind = type(c).__name__.replace("Container", "").lower()
            mix[kind] = mix.get(kind, 0) + 1
    return mix


def build_index(postings, n_docs: int):
    """Arena pre-sized to every container (plus reserved row 0), so the
    slab never grows by concatenation; every posting list is promoted in
    one batched host conversion."""
    from repro.core import BitmapArena
    from repro.data.index import InvertedIndex
    rows = sum(len(bm.containers) for bm in postings.values())
    arena = BitmapArena(capacity=rows + 1)
    return InvertedIndex.from_postings(postings, n_docs, arena=arena), arena


# ---------------------------------------------------------------------------
# queries and their references
# ---------------------------------------------------------------------------

def make_queries(rng, terms: list, n: int, n_similar: int) -> list:
    """``n`` boolean queries over Zipf-popular terms (every kind, weighted
    and unweighted thresholds) followed by ``n_similar`` jaccard top-10
    queries."""
    from repro.serve import Query
    hot = min(len(terms), 64)

    def pick(m):
        # half the operands from the head, half from anywhere
        head = rng.choice(hot, size=m, replace=False)
        tail = rng.integers(0, len(terms), m)
        return tuple(terms[int(h if i % 2 == 0 else t)]
                     for i, (h, t) in enumerate(zip(head, tail)))

    out = []
    for i in range(n):
        kind = i % 6
        if kind == 0:
            out.append(Query.and_(*pick(int(rng.integers(2, 4)))))
        elif kind == 1:
            out.append(Query.or_(*pick(int(rng.integers(2, 9)))))
        elif kind == 2:
            out.append(Query.xor_(*pick(int(rng.integers(2, 5)))))
        elif kind == 3:
            out.append(Query.andnot(*pick(int(rng.integers(2, 5)))))
        elif kind == 4:
            ts = pick(int(rng.integers(3, 7)))
            out.append(Query.threshold(ts, int(rng.integers(2, len(ts)))))
        else:
            ts = pick(int(rng.integers(3, 7)))
            w = [int(x) for x in rng.integers(1, 5, len(ts))]
            out.append(Query.threshold(ts, int(rng.integers(2, sum(w))), w))
    for i in range(n_similar):
        term = terms[int(rng.integers(0, hot)) if i % 2 else
                     int(rng.integers(0, len(terms)))]
        out.append(Query.similar(term, k=10, metric="jaccard"))
    return out


def same_bitmap(a, b) -> bool:
    """Bit-for-bit: same keys, same container kinds, same contents."""
    if list(a.keys) != list(b.keys):
        return False
    for x, y in zip(a.containers, b.containers):
        if type(x) is not type(y):
            return False
        if not np.array_equal(x.to_array_values(), y.to_array_values()):
            return False
    return True


def host_twin(index, q, sim_ctx):
    """The jax-free answer to one query."""
    from repro.core import aggregate
    if q.kind == "similar":
        terms, eng = sim_ctx
        idx, score, _ = eng.topk(terms.index(q.terms[0]), q.k, q.metric,
                                 backend="host")
        return [(terms[i], float(s))
                for i, s in zip(idx.tolist(), score.tolist())]
    plan = aggregate.plan_wide(q.kind, [index._get(t) for t in q.terms],
                               q.t, q.weights, arena=index.arena)
    return aggregate.execute_plan_host(plan)


def numpy_answer(index, q) -> np.ndarray:
    """Plain numpy set algebra over the posting lists' document ids."""
    sets = [index._get(t).to_array() for t in q.terms]
    if q.kind == "and":
        out = sets[0]
        for s in sets[1:]:
            out = np.intersect1d(out, s)
        return out
    if q.kind == "andnot":
        drop = np.unique(np.concatenate(sets[1:])) if sets[1:] else []
        return np.setdiff1d(sets[0], drop)
    weights = q.weights or [1] * len(sets)
    vals = np.concatenate(sets)
    w = np.repeat(np.asarray(weights, np.int64), [s.size for s in sets])
    ids, inv = np.unique(vals, return_inverse=True)
    count = np.bincount(inv, weights=w).astype(np.int64)
    if q.kind == "or":
        return ids
    if q.kind == "xor":
        return ids[count % 2 == 1]
    return ids[count >= q.t]


def numpy_similar(index, term: str, k: int) -> list:
    """Brute-force jaccard top-k with numpy (float32 scores, ties to the
    lower term index, the query term itself excluded)."""
    terms = list(index.postings)
    q = index.postings[term].to_array()
    score = np.empty(len(terms), np.float32)
    for i, t in enumerate(terms):
        o = index.postings[t].to_array()
        inter = np.float32(np.intersect1d(q, o, assume_unique=True).size)
        denom = np.float32(q.size) + np.float32(o.size) - inter
        score[i] = inter / denom if denom > 0 else np.float32(1.0)
    score[terms.index(term)] = -1.0
    order = np.argsort(-score, kind="stable")[:k]
    return [(terms[i], float(score[i])) for i in order]


def check_answers(index, tickets, sim_ctx, rng, n_numpy: int) -> None:
    from repro.serve import OK
    bad = []
    for t in tickets:
        if t.result.status != OK:
            bad.append((t.query, t.result.status, t.result.error))
            continue
        want = host_twin(index, t.query, sim_ctx)
        got = t.result.value
        same = (got == want if t.query.kind == "similar"
                else same_bitmap(got, want))
        if not same:
            bad.append((t.query, "differs from host twin"))
    if bad:
        raise SmokeFailure(f"{len(bad)} answers wrong, first: {bad[0]}")
    _report("answers bit-identical to host twin", len(tickets))
    booleans = [t for t in tickets if t.query.kind != "similar"]
    sample = [booleans[int(i)] for i in
              rng.choice(len(booleans), min(n_numpy, len(booleans)),
                         replace=False)]
    for t in sample:
        if not np.array_equal(t.result.value.to_array(),
                              numpy_answer(index, t.query)):
            raise SmokeFailure(f"numpy set check failed: {t.query}")
    sims = [t for t in tickets if t.query.kind == "similar"][:2]
    for t in sims:
        if t.result.value != numpy_similar(index, t.query.terms[0],
                                           t.query.k):
            raise SmokeFailure(f"numpy top-k check failed: {t.query}")
    _report("sample checked against numpy sets",
            f"{len(sample)} boolean, {len(sims)} similar")


def serve_ticks(server, batches) -> tuple[list, list]:
    """Submit each batch and tick until idle; returns tickets and the
    wall time of each tick (ends when every answer is on the host)."""
    tickets, secs = [], []
    for batch in batches:
        t0 = time.perf_counter()
        ts = [server.submit(q) for q in batch]
        server.run_until_idle()
        secs.append(time.perf_counter() - t0)
        tickets.extend(ts)
    return tickets, secs


def check_server(server) -> None:
    st = server.stats()
    _report("server stats", json.dumps(st.as_dict()))
    if st.host_fallbacks or st.dispatch_retries or st.resolved_error:
        raise SmokeFailure(
            f"device path degraded: host_fallbacks={st.host_fallbacks} "
            f"dispatch_retries={st.dispatch_retries} "
            f"resolved_error={st.resolved_error}; "
            f"cause: {st.fallback_cause}")


def peak_memory(devices) -> None:
    for dev in devices:
        stats = dev.memory_stats() or {}
        _report(f"peak_bytes_in_use (device {dev.id})",
                stats.get("peak_bytes_in_use", "not reported"))


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def run_one_chip(jax, args) -> None:
    from repro.serve import QueryServer
    rng = np.random.default_rng(args.seed)
    t0 = time.perf_counter()
    postings = zipf_postings(rng, args.rows)
    index, arena = build_index(postings, 1 << 20)
    arena.sync()
    build_s = time.perf_counter() - t0
    if arena.n_rows - 1 < args.rows:
        raise SmokeFailure(f"only {arena.n_rows - 1} resident rows")
    _report("terms", len(postings))
    _report("resident rows", arena.n_rows - 1)
    _report("slab bytes", arena.capacity * ROW_BYTES)
    _report("container mix", json.dumps(container_mix(postings)))
    _report("index build s (generate + promote + upload)", f"{build_s:.3f}")

    server = QueryServer(index)
    terms = list(postings)
    batches = [make_queries(rng, terms, args.queries, args.similar)
               for _ in range(args.ticks)]
    tickets, secs = serve_ticks(server, batches)
    _report("first tick s (includes compilation)", f"{secs[0]:.3f}")
    _report("later first-time ticks s", [round(s, 3) for s in secs[1:]])
    warm, warm_secs = serve_ticks(server, [batches[0]])
    _report("warm tick s (first tick's queries again)", f"{warm_secs[0]:.3f}")
    _report("queries answered", len(tickets) + len(warm))
    peak_memory(jax.devices()[:1])
    check_server(server)
    check_answers(index, tickets + warm, index._sim_engine(), rng,
                  n_numpy=24)


def run_sharded(jax, args) -> None:
    from repro.core import RoaringBitmap, aggregate
    from repro.launch.mesh import make_wide_mesh
    from repro.serve import OK, Query, QueryServer
    if len(jax.devices()) < 4:
        raise SmokeFailure(f"--chips 4 needs 4 devices, JAX found "
                           f"{len(jax.devices())}")
    mesh = make_wide_mesh(4)
    rng = np.random.default_rng(args.seed)
    t0 = time.perf_counter()
    postings = single_chunk_postings(rng, args.candidates)
    index, arena = build_index(postings, 1 << 16)
    shards = arena.shard_slabs(mesh)
    shards.sync()
    _report("candidates", len(postings))
    _report("resident rows", arena.n_rows - 1)
    _report("slab bytes (all shards)", shards.size * shards.cap_s * ROW_BYTES)
    _report("container mix", json.dumps(container_mix(postings)))
    _report("index build s (generate + promote + upload)",
            f"{time.perf_counter() - t0:.3f}")
    placement = []
    for s in range(shards.size):
        (dev,) = shards.shard_slab(s).devices()
        placement.append(f"shard {s} -> device {dev.id} "
                         f"{getattr(dev, 'coords', '')}")
    _report("per-shard slab placement", "; ".join(placement))
    if len({p.split(" -> ")[1] for p in placement}) != shards.size:
        raise SmokeFailure("per-shard slabs share a device")

    terms = list(postings)
    head = min(len(terms), 4096)
    # head terms leave few survivors to score; tail terms (cardinality
    # near 10) leave nearly every candidate a survivor
    sim_terms = [terms[int(i)] for i in
                 np.concatenate([np.arange(3), rng.integers(head // 2, head,
                                                            3)])]
    groups = [[postings[terms[int(i)]] for i in
               rng.choice(head, size=min(k, head // 2), replace=False)]
              for k in (64, 1024)]
    server = QueryServer(index, mesh=mesh)
    batch = make_queries(rng, terms[:head], args.queries, 0)

    def round_():
        t1 = time.perf_counter()
        sims = [index.similar(t, 10, "jaccard", mesh=mesh)
                for t in sim_terms]
        wide = [RoaringBitmap.or_many(g, mesh=mesh, arena=arena)
                for g in groups]
        wide += [RoaringBitmap.threshold_many(g, 3, mesh=mesh, arena=arena)
                 for g in groups]
        w = [int(x) for x in rng.integers(1, 4, len(groups[0]))]
        wide.append(RoaringBitmap.threshold_many(groups[0], 5, weights=w,
                                                 mesh=mesh, arena=arena))
        tickets, _ = serve_ticks(server, [batch])
        return sims, wide, w, tickets, time.perf_counter() - t1

    sims, wide, w, tickets, first_s = round_()
    uploaded = [st.rows_uploaded for st in shards.stats]
    sims2, wide2, w2, tickets2, warm_s = round_()
    _report("first round s (includes compilation)", f"{first_s:.3f}")
    _report("warm round s", f"{warm_s:.3f}")
    _report("per-shard rows_uploaded", uploaded)
    if [st.rows_uploaded for st in shards.stats] != uploaded:
        raise SmokeFailure("warm repeats uploaded rows to the shards")
    peak_memory(jax.devices())
    check_server(server)

    sim_ctx = index._sim_engine(mesh=mesh)
    for term, res, res2 in zip(sim_terms, sims, sims2):
        want = host_twin(index, Query.similar(term, 10), sim_ctx)
        if res != want or res2 != want:
            raise SmokeFailure(f"sharded top-k differs for {term}")
    plans = [aggregate.plan_wide("or", g, arena=arena) for g in groups]
    plans += [aggregate.plan_wide("threshold", g, 3, arena=arena)
              for g in groups]
    for got, ws in ((wide, w), (wide2, w2)):
        pl_ = plans + [aggregate.plan_wide("threshold", groups[0], 5, ws,
                                           arena=arena)]
        for res, plan in zip(got, pl_):
            if not same_bitmap(res, aggregate.execute_plan_host(plan)):
                raise SmokeFailure(f"sharded {plan.op} differs")
    for t in tickets + tickets2:
        if t.result.status != OK or not same_bitmap(
                t.result.value, host_twin(index, t.query, None)):
            raise SmokeFailure(f"sharded ticket wrong: {t.query}")
    _report("answers bit-identical to host twin",
            f"{2 * len(sim_terms)} top-k, {len(wide) + len(wide2)} wide "
            f"aggregates, {len(tickets) + len(tickets2)} server tickets")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded path across 4 chips")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny data on the CPU (never reports a TPU)")
    args = ap.parse_args(argv)
    small = args.rehearse
    args.rows = 600 if small else ONE_CHIP_ROWS
    args.candidates = 512 if small else SHARDED_CANDIDATES
    args.ticks = 2 if small else 4
    args.queries = 24 if small else 80
    args.similar = 2 if small else 6
    if small:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not small:
        print(f"no TPU: JAX found {jax.devices()}", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache
    _report("compile cache", enable_compile_cache(ROOT))
    _report("device", f"{dev.platform} {dev.device_kind} "
                      f"x{len(jax.devices())}")
    try:
        (run_sharded if args.chips == 4 else run_one_chip)(jax, args)
    except SmokeFailure as e:
        print(f"FAILED: {e}", file=sys.stderr)
        return 1
    result = {"ok": True, "device": {"platform": dev.platform,
                                     "kind": dev.device_kind,
                                     "count": len(jax.devices())}}
    if small:
        result["rehearsal"] = True
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
