"""Sharded similarity top-k over per-shard arena slabs.

The tier-1 process sees exactly one CPU device (tests/conftest.py pins
that), so the real multi-device runs happen in subprocesses launched
with ``XLA_FLAGS=--xla_force_host_platform_device_count=N``, mirroring
tests/core/test_sharded.py.  The subprocess asserts the full tentpole
contract: bit-identical results (indices, float32 scores, intersection
counts -- including tie order) against a cold single-device engine for
member / bitmap / unknown-term queries across every metric, tie groups
straddling shard boundaries at the k cut, warm re-queries moving ZERO
container rows host->device (per-shard ``ArenaStats``), single-row
single-shard repatch on :meth:`SimilarityEngine.refresh`, a seeded
mutation-query interleave, batched parity, ``InvertedIndex.similar``
wiring, and the query server's ``slab_mismatch`` recovery rung against
a sharded engine.

In-process tests cover the kernel primitives (``similarity_topk_ids`` /
``topk_merge``) on the ref and Pallas interpret backends, the 1-device
mesh fallback, and the arena requirement.
"""

import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

SRC = str(Path(__file__).resolve().parents[2] / "src")

_SUBPROCESS_BODY = """
import os, sys
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count={d} "
    + os.environ.get("XLA_FLAGS", ""))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np
import jax
from jax.experimental import mesh_utils
from jax.sharding import Mesh

from repro.core import BitmapArena, RoaringBitmap
from repro.core.pairwise import SimilarityEngine
from repro.data.index import InvertedIndex
from repro.serve import FaultInjector, Query, QueryServer

assert jax.device_count() == {d}, jax.device_count()
S = {d}
mesh = Mesh(mesh_utils.create_device_mesh((S,)), ("wide",))

rng = np.random.default_rng(0xB17)
def bm(v):
    return RoaringBitmap.from_values(np.asarray(np.unique(v), np.uint32))

bms = []
for i in range(41):
    n = int(rng.integers(0, 6000))
    bms.append(bm(rng.choice(300_000, size=n, replace=False)))
bms.append(RoaringBitmap())                     # empty candidate

def check(a, b, ctx):
    for x, y, part in zip(a, b, ("idx", "score", "inter")):
        assert np.array_equal(x, y), (ctx, part, x, y)

arena = BitmapArena()
eng = SimilarityEngine(bms, arena=arena, mesh=mesh)
cold = SimilarityEngine(bms, arena=BitmapArena())
qbm = bm(rng.choice(300_000, size=4000, replace=False))
empty_q = RoaringBitmap()

# 1. bit-identity: member / bitmap / empty queries, every metric, k sweep
for metric in ("jaccard", "cosine", "containment"):
    for query in (0, 7, len(bms) - 1, qbm, empty_q):
        for k in (1, 5, len(bms)):
            check(cold.topk(query, k, metric, backend="ref"),
                  eng.topk(query, k, metric), (metric, k))

# 2. tie group straddling shards: identical posting lists at consecutive
# global indices (their rows cycle through every shard) and k cutting
# inside the group -- the winners must be the LOWEST global indices, in
# ascending order, on both paths
tie_vals = rng.choice(300_000, size=500, replace=False)
ties = [bm(tie_vals) for _ in range(2 * S + 1)]   # spans all shards twice
tied = ties + bms[:9]
tarena = BitmapArena()
teng = SimilarityEngine(tied, arena=tarena, mesh=mesh)
tcold = SimilarityEngine(tied, arena=BitmapArena())
for k in (2, S, 2 * S):                           # cuts inside the group
    got = teng.topk(bm(tie_vals), k, "jaccard")
    want = tcold.topk(bm(tie_vals), k, "jaccard", backend="ref")
    check(want, got, ("tie", k))
    assert got[0].tolist() == list(range(k))      # lowest global indices
    assert np.all(got[1][:k] == got[1][0])        # one tie group

# 2b. no row leaves its shard: the compiled dispatch exchanges only the
# per-candidate counts (an all-reduce), never slab rows (no all-gather)
from repro.core.pairwise import _sharded_topk
tshards = tarena.shard_slabs(mesh)
plan = teng._plan_sharded(np.arange(len(tied)), tshards)
gidx_p, cards_p, starts_p, lpos_p, col_p = plan
assert (starts_p[:, -1] > 0).sum() > 1            # rows on several shards
hlo = _sharded_topk(mesh, "wide", "jaccard", 3, None).lower(
    tshards.assembled(), lpos_p, col_p, starts_p,
    teng._query_block(teng._query_words(0)), np.int32(500), cards_p,
    gidx_p, np.int32(len(tied)), np.int32(-1)).compile().as_text()
assert "all-gather" not in hlo and "all-reduce" in hlo

# 3. warm re-queries move ZERO container rows host->device
shards = arena.shard_slabs(mesh)
up0 = [s.rows_uploaded for s in shards.stats]
g0 = [s.device_gathers for s in shards.stats]
for metric in ("jaccard", "cosine"):
    eng.topk(3, 10, metric)
    eng.topk(qbm, 10, metric)
assert [s.rows_uploaded for s in shards.stats] == up0
assert all(g1 > g for g1, g in zip(
    (s.device_gathers for s in shards.stats), g0))
assert arena.stats.rows_uploaded == 0             # single-dev slab unused

# 4. refresh(): one container edit repatches exactly ONE row on exactly
# ONE shard
bms[5].add(299_999)
assert eng.refresh()
p0 = [s.rows_patched for s in shards.stats]
got = eng.topk(5, 7, "jaccard")                   # flush happens lazily
deltas = [b - a for a, b in zip(p0,
                                (s.rows_patched for s in shards.stats))]
assert sum(deltas) == 1 and max(deltas) == 1, deltas
check(SimilarityEngine(bms, arena=BitmapArena()).topk(
    5, 7, "jaccard", backend="ref"), got, "refresh")

# 5. seeded mutation-query interleave vs a cold single-device engine
for step in range(12):
    t = int(rng.integers(0, len(bms) - 1))
    bms[t].add(int(rng.integers(0, 1 << 20)))
    eng.refresh()
    q = int(rng.integers(0, len(bms))) if step % 2 else qbm
    k = int(rng.integers(1, 12))
    metric = ("jaccard", "cosine", "containment")[step % 3]
    check(SimilarityEngine(bms, arena=BitmapArena()).topk(
        q, k, metric, backend="ref"),
        eng.topk(q, k, metric), ("interleave", step))

# 6. batched parity
batch = [0, 1, qbm, len(bms) - 1]
wants = SimilarityEngine(bms, arena=BitmapArena()).topk_batch(
    batch, 6, "jaccard", backend="ref")
for want, got in zip(wants, eng.topk_batch(batch, 6, "jaccard")):
    check(want, got, "batch")

# 7. InvertedIndex.similar(mesh=) + QueryServer slab_mismatch recovery
docs = [[f"t{{j}}" for j in rng.choice(50, rng.integers(2, 12))]
        for _ in range(3000)]
cold_ix = InvertedIndex().build(docs)
warm_ix = InvertedIndex(arena=BitmapArena()).build(docs)
assert warm_ix.similar("t1", 8, mesh=mesh) == cold_ix.similar("t1", 8)
assert warm_ix.similar("t1", 8, "cosine", mesh=mesh) == \\
    cold_ix.similar("t1", 8, "cosine")
assert warm_ix.similar("absent", 8, mesh=mesh) == \\
    cold_ix.similar("absent", 8)

faults = FaultInjector.script({{"slab_mismatch": [True]}})
srv = QueryServer(warm_ix, backend="ref", faults=faults, mesh=mesh)
ref_srv = QueryServer(cold_ix, backend="ref")
qs = [Query.similar("t2", 5), Query.similar("t7", 3, metric="cosine")]
ta = [srv.submit(q) for q in qs]
tb = [ref_srv.submit(q) for q in qs]
srv.run_until_idle()
ref_srv.run_until_idle()
for a, b in zip(ta, tb):
    assert a.result.ok and a.result.value == b.result.value
assert srv.stats().replans == 1
print("TOPK_SHARDED_OK")
"""


def _run_subprocess(devices: int) -> str:
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-c", _SUBPROCESS_BODY.format(d=devices)],
        capture_output=True, text=True, timeout=600, env=env)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return proc.stdout


@pytest.mark.parametrize("devices", [2, 4])
def test_sharded_topk_matches_single_device(devices):
    """The tentpole contract on a forced multi-device CPU mesh:
    bit-identical results including tie order, warm zero-PCIe, per-shard
    refresh accounting, server recovery."""
    assert "TOPK_SHARDED_OK" in _run_subprocess(devices)


# ---------------------------------------------------------------------------
# in-process: kernel primitives + 1-device degradation
# ---------------------------------------------------------------------------

def _tiny_case(rng):
    import jax.numpy as jnp
    from repro.kernels.ref import WORDS
    T, C = 6, 4
    rows_per = [1, 2, 0, 3, 1, 2]
    starts = np.zeros(T + 1, np.int32)
    starts[1:] = np.cumsum(rows_per)
    rows = rng.integers(0, 2 ** 32, size=(int(starts[-1]), WORDS),
                        dtype=np.uint32)
    row_col = rng.integers(0, C, size=(rows.shape[0],), dtype=np.int32)
    q_words = rng.integers(0, 2 ** 32, size=(C, WORDS), dtype=np.uint32)
    cards = np.array([max(1, int(np.unpackbits(np.ascontiguousarray(
        rows[starts[t]:starts[t + 1]]).view(np.uint8)).sum()))
        for t in range(T)], np.int32)
    q_card = int(np.unpackbits(q_words.view(np.uint8)).sum())
    gidx = np.array([3, 9, 12, 20, 27, 33], np.int32)
    return (jnp.asarray(rows), jnp.asarray(row_col), jnp.asarray(starts),
            jnp.asarray(q_words), q_card, jnp.asarray(cards),
            jnp.asarray(gidx))


@pytest.mark.parametrize("metric", ["jaccard", "cosine", "containment"])
def test_similarity_topk_ids_ref_pallas_parity(metric, rng):
    """The per-shard fused kernel agrees bit-for-bit with the jnp oracle
    on the interpret backend, across padding and exclusion masks."""
    from repro.kernels import ops as kops
    rows, col, starts, q, qc, cards, gidx = _tiny_case(rng)
    for n_valid in (6, 4):
        for exclude in (-1, 9):
            out = {}
            for be in ("ref", "pallas"):
                out[be] = kops.similarity_topk_ids(
                    rows, col, starts, q, qc, cards, gidx, metric=metric,
                    k=3, n_valid=n_valid, exclude=exclude,
                    backend=be)
            for a, b in zip(out["ref"], out["pallas"]):
                assert np.array_equal(np.asarray(a), np.asarray(b))


def test_topk_merge_tie_rule():
    """Labelled selection resolves equal scores to the LOWEST global
    index -- the pinned contract (Pallas kernel and jnp oracle)."""
    import jax.numpy as jnp
    from repro.kernels import ref, topk_ops
    score = jnp.asarray(np.array([.5, .9, .9, .1, .9, .5], np.float32))
    inter = jnp.asarray(np.array([5, 9, 9, 1, 9, 5], np.int32))
    gidx = jnp.asarray(np.array([40, 31, 7, 2, 19, 3], np.int32))
    for merge in (topk_ops.topk_merge, ref.topk_select_ids):
        idx, sco, itr = merge(score, inter, gidx, 4)
        assert np.asarray(idx).tolist() == [7, 19, 31, 3]
        assert np.array_equal(np.asarray(sco),
                              np.array([.9, .9, .9, .5], np.float32))
        assert np.asarray(itr).tolist() == [9, 9, 9, 5]


def test_one_device_mesh_degrades(rng):
    """A 1-device mesh must fall back to the single-device engine (and
    an opaque/absent mesh never shards)."""
    import jax
    from jax.experimental import mesh_utils
    from jax.sharding import Mesh
    from repro.core import BitmapArena, RoaringBitmap
    from repro.core.pairwise import SimilarityEngine
    mesh = Mesh(mesh_utils.create_device_mesh(
        (1,), devices=jax.devices()[:1]), ("wide",))
    bms = [RoaringBitmap.from_values(np.unique(
        rng.integers(0, 1 << 18, 2000, dtype=np.uint32)))
        for _ in range(9)]
    eng = SimilarityEngine(bms, arena=BitmapArena(), mesh=mesh)
    assert eng._mesh is None                      # degraded
    plain = SimilarityEngine(bms)
    for part_a, part_b in zip(eng.topk(2, 4), plain.topk(2, 4)):
        assert np.array_equal(part_a, part_b)


def test_sharded_engine_requires_arena():
    """mesh= with >1 shard and no arena must refuse loudly, engine and
    index both."""
    from repro.core.pairwise import SimilarityEngine
    from repro.data.index import InvertedIndex

    class _FakeDevs:
        shape = (2,)

        def reshape(self, *_):
            return [None, None]

    class _FakeMesh:
        axis_names = ("wide",)
        devices = _FakeDevs()

    with pytest.raises(ValueError, match="arena"):
        SimilarityEngine([], mesh=_FakeMesh())
    ix = InvertedIndex().build([["a", "b"], ["b"]])
    with pytest.raises(ValueError, match="arena"):
        ix.similar("a", 2, mesh=_FakeMesh())
