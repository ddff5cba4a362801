"""Every kernel entry of the serving path compiles for a described TPU v5e.

Interpret mode runs the kernels' semantics on the CPU but not the TPU
compiler's rules: block tiling, vector layouts, scalar stores and the
1 MiB of SMEM that scalar-prefetched vectors live in.  These tests hand
each entry the shapes the chip smoke serves (>= 262,144 container rows or
candidates) and compile it for one chip of a described ``v5e:2x2``
topology; nothing runs.  A compiled Pallas kernel shows up as a
``tpu_custom_call`` in the optimized HLO.

The sharded dispatches (similarity top-k and the wide aggregates over
per-shard arena slabs) are compiled for all four chips of the topology at
the four-chip smoke's size, and must keep every row on its own shard:
their temporaries stay within one shard's rows.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process may load the TPU library at a time,
and the test workers each import every test file.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.core import aggregate, pairwise
from repro.kernels import (array_ops, bitset_convert, bitset_ops, harley_seal,
                           pair_ops, segment_ops, topk_ops)
from repro.kernels.ref import ARRAY_CAP, WORDS
from repro.launch.mesh import auto_axes

ROWS = 1 << 18           # resident container rows / candidates
SEGS = 3 * segment_ops.SEG_CHUNK // 2   # crosses one segment-chunk boundary
KEYS = 256               # global chunk-key columns of a similarity query
SHARDS = 4
CANDIDATES = 1 << 20     # single-chunk candidates over four shard slabs


@pytest.fixture(scope="module")
def v5e():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 -- any failure means "no TPU compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # can never be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(v5e):
    return SingleDeviceSharding(v5e.devices[0])


@pytest.fixture(scope="module")
def four_chips(v5e):
    return Mesh(np.array(v5e.devices[:SHARDS]), ("wide",),
                axis_types=auto_axes(1))


def _compile(sharding, fn, *shapes):
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
            for shape, dtype in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


_U32, _I32 = jnp.uint32, jnp.int32


@pytest.mark.parametrize("op", segment_ops.OPS)
def test_segment_reduce(one_chip, op):
    def fn(slab, starts, t, w):
        return segment_ops.segment_reduce(
            slab, starts, op, jmax=4, threshold=t,
            weights=w if op == "threshold" else None, planes=5, wbits=2,
            interpret=False)
    _compile(one_chip, fn, ((ROWS, WORDS), _U32), ((SEGS + 1,), _I32),
             ((SEGS,), _I32), ((ROWS,), _I32))


def test_segment_reduce_rows_dual(one_chip):
    def fn(table, staged, pos, sidx, starts):
        return segment_ops.segment_reduce_rows_dual(
            table, staged, pos, sidx, starts, "or", jmax=8,
            interpret=False)
    _compile(one_chip, fn, ((ROWS, WORDS), _U32), ((64, WORDS), _U32),
             ((SEGS * 8,), _I32), ((SEGS * 8,), _I32), ((SEGS + 1,), _I32))


def test_segment_reduce_cnf(one_chip):
    """A whole call of rows at the SMEM bound: one int32 a row."""
    n = segment_ops.CNF_ROW_CHUNK

    def fn(table, staged, pos, sidx, clause_starts, starts):
        return segment_ops.segment_reduce_cnf(
            table, staged, pos, sidx, clause_starts, starts,
            interpret=False)
    _compile(one_chip, fn, ((ROWS, WORDS), _U32), ((1, WORDS), _U32),
             ((n,), _I32), ((n,), _I32), ((n + 1,), _I32),
             ((SEGS + 1,), _I32))


def test_similarity_topk(one_chip):
    def fn(rows, col, starts, q, cards, seg):
        return topk_ops.similarity_topk(
            rows, col, starts, q, jnp.int32(1000), cards, jnp.int32(7),
            seg, metric="cosine", k=10, interpret=False)
    _compile(one_chip, fn, ((ROWS, WORDS), _U32), ((ROWS,), _I32),
             ((ROWS + 1,), _I32), ((KEYS, WORDS), _U32), ((ROWS,), _I32),
             ((ROWS,), _I32))


def test_similarity_topk_ids(one_chip):
    def fn(rows, col, starts, q, cards, gidx):
        return topk_ops.similarity_topk_ids(
            rows, col, starts, q, jnp.int32(1000), cards, gidx,
            jnp.int32(ROWS - 5), jnp.int32(7), metric="jaccard", k=10,
            interpret=False)
    _compile(one_chip, fn, ((ROWS, WORDS), _U32), ((ROWS,), _I32),
             ((ROWS + 1,), _I32), ((KEYS, WORDS), _U32), ((ROWS,), _I32),
             ((ROWS,), _I32))


def test_topk_merge(one_chip):
    def fn(score, inter, gidx):
        return topk_ops.topk_merge(score, inter, gidx, 10, interpret=False)
    _compile(one_chip, fn, ((ROWS,), jnp.float32), ((ROWS,), _I32),
             ((ROWS,), _I32))


def test_popcount(one_chip):
    _compile(one_chip,
             lambda w: harley_seal.popcount(w, interpret=False),
             ((ROWS, WORDS), _U32))


def test_bitset_pair_card(one_chip):
    _compile(one_chip,
             lambda a, b, o: pair_ops.bitset_pair_card(a, b, o,
                                                       interpret=False),
             ((4096, WORDS), _U32), ((4096, WORDS), _U32), ((4096,), _I32))


def test_array_intersect_card(one_chip):
    _compile(one_chip,
             lambda a, ac, b, bc: array_ops.array_intersect_card(
                 a, ac, b, bc, interpret=False),
             ((1024, ARRAY_CAP), _I32), ((1024,), _I32),
             ((1024, ARRAY_CAP), _I32), ((1024,), _I32))


def test_array_bitset_probe(one_chip):
    _compile(one_chip,
             lambda v, c, w: pair_ops.array_bitset_probe(v, c, w,
                                                         interpret=False),
             ((1024, ARRAY_CAP), _I32), ((1024,), _I32),
             ((1024, WORDS), _U32))


_ARRAYS = (((1024, ARRAY_CAP), _I32), ((1024,), _I32))
_BITSETS = (((4096, WORDS), _U32),) * 2


@pytest.mark.parametrize("fn,shapes", [
    (lambda v, c: bitset_convert.array_to_bitset(v, c, interpret=False),
     _ARRAYS),
    (lambda w, v, c: bitset_convert.bitset_set_many(w, v, c,
                                                    interpret=False),
     (((1024, WORDS), _U32),) + _ARRAYS),
    (lambda a, ac, b, bc: array_ops.array_intersect(a, ac, b, bc,
                                                    interpret=False),
     _ARRAYS * 2),
    (lambda a, ac, b, bc: array_ops.array_pair_masks(a, ac, b, bc,
                                                     interpret=False),
     _ARRAYS * 2),
    (lambda a, b, o: pair_ops.bitset_pair_op(a, b, o, interpret=False),
     _BITSETS + (((4096,), _I32),)),
    (lambda a, b: bitset_ops.bitset_op(a, b, "and", interpret=False),
     _BITSETS),
    (lambda a, b: bitset_ops.bitset_op_card(a, b, "xor", interpret=False),
     _BITSETS),
], ids=["array_to_bitset", "bitset_set_many", "array_intersect",
        "array_pair_masks", "bitset_pair_op", "bitset_op",
        "bitset_op_card"])
def test_other_kernel_entries(one_chip, fn, shapes):
    _compile(one_chip, fn, *shapes)


def _compile_sharded(mesh, monkeypatch, fn, *shapes):
    """Compile a sharded dispatch for the described chips.  The kernels
    choose interpret mode from ``jax.default_backend()``, which is the
    CPU here: report the TPU so that they compile for the chip."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    args = [jax.ShapeDtypeStruct(shape, dtype,
                                 sharding=NamedSharding(mesh, spec))
            for shape, dtype, spec in shapes]
    return jax.jit(fn).lower(*args).compile()


_SHARDED, _REPLICATED = PartitionSpec("wide"), PartitionSpec()
_CAP_S = -(-(CANDIDATES + 1) // SHARDS)          # rows per shard slab
_SLAB = ((SHARDS * _CAP_S, WORDS), _U32, _SHARDED)


def test_sharded_similarity_topk(four_chips, monkeypatch):
    """Every candidate survives (a tail query): each shard gathers its
    own 2^18 rows and only the per-candidate counts are all-reduced."""
    rows = CANDIDATES // SHARDS
    fn = pairwise._sharded_topk(four_chips, "wide", "jaccard", 10, "pallas")
    compiled = _compile_sharded(
        four_chips, monkeypatch, fn, _SLAB,
        ((SHARDS, rows), _I32, _SHARDED), ((SHARDS, rows), _I32, _SHARDED),
        ((SHARDS, CANDIDATES + 1), _I32, _SHARDED),
        ((1, WORDS), _U32, _REPLICATED), ((), _I32, _REPLICATED),
        ((CANDIDATES,), _I32, _REPLICATED), ((CANDIDATES,), _I32, _REPLICATED),
        ((), _I32, _REPLICATED), ((), _I32, _REPLICATED))
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "all-gather" not in text
    local_rows = rows * WORDS * 4
    assert compiled.memory_analysis().temp_size_in_bytes < 1.25 * local_rows


@pytest.mark.parametrize("op", ["or", "andnot", "and", "threshold"])
def test_sharded_wide_aggregate(four_chips, monkeypatch, op):
    """A 1,024-operand aggregate: 256 rows per shard, each gathered from
    its own slab (threshold counts in XLA, by design)."""
    rows, segs = 256, 2 if op == "andnot" else 1
    fn = aggregate._sharded_rows_fn(four_chips, "wide", op, "pallas", SHARDS,
                                    rows, 11 if op == "threshold" else None)
    compiled = _compile_sharded(
        four_chips, monkeypatch, fn, _SLAB,
        ((64, WORDS), _U32, _REPLICATED), ((SHARDS, rows), _I32, _SHARDED),
        ((SHARDS, rows), _I32, _SHARDED), ((SHARDS, segs + 1), _I32, _SHARDED),
        ((SHARDS, rows), _I32, _SHARDED), ((), _I32, _REPLICATED))
    assert ("tpu_custom_call" in compiled.as_text()) == (op != "threshold")
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20
