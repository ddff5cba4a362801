"""Public jit'd wrappers over the Pallas kernels, with a backend switch.

``backend``:
  * "pallas" -- always run the Pallas kernel (interpret=True off-TPU);
  * "ref"    -- always run the pure-jnp oracle (fast under jit on CPU);
  * "auto"   -- Pallas on TPU, oracle elsewhere (default: the oracle *is*
                the correct lowering for CPU tests, and the kernels are the
                TPU target validated in interpret mode by the test suite).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import array_ops as _array_ops
from repro.kernels import bitset_convert as _convert
from repro.kernels import bitset_ops as _bitset_ops
from repro.kernels import block_sparse_attn as _bsa
from repro.kernels import harley_seal as _hs
from repro.kernels import pair_ops as _pair_ops
from repro.kernels import ref
from repro.kernels import segment_ops as _segment_ops
from repro.kernels import topk_ops as _topk_ops

Backend = str
_DEFAULT: Backend = "auto"


def set_default_backend(backend: Backend) -> None:
    global _DEFAULT
    assert backend in ("auto", "pallas", "ref")
    _DEFAULT = backend


def _use_pallas(backend: Backend | None) -> bool:
    b = _DEFAULT if backend is None else backend
    if b == "pallas":
        return True
    if b == "ref":
        return False
    return jax.default_backend() == "tpu"


def prefer_kernel(backend: Backend | None) -> bool:
    """Whether a host planner should route work through the (jit'd)
    kernel wrappers at all, vs staying on its vectorized numpy twins.

    On TPU (or when a backend is forced, e.g. in tests) the fused kernels
    win; on CPU the host paths avoid a device round-trip that the jnp
    reference lowering cannot amortize.  Shared by the wide-aggregation
    and pairwise planners so the two policies can never drift."""
    if backend in ("pallas", "ref"):
        return True
    return jax.default_backend() == "tpu"


def popcount(words: jax.Array, *, backend: Backend | None = None) -> jax.Array:
    if _use_pallas(backend):
        return _hs.popcount(words)
    return ref.popcount_words(words)


def bitset_op(a, b, op: str, *, backend: Backend | None = None):
    if _use_pallas(backend):
        return _bitset_ops.bitset_op(a, b, op)
    return ref.bitset_op(a, b, op)


def bitset_op_card(a, b, op: str, *, backend: Backend | None = None):
    if _use_pallas(backend):
        return _bitset_ops.bitset_op_card(a, b, op)
    return ref.bitset_op_card(a, b, op)


def array_to_bitset(values, card, *, backend: Backend | None = None):
    if _use_pallas(backend):
        return _convert.array_to_bitset(values, card)
    return ref.array_to_bitset(values, card)


def bitset_set_many(words, values, card, *, backend: Backend | None = None):
    if _use_pallas(backend):
        return _convert.bitset_set_many(words, values, card)
    return ref.bitset_set_many(words, values, card)


def bitset_to_array(words):
    """Extraction is a pure-jnp path on all backends (see bitset_convert)."""
    return ref.bitset_to_array(words)


def array_intersect(a_vals, a_card, b_vals, b_card, *,
                    backend: Backend | None = None):
    if _use_pallas(backend):
        return _array_ops.array_intersect(a_vals, a_card, b_vals, b_card)
    return ref.array_intersect_mask(a_vals, a_card, b_vals, b_card)


def array_intersect_card(a_vals, a_card, b_vals, b_card, *,
                         backend: Backend | None = None):
    """Count-only batched sorted-array intersection (N,) int32 -- the
    array x array class of the pairwise similarity-join planner."""
    if _use_pallas(backend):
        return _array_ops.array_intersect_card(a_vals, a_card,
                                               b_vals, b_card)
    return _ref_array_intersect_count(a_vals, a_card, b_vals, b_card)


_ref_array_intersect_count = jax.jit(ref.array_intersect_count)


def array_pair_masks(a_vals, a_card, b_vals, b_card, *,
                     backend: Backend | None = None):
    """Two-sided membership masks + count for a batch of sorted-array
    pairs: one dispatch feeds AND/OR/XOR/ANDNOT materialization."""
    if _use_pallas(backend):
        return _array_ops.array_pair_masks(a_vals, a_card, b_vals, b_card)
    return ref.array_pair_masks(a_vals, a_card, b_vals, b_card)


def array_bitset_probe(vals, card, words, *, backend: Backend | None = None):
    """Batched array x bitset membership probe (mask over the array's
    slots + count per row)."""
    if _use_pallas(backend):
        return _pair_ops.array_bitset_probe(vals, card, words)
    return _ref_array_bitset_probe(vals, card, words)


_ref_array_bitset_probe = jax.jit(ref.array_bitset_probe)


def bitset_pair_op(a, b, opids, *, backend: Backend | None = None):
    """Mixed-op batched bitset algebra: per-row op ids into
    ``ref.PAIR_OPS``; returns (words, cards) in one dispatch."""
    opids = jnp.asarray(opids, jnp.int32)
    if _use_pallas(backend):
        return _pair_ops.bitset_pair_op(a, b, opids)
    return _ref_bitset_pair_op(a, b, opids)


def bitset_pair_card(a, b, opids, *, backend: Backend | None = None):
    """Count-only mixed-op batch (fast counts, paper section 5.9)."""
    opids = jnp.asarray(opids, jnp.int32)
    if _use_pallas(backend):
        return _pair_ops.bitset_pair_card(a, b, opids)
    return _ref_bitset_pair_card(a, b, opids)


_ref_bitset_pair_op = jax.jit(ref.bitset_pair_op)
_ref_bitset_pair_card = jax.jit(ref.bitset_pair_card)


def similarity_topk(rows, row_col, starts, q_words, q_card, cards, *,
                    metric: str, k: int, exclude=-1, seg=None,
                    backend: Backend | None = None):
    """Fused similarity top-k: score a query against T device-resident
    candidates and select the best k in ONE jit (score + select never
    leave the device; only k indices/scores return).  ``seg`` is the
    layout's cached row-to-candidate map, which the Pallas path sums by;
    the jnp oracle derives its own from ``starts``.  See
    kernels/topk_ops.py for the layout and docs/ARCHITECTURE.md for where
    this sits in the paper map."""
    exclude = jnp.asarray(exclude, jnp.int32)
    if _use_pallas(backend):
        return _topk_ops.similarity_topk(rows, row_col, starts, q_words,
                                         q_card, cards, exclude, seg,
                                         metric=metric, k=k)
    return _ref_similarity_topk(rows, row_col, starts, q_words,
                                jnp.asarray(q_card, jnp.int32),
                                cards, exclude, metric=metric, k=k)


_ref_similarity_topk = jax.jit(ref.similarity_topk,
                               static_argnames=("metric", "k"))


def similarity_topk_ids(rows, row_col, starts, q_words, q_card, cards,
                        gidx, *, metric: str, k: int, n_valid,
                        exclude=-1, axis: str | None = None,
                        backend: Backend | None = None):
    """Fused similarity top-k over a candidate SUBSET labelled with
    global ids (a pruned candidate list): slots >= ``n_valid`` are
    padding, ``exclude`` is a GLOBAL candidate id, and score ties resolve
    to the lowest GLOBAL index.  Inside ``shard_map``, ``axis`` names the
    mesh axis the candidates' rows are split over: each shard scores the
    rows it holds and the partial intersections are summed across the
    axis (the sharded ``SimilarityEngine`` path).  See
    kernels/topk_ops.py."""
    exclude = jnp.asarray(exclude, jnp.int32)
    n_valid = jnp.asarray(n_valid, jnp.int32)
    if _use_pallas(backend):
        return _topk_ops.similarity_topk_ids(
            rows, row_col, starts, q_words, q_card, cards, gidx, n_valid,
            exclude, metric=metric, k=k, axis=axis)
    return _ref_similarity_topk_ids(
        rows, row_col, starts, q_words, jnp.asarray(q_card, jnp.int32),
        cards, gidx, n_valid, exclude, metric=metric, k=k, axis=axis)


_ref_similarity_topk_ids = jax.jit(ref.similarity_topk_ids,
                                   static_argnames=("metric", "k", "axis"))


_ref_segment_reduce = jax.jit(
    ref.segment_reduce, static_argnames=("op", "jmax"))

_ref_segment_counters = jax.jit(
    ref.segment_counters, static_argnames=("jmax", "planes"))


def segment_reduce(slab, starts, op: str, *, jmax: int, threshold: int = 0,
                   weights=None, planes: int | None = None, wbits: int = 1,
                   backend: Backend | None = None):
    """Segmented K-way OR/AND/XOR/ANDNOT/threshold reduce fused with
    cardinality: one dispatch for an arbitrary number of bitmaps (wide
    aggregation, paper section 5.8).  See kernels/segment_ops.py for the
    layout.  ``threshold`` is a runtime scalar (T-sweeps share one
    compilation) or a (S,) per-segment vector (coalesced multi-query
    batches).  ``weights`` (N,) int32 weight threshold rows (``wbits``
    static bit width, ``planes`` static counter width)."""
    t = jnp.asarray(threshold, jnp.int32)
    if weights is not None:
        weights = jnp.asarray(weights, jnp.int32)
    if _use_pallas(backend):
        return _segment_ops.segment_reduce(slab, starts, op, jmax=jmax,
                                           threshold=t, weights=weights,
                                           planes=planes, wbits=wbits)
    return _ref_segment_reduce(slab, starts, op, jmax=jmax, threshold=t,
                               weights=weights)


_ref_segment_reduce_rows = jax.jit(
    ref.segment_reduce_rows, static_argnames=("op", "jmax"))


def segment_reduce_rows(table, ids, starts, op: str, *, jmax: int,
                        threshold: int = 0, weights=None,
                        planes: int | None = None, wbits: int = 1,
                        backend: Backend | None = None):
    """Resident-slab segmented reduce: gather ``ids`` rows from a
    device-resident ``table`` (``core.arena.BitmapArena`` slab, optionally
    with a staged host block appended) on-device, then reduce exactly like
    :func:`segment_reduce`.  Warm arena queries ship only ids/starts/
    threshold over PCIe -- container words stay resident (docs/MEMORY.md).
    Pad ragged segments with id 0, the arena's reserved all-zero row."""
    t = jnp.asarray(threshold, jnp.int32)
    ids = jnp.asarray(ids, jnp.int32)
    if weights is not None:
        weights = jnp.asarray(weights, jnp.int32)
    if _use_pallas(backend):
        return _segment_ops.segment_reduce_rows(
            table, ids, starts, op, jmax=jmax, threshold=t,
            weights=weights, planes=planes, wbits=wbits)
    return _ref_segment_reduce_rows(table, ids, starts, op, jmax=jmax,
                                    threshold=t, weights=weights)


_ref_segment_reduce_rows_dual = jax.jit(
    ref.segment_reduce_rows_dual, static_argnames=("op", "jmax"))


def segment_reduce_rows_dual(table, staged, pos, sidx, starts, op: str, *,
                             jmax: int, threshold: int = 0, weights=None,
                             planes: int | None = None, wbits: int = 1,
                             backend: Backend | None = None):
    """Dual-source resident-slab reduce: slot ``i`` gathers
    ``table[pos[i]] | staged[sidx[i]]`` on-device (exactly one side real,
    the other the reserved zero row) and reduces like
    :func:`segment_reduce`.  ``table`` is the arena's resident
    single-device slab and is never copied per call; only the small ``staged`` block of cold rows
    crosses PCIe.  See kernels/segment_ops.py."""
    t = jnp.asarray(threshold, jnp.int32)
    pos = jnp.asarray(pos, jnp.int32)
    sidx = jnp.asarray(sidx, jnp.int32)
    if weights is not None:
        weights = jnp.asarray(weights, jnp.int32)
    if _use_pallas(backend):
        return _segment_ops.segment_reduce_rows_dual(
            table, staged, pos, sidx, starts, op, jmax=jmax, threshold=t,
            weights=weights, planes=planes, wbits=wbits)
    return _ref_segment_reduce_rows_dual(table, staged, pos, sidx, starts,
                                         op, jmax=jmax, threshold=t,
                                         weights=weights)


_ref_segment_reduce_cnf = jax.jit(
    ref.segment_reduce_cnf, static_argnames=("cmax", "jmax"))


def segment_reduce_cnf(table, staged, pos, sidx, clause_starts, starts, *,
                       cmax: int, jmax: int, backend: Backend | None = None):
    """AND of ORs in one call: slot ``i`` gathers ``table[pos[i]] |
    staged[sidx[i]]`` (as :func:`segment_reduce_rows_dual`), clause ``c``
    ORs slots ``clause_starts[c]:clause_starts[c+1]`` and segment ``s``
    ANDs clauses ``starts[s]:starts[s+1]``, fused with the segment's
    cardinality.  ``cmax`` / ``jmax`` bound a clause's rows and a
    segment's clauses for the jnp oracle (the kernel needs no bound).
    See kernels/segment_ops.py."""
    pos = jnp.asarray(pos, jnp.int32)
    sidx = jnp.asarray(sidx, jnp.int32)
    if _use_pallas(backend):
        return _segment_ops.segment_reduce_cnf(
            table, staged, pos, sidx, clause_starts, starts)
    return _ref_segment_reduce_cnf(table, staged, pos, sidx, clause_starts,
                                   starts, cmax=cmax, jmax=jmax)


def segment_counters(slab, starts, *, jmax: int, planes: int, weights=None,
                     backend: Backend | None = None):
    """Per-segment bit-sliced occurrence counters (S, planes, WORDS) --
    the exchange payload of the sharded threshold path.  Counter
    computation is a pure-jnp path on all backends: it exists to be
    all-gathered and combined across mesh shards, where XLA's fusion of
    the 32 plane extractions is already the right lowering."""
    del backend
    if weights is not None:
        weights = jnp.asarray(weights, jnp.int32)
    return _ref_segment_counters(slab, starts, jmax=jmax, planes=planes,
                                 weights=weights)


def decode_attention(q, k, v, block_mask_words, kv_len, *,
                     block_size: int = 128, sm_scale=None, softcap: float = 0.0,
                     backend: Backend | None = None):
    if _use_pallas(backend):
        return _bsa.decode_attention(q, k, v, block_mask_words, kv_len,
                                     block_size=block_size, sm_scale=sm_scale,
                                     softcap=softcap)
    return ref.block_sparse_attention_decode(
        q, k, v, block_mask_words, kv_len,
        block_size=block_size, sm_scale=sm_scale, softcap=softcap)
