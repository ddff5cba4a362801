"""Device-resident fused top-k similarity kernels.

The paper's fast-count argument (section 5.9: the logical op and the
popcount must happen while the words sit in vector registers) extends to
similarity joins: the *scores* never need to leave the device either.
This module runs the whole ``InvertedIndex.similar`` hot path --
AND-cardinality scoring of a query bitmap against T candidate bitmaps,
metric evaluation (jaccard / cosine / containment by inclusion-exclusion
over the AND count), and the k-selection -- inside ONE jit, so only k
indices and k scores ever cross back to the host.

Layout (prepared once by ``core.pairwise.SimilarityEngine`` and cached on
device -- the serving contract):

  * ``rows``    (N, WORDS) uint32: every candidate container promoted to
    the bitset domain, candidate-major (candidate t owns rows
    ``starts[t]:starts[t+1]``).
  * ``row_col`` (N,) int32: which global chunk key each row belongs to --
    the scoring step ANDs row r with ``q_words[row_col[r]]``, so a query
    that lacks the key contributes zero automatically.
  * ``seg``     (N,) int32: which candidate each row belongs to (``T`` on
    layout padding), built from ``starts`` once per layout, so no
    dispatch searches ``starts`` for it.
  * ``q_words`` (C, WORDS) uint32: the query's containers scattered over
    the global key columns.  This is the ONLY per-query device transfer
    (C * 8 kB); the candidate slab stays resident.

Three stages compose inside one jit:

  1. ``_and_card_kernel`` -- grid over 8-row blocks of ``rows``: each
     step DMAs the 8 query rows its candidate rows name (``row_col``
     arrives as an SMEM block, never as a prefetched N-vector, which
     would overflow the TPU's 1 MiB of SMEM at index sizes), ANDs and
     Harley-Seal popcounts them.
  2. per-candidate sums by ``seg`` (``ref.candidate_inter``; derived
     from ``starts`` when no map is given) and the float32 score
     (``ref.similarity_scores``: correctly rounded, so scores and ties
     match the host twin bit for bit).
  3. ``topk_merge`` -- ``_select_ids_kernel`` runs k rounds of (max,
     lowest label among the maxes) over (64, 128) blocks of labelled
     scores, then again over the blocks' k-lists until one block
     remains.  Per-block selection keeps every member of the global
     top-k (each block's share of it is a prefix of the block's own
     order), so the result equals one selection over the whole vector.

Ties at equal score resolve to the LOWEST candidate index -- bit-identical
to a stable host argsort of the negated scores, which is what the host
planner runs off-device.  ``kernels.ref.similarity_topk`` is the pure-jnp
oracle.  See docs/ARCHITECTURE.md (sections 4.2/5.9 row of the paper map).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.harley_seal import harley_seal_reduce
from repro.kernels.ref import (METRICS, WORDS, candidate_inter,
                               similarity_scores)


ROW_BLOCK = 8           # candidate rows per AND-popcount grid step
_COL_BLOCK = 1024       # row_col entries per SMEM block (XLA tiles 1-D by 1024)
SELECT_ROWS = 64        # select block: (64, 128) labelled entries
_LANES = 128
_BIG = 2**31 - 1        # label of layout padding: never the lowest


def _and_card_kernel(col_ref, rows_ref, q_hbm, out_ref, qbuf, sem):
    """|rows[r] & q_words[row_col[r]]| for one 8-row block: the query
    rows the block names are DMA'd from HBM by column.  Query rows live
    as (C, 1, WORDS) so that one row is a whole tile to the DMA engine."""
    base = (pl.program_id(0) % (_COL_BLOCK // ROW_BLOCK)) * ROW_BLOCK
    copies = [pltpu.make_async_copy(q_hbm.at[col_ref[base + i]],
                                    qbuf.at[i], sem.at[i])
              for i in range(ROW_BLOCK)]
    for c in copies:
        c.start()
    for i, c in enumerate(copies):
        c.wait()
        out_ref[pl.ds(i, 1), :] = harley_seal_reduce(
            rows_ref[pl.ds(i, 1), :] & qbuf[i])


def _interpret_params(interpret: bool):
    # DMAs and semaphores need the TPU-semantics interpreter off the chip
    return pltpu.InterpretParams() if interpret else False


def _pad_to(x, size, fill):
    n_pad = size - x.shape[0]
    if not n_pad:
        return x
    return jnp.pad(x, ((0, n_pad),) + ((0, 0),) * (x.ndim - 1),
                   constant_values=fill)


def row_and_card(rows: jax.Array, row_col: jax.Array, q_words: jax.Array,
                 *, interpret: bool) -> jax.Array:
    """(N,) int32 per-row ``popcount(rows[r] & q_words[row_col[r]])``.
    The candidate slab is never padded or copied: the last 8-row block
    may run past row N-1, and its extra outputs are dropped."""
    n = rows.shape[0]
    steps = -(-n // ROW_BLOCK)
    n_col = -(-n // _COL_BLOCK) * _COL_BLOCK
    out = pl.pallas_call(
        _and_card_kernel,
        grid=(steps,),
        in_specs=[pl.BlockSpec((_COL_BLOCK,),
                               lambda i: (i // (_COL_BLOCK // ROW_BLOCK),),
                               memory_space=pltpu.SMEM),
                  pl.BlockSpec((ROW_BLOCK, WORDS), lambda i: (i, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((ROW_BLOCK, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((steps * ROW_BLOCK, 1), jnp.int32),
        scratch_shapes=[pltpu.VMEM((ROW_BLOCK, 1, WORDS), jnp.uint32),
                        pltpu.SemaphoreType.DMA((ROW_BLOCK,))],
        interpret=_interpret_params(interpret),
    )(_pad_to(row_col.astype(jnp.int32), n_col, 0),
      rows.astype(jnp.uint32),
      q_words.astype(jnp.uint32).reshape(q_words.shape[0], 1, WORDS))
    return out[:n, 0]


def _max2(x):
    return jnp.max(jnp.max(x, axis=0, keepdims=True), axis=1, keepdims=True)


def _min2(x):
    return jnp.min(jnp.min(x, axis=0, keepdims=True), axis=1, keepdims=True)


def _select_ids_kernel(score_ref, inter_ref, gidx_ref, idx_ref, sco_ref,
                       int_ref, *, k):
    """k rounds of (max, lowest GLOBAL id among the maxes) over one
    block: the pinned global-id tie rule.  Entries of the winning id
    mask together, so identical padding entries cannot occupy more than
    one round.  Results accumulate in (1, kp) vectors (the TPU stores no
    scalars to VMEM) and are written once."""
    g = gidx_ref[...]
    it = inter_ref[...]
    lane = jax.lax.broadcasted_iota(jnp.int32, idx_ref.shape, 1)

    def round_(i, carry):
        s, oi, os_, on = carry
        m = _max2(s)
        w = _min2(jnp.where(s == m, g, _BIG))
        hit = (g == w) & (s == m)
        v = _max2(jnp.where(hit, it, 0))
        sel = lane == i
        return (jnp.where(hit, jnp.float32(-2.0), s),
                jnp.where(sel, w, oi), jnp.where(sel, m, os_),
                jnp.where(sel, v, on))

    zi = jnp.zeros(idx_ref.shape, jnp.int32)
    _, oi, os_, on = jax.lax.fori_loop(
        0, k, round_,
        (score_ref[...], zi, jnp.zeros(idx_ref.shape, jnp.float32), zi))
    idx_ref[...] = oi
    sco_ref[...] = os_
    int_ref[...] = on


def _select_blocks(score, inter, gidx, k, interpret):
    """One select pass: every (rows, 128) block of the flat labelled
    entries yields its k best as (nb, 1, kp) rows."""
    m = score.shape[0]
    # a block at least 2k wide: every pass over > 1 block halves the
    # entries, so the merge passes terminate
    rows = max(SELECT_ROWS, -(-2 * k // (8 * _LANES)) * 8)
    per_block = rows * _LANES
    if m > per_block:
        nb = -(-m // per_block)
    else:
        nb, rows = 1, max(8, -(-m // (8 * _LANES)) * 8)
    size = nb * rows * _LANES
    kp = -(-k // _LANES) * _LANES
    ins = [_pad_to(score.astype(jnp.float32), size, -2.0),
           _pad_to(inter.astype(jnp.int32), size, 0),
           _pad_to(gidx.astype(jnp.int32), size, _BIG)]
    blk = pl.BlockSpec((rows, _LANES), lambda i: (i, 0))
    out = pl.BlockSpec((None, 1, kp), lambda i: (i, 0, 0))
    return pl.pallas_call(
        functools.partial(_select_ids_kernel, k=k),
        grid=(nb,),
        in_specs=[blk, blk, blk],
        out_specs=[out, out, out],
        out_shape=[jax.ShapeDtypeStruct((nb, 1, kp), jnp.int32),
                   jax.ShapeDtypeStruct((nb, 1, kp), jnp.float32),
                   jax.ShapeDtypeStruct((nb, 1, kp), jnp.int32)],
        interpret=interpret,
    )(*[x.reshape(nb * rows, _LANES) for x in ins])


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def topk_merge(score: jax.Array, inter: jax.Array, gidx: jax.Array,
               k: int, *, interpret: bool | None = None
               ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Top-k over labelled (score, inter, gidx) entries: k rounds of (max
    score, LOWEST global id among the maxes), ties to the lowest GLOBAL
    index -- bit-identical to ``ref.topk_select_ids`` over the whole
    vector.  Serves the single-device candidate vector (labels
    ``arange(T)``) and labelled candidate subsets (global ids)."""
    assert k >= 1
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    while True:
        idx, sco, intr = _select_blocks(score, inter, gidx, k, interpret)
        if idx.shape[0] == 1:
            return idx[0, 0, :k], sco[0, 0, :k], intr[0, 0, :k]
        gidx = idx[:, 0, :k].reshape(-1)
        score = sco[:, 0, :k].reshape(-1)
        inter = intr[:, 0, :k].reshape(-1)


@functools.partial(jax.jit, static_argnames=("metric", "k", "interpret"))
def similarity_topk(rows: jax.Array, row_col: jax.Array, starts: jax.Array,
                    q_words: jax.Array, q_card: jax.Array, cards: jax.Array,
                    exclude: jax.Array = -1, seg: jax.Array | None = None,
                    *, metric: str, k: int, interpret: bool | None = None
                    ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Fused score + k-select over a device-resident candidate slab.

    rows:    (N, WORDS) uint32 candidate container rows, candidate-major.
    row_col: (N,) int32 global-key column of each row (indexes q_words).
    starts:  (T + 1,) int32 per-candidate row offsets (ragged segments).
    q_words: (C, WORDS) uint32 query bitset rows over the global keys.
    q_card:  scalar int32 query cardinality; cards: (T,) int32.
    exclude: runtime int32 candidate index scored -1 (-1: none).
    seg:     optional (N,) int32 candidate of each row, T on padding rows
             (the engine's cached map); None derives it from ``starts``.
    metric:  "jaccard" | "cosine" | "containment" (static).
    k:       static selection size.

    Returns (idx (k,) int32, score (k,) float32, inter (k,) int32),
    best-first, ties to the lowest index.  One jit end-to-end.

    Tie order is a PINNED contract: equal scores cut at the k boundary
    resolve to the lowest candidate index, and on the sharded path
    (``similarity_topk_ids`` with ``axis``) to the lowest GLOBAL
    candidate index -- so a tie group whose rows sit on several shards
    selects in exactly the order this single-device path (and the
    stable host argsort) would emit.
    """
    assert metric in METRICS, metric
    assert k >= 1
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    t = starts.shape[0] - 1
    inter = candidate_inter(
        row_and_card(rows, row_col, q_words, interpret=interpret),
        starts.astype(jnp.int32), seg)
    score = similarity_scores(inter, jnp.asarray(q_card, jnp.int32),
                              cards, metric)
    score = jnp.where(jnp.arange(t) == exclude, jnp.float32(-1.0), score)
    return topk_merge(score, inter, jnp.arange(t, dtype=jnp.int32), k,
                      interpret=interpret)


@functools.partial(jax.jit,
                   static_argnames=("metric", "k", "axis", "interpret"))
def similarity_topk_ids(rows: jax.Array, row_col: jax.Array,
                        starts: jax.Array, q_words: jax.Array,
                        q_card: jax.Array, cards: jax.Array,
                        gidx: jax.Array, n_valid: jax.Array,
                        exclude: jax.Array = -1, *, metric: str, k: int,
                        axis: str | None = None,
                        interpret: bool | None = None
                        ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Fused score + k-select over a candidate SUBSET labelled with
    global ids.

    Layout matches :func:`similarity_topk` with four additions carried
    by ``kernels.ref.similarity_topk_ids`` (the oracle): ``gidx`` (T,)
    int32 global candidate ids (selection/exclusion key on them),
    ``n_valid`` runtime scalar valid-slot count (pad slots score -2.0),
    ``exclude`` a GLOBAL id (-1: none), and ``axis``: inside
    ``shard_map``, the mesh axis the candidates' rows are split over --
    each shard passes only the rows it holds (``starts`` indexes them),
    and the per-candidate partial intersections are summed across the
    axis before scoring, so every shard selects from the same totals.
    Returns (gidx (k,) int32, score (k,) float32, inter (k,) int32),
    ties to the lowest GLOBAL index."""
    assert metric in METRICS, metric
    assert k >= 1
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    t = starts.shape[0] - 1
    inter = candidate_inter(
        row_and_card(rows, row_col, q_words, interpret=interpret),
        starts.astype(jnp.int32))
    if axis is not None:
        inter = jax.lax.psum(inter, axis)
    score = similarity_scores(inter, jnp.asarray(q_card, jnp.int32),
                              cards, metric)
    # exclusion keys on the GLOBAL id; pad slots (>= n_valid) are forced
    # to -2.0 LAST -- an all-zero pad row would otherwise score 1.0 under
    # the zero-denominator convention
    score = jnp.where(gidx == exclude, jnp.float32(-1.0), score)
    score = jnp.where(jnp.arange(t) >= n_valid, jnp.float32(-2.0), score)
    return topk_merge(score, inter, gidx, k, interpret=interpret)
