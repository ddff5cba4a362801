"""Pure-jnp oracles for every Pallas kernel in this package.

Device bitset convention: one Roaring bitset container = 2048 x uint32 words;
bit ``i`` of the container lives in ``words[i >> 5]`` at position ``i & 31``.
(The host path uses 1024 x uint64; the uint32 choice matches the TPU VPU's
32-bit lanes -- see DESIGN.md section 3.)
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

WORDS = 2048            # uint32 words per 2^16-bit container
CONTAINER_BITS = 1 << 16
ARRAY_CAP = 4096        # fixed capacity of the array-value slab

_M1 = jnp.uint32(0x55555555)
_M2 = jnp.uint32(0x33333333)
_M4 = jnp.uint32(0x0F0F0F0F)
_H01 = jnp.uint32(0x01010101)


def popcount_u32(v: jax.Array) -> jax.Array:
    """SWAR per-lane popcount of uint32 values -> int32."""
    v = v.astype(jnp.uint32)
    v = v - ((v >> jnp.uint32(1)) & _M1)
    v = (v & _M2) + ((v >> jnp.uint32(2)) & _M2)
    v = (v + (v >> jnp.uint32(4))) & _M4
    return ((v * _H01) >> jnp.uint32(24)).astype(jnp.int32)


def popcount_words(words: jax.Array) -> jax.Array:
    """(..., WORDS) uint32 -> (...,) int32 cardinality (section 4.1.1 oracle)."""
    return popcount_u32(words).sum(axis=-1).astype(jnp.int32)


def bitset_op(a: jax.Array, b: jax.Array, op: str) -> tuple[jax.Array, jax.Array]:
    """(..., WORDS) x2 -> (result words, cardinality).  Section 4.1.2 oracle."""
    a = a.astype(jnp.uint32)
    b = b.astype(jnp.uint32)
    if op == "and":
        r = a & b
    elif op == "or":
        r = a | b
    elif op == "xor":
        r = a ^ b
    elif op == "andnot":
        r = a & ~b
    else:
        raise ValueError(op)
    return r, popcount_words(r)


def bitset_op_card(a: jax.Array, b: jax.Array, op: str) -> jax.Array:
    """Count-only variant (paper section 5.9): never materializes ``r``
    outside registers."""
    return bitset_op(a, b, op)[1]


PAIR_OPS = ("and", "or", "xor", "andnot")   # index == per-row op id


def bitset_pair_op(a: jax.Array, b: jax.Array,
                   opids: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Mixed-op batched bitset algebra (section 4.1.2 generalized): one
    dispatch applies a *different* logical op per row.

    a/b: (M, WORDS) uint32; opids: (M,) int32 indexing ``PAIR_OPS``
    (0 and, 1 or, 2 xor, 3 andnot).  Returns (words, cards)."""
    a = a.astype(jnp.uint32)
    b = b.astype(jnp.uint32)
    sel = opids.astype(jnp.int32)[:, None]
    r = jnp.where(sel == 0, a & b,
                  jnp.where(sel == 1, a | b,
                            jnp.where(sel == 2, a ^ b, a & ~b)))
    return r, popcount_words(r)


def bitset_pair_card(a: jax.Array, b: jax.Array,
                     opids: jax.Array) -> jax.Array:
    """Count-only mixed-op batch (the similarity-join hot path: never
    materializes the result words in HBM)."""
    return bitset_pair_op(a, b, opids)[1]


def array_to_bitset(values: jax.Array, card: jax.Array) -> jax.Array:
    """Sorted uint16-valued (N, ARRAY_CAP) int32 arrays (first ``card`` entries
    valid) -> (N, WORDS) uint32 bitsets.  Oracle for the section 3.2 analogue.

    Uses the disjoint-contribution sum trick: values are distinct, so each
    (word, bit) pair is hit at most once and OR == +.
    """
    n = values.shape[0]
    valid = (jnp.arange(ARRAY_CAP)[None, :] < card[:, None])
    word_idx = jnp.where(valid, values >> 5, WORDS)  # out-of-range drops
    bit = jnp.where(valid, jnp.uint32(1) << (values & 31).astype(jnp.uint32),
                    jnp.uint32(0))

    def one(widx, b):
        return jnp.zeros(WORDS, jnp.uint32).at[widx].add(b, mode="drop")

    return jax.vmap(one)(word_idx, bit)


def bitset_set_many(words: jax.Array, values: jax.Array,
                    card: jax.Array) -> tuple[jax.Array, jax.Array]:
    """OR an array container into an existing bitset, tracking the cardinality
    delta via the paper's XOR trick (section 3.2).  Returns (words, delta)."""
    add = array_to_bitset(values, card)
    new = words | add
    delta = popcount_words(words ^ new)
    return new, delta


def bitset_to_array(words: jax.Array) -> tuple[jax.Array, jax.Array]:
    """(N, WORDS) uint32 -> ((N, ARRAY_CAP) int32 sorted values, (N,) card).

    Oracle for the section 3.1 extraction.  Positions beyond the cardinality
    are padded with CONTAINER_BITS (an impossible value).  Only meaningful
    when card <= ARRAY_CAP (the Roaring array-container invariant); extra
    values are dropped, matching the fixed-capacity device layout.
    """
    n = words.shape[0]
    bit_pos = jnp.arange(CONTAINER_BITS)
    bits = ((words[:, bit_pos >> 5] >> (bit_pos & 31).astype(jnp.uint32))
            & jnp.uint32(1)).astype(jnp.int32)
    csum = jnp.cumsum(bits, axis=-1)
    card = csum[:, -1]
    # value k of the output = first position whose running count is k+1
    targets = jnp.arange(1, ARRAY_CAP + 1)

    def one(cs):
        return jnp.searchsorted(cs, targets, side="left").astype(jnp.int32)

    vals = jax.vmap(one)(csum)
    vals = jnp.where(targets[None, :] <= card[:, None], vals,
                     jnp.int32(CONTAINER_BITS))
    return vals, card.astype(jnp.int32)


def array_intersect_mask(a_vals: jax.Array, a_card: jax.Array,
                         b_vals: jax.Array, b_card: jax.Array) -> tuple[jax.Array, jax.Array]:
    """All-vs-all membership (the pcmpistrm analogue, section 4.2 oracle).

    Inputs: (N, ARRAY_CAP) int32 sorted values + (N,) cards.
    Returns (mask (N, ARRAY_CAP) bool over A's slots, counts (N,) int32).
    """
    va = (jnp.arange(ARRAY_CAP)[None, :] < a_card[:, None])
    vb = (jnp.arange(ARRAY_CAP)[None, :] < b_card[:, None])
    eq = (a_vals[:, :, None] == b_vals[:, None, :]) & vb[:, None, :]
    mask = eq.any(axis=-1) & va
    return mask, mask.sum(axis=-1).astype(jnp.int32)


def array_intersect_count(a_vals: jax.Array, a_card: jax.Array,
                          b_vals: jax.Array, b_card: jax.Array) -> jax.Array:
    """Memory-lean count-only intersection oracle: a vectorized binary
    search per A value (O(M * ARRAY_CAP) memory) instead of the
    ``array_intersect_mask`` all-vs-all cube (O(M * ARRAY_CAP^2)) --
    the count path must scale to planner-sized batches."""
    pad = jnp.int32(CONTAINER_BITS)
    pos = jnp.arange(ARRAY_CAP)[None, :]
    va = pos < a_card[:, None]
    b_sorted = jnp.where(pos < b_card[:, None], b_vals, pad)

    def one(b_row, a_row):
        return jnp.searchsorted(b_row, a_row).astype(jnp.int32)

    idx = jnp.minimum(jax.vmap(one)(b_sorted, a_vals), ARRAY_CAP - 1)
    hit = (jnp.take_along_axis(b_sorted, idx, axis=1) == a_vals) & va
    return hit.sum(axis=-1).astype(jnp.int32)


def array_pair_masks(a_vals: jax.Array, a_card: jax.Array,
                     b_vals: jax.Array, b_card: jax.Array
                     ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Two-sided all-vs-all membership (sections 4.2-4.5 oracle).

    Like ``array_intersect_mask`` but also emits the B-side mask, so one
    dispatch feeds every materializing array-array op: AND keeps A's hits,
    ANDNOT drops them, OR appends B's misses, XOR keeps both sides' misses.
    Returns (mask_a (M, ARRAY_CAP), mask_b (M, ARRAY_CAP), count (M,))."""
    va = (jnp.arange(ARRAY_CAP)[None, :] < a_card[:, None])
    vb = (jnp.arange(ARRAY_CAP)[None, :] < b_card[:, None])
    eq = ((a_vals[:, :, None] == b_vals[:, None, :])
          & va[:, :, None] & vb[:, None, :])
    mask_a = eq.any(axis=-1)
    mask_b = eq.any(axis=1)
    return (mask_a.astype(jnp.int32), mask_b.astype(jnp.int32),
            mask_a.sum(axis=-1).astype(jnp.int32))


def array_bitset_probe(vals: jax.Array, card: jax.Array,
                       words: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Vectorized probe of sorted array values against a bitset row (the
    asymmetric intersection of section 4.2: binary search degenerates to a
    direct word fetch + bit test in the bitset domain).

    vals: (M, ARRAY_CAP) int32 sorted uint16-valued (slots >= card ignored);
    card: (M,) int32; words: (M, WORDS) uint32.  Returns
    (mask (M, ARRAY_CAP) int32 over the array's slots, count (M,))."""
    valid = (jnp.arange(ARRAY_CAP)[None, :] < card[:, None])
    widx = jnp.clip(vals >> 5, 0, WORDS - 1)
    w = jnp.take_along_axis(words.astype(jnp.uint32), widx, axis=1)
    bit = (w >> (vals & 31).astype(jnp.uint32)) & jnp.uint32(1)
    mask = jnp.where(valid, bit.astype(jnp.int32), 0)
    return mask, mask.sum(axis=-1).astype(jnp.int32)


METRICS = ("jaccard", "cosine", "containment")   # index == metric id


def _f32_parts(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Positive normal float32 -> (24-bit integer significand m, unbiased
    exponent e) with x == m * 2^(e - 23)."""
    bits = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.int32)
    return (bits & 0x7FFFFF) | 0x800000, ((bits >> 23) & 0xFF) - 127


def _f32_round(q: jax.Array, sticky: jax.Array, e: jax.Array) -> jax.Array:
    """Round a 25-bit significand ``q`` (24 bits + guard; ``sticky`` marks
    a nonzero tail) to nearest-even float32 with unbiased exponent ``e``."""
    mant = q >> 1
    up = ((q & 1) == 1) & (sticky | ((mant & 1) == 1))
    mant = mant + up.astype(jnp.int32)
    carry = mant >> 24                    # rounded up to 2^24: renormalize
    bits = ((e + carry + 127) << 23) | ((mant >> carry) & 0x7FFFFF)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def div_rn(a: jax.Array, b: jax.Array) -> jax.Array:
    """Correctly rounded float32 ``a / b`` for ``a >= 0`` and ``b > 0``
    whose quotient is 0 or a normal float32.

    The TPU computes float32 division by a reciprocal approximation that
    misses the nearest float32 on roughly a third of integer quotients
    (and ``1/3`` and ``2/6`` then differ), so scores could neither match
    the host twin bit for bit nor tie where the host ties.  Integer long
    division of the significands is exact on every backend."""
    ma, ea = _f32_parts(a)
    mb, eb = _f32_parts(b)
    lt = ma < mb

    def digit(_, carry):
        q, r = carry
        r = r << 1
        bit = r >= mb
        return (q << 1) | bit.astype(jnp.int32), jnp.where(bit, r - mb, r)

    # quotient significand in [1, 2): a leading 1, then 24 more bits
    q, r = jax.lax.fori_loop(0, 24, digit, (
        jnp.ones_like(ma), jnp.where(lt, ma << 1, ma) - mb))
    out = _f32_round(q, r != 0, ea - eb - lt.astype(jnp.int32))
    return jnp.where(a == 0, jnp.float32(0.0), out)


def sqrt_rn(x: jax.Array) -> jax.Array:
    """Correctly rounded float32 square root of ``x >= 0`` (normal or 0),
    by digit-by-digit integer square root: the TPU's float32 ``sqrt`` is
    an approximation, like its division (see :func:`div_rn`)."""
    m, e = _f32_parts(x)
    odd = ((e - 23) & 1) == 1
    big = jnp.where(odd, m << 1, m)       # x = big * 2^(2h), big < 2^25
    h = (e - 23 - odd.astype(jnp.int32)) >> 1

    def digit(i, carry):                  # isqrt(big * 2^26), 2 bits a step
        root, rem = carry
        pair = jnp.where(i < 13, (big >> jnp.maximum(24 - 2 * i, 0)) & 3, 0)
        rem = (rem << 2) | pair
        trial = (root << 2) | 1
        ge = rem >= trial
        return (root << 1) | ge.astype(jnp.int32), \
            jnp.where(ge, rem - trial, rem)

    root, rem = jax.lax.fori_loop(0, 26, digit, (jnp.zeros_like(m),
                                                 jnp.zeros_like(m)))
    wide = root >= (1 << 25)              # 26-bit root: drop one more bit
    q = jnp.where(wide, root >> 1, root)
    sticky = (rem != 0) | (wide & ((root & 1) == 1))
    out = _f32_round(q, sticky, h + 11 + wide.astype(jnp.int32))
    return jnp.where(x == 0, jnp.float32(0.0), out)


def similarity_scores(inter: jax.Array, q_card: jax.Array,
                      cards: jax.Array, metric: str) -> jax.Array:
    """Similarity scores from intersection cardinalities, float32.

    All three metrics derive from the AND cardinality by inclusion-
    exclusion ("beyond unions and intersections", Kaser & Lemire):
    jaccard = |A∩B| / |A∪B|, cosine = |A∩B| / sqrt(|A||B|),
    containment = |A∩B| / |A| (the query side).  A zero denominator
    scores 1.0 (the host convention).  The formula is evaluated in
    float32 with a fixed operation order and correctly rounded division
    and square root (:func:`div_rn`, :func:`sqrt_rn`), so the device
    path, the jnp oracle, and the numpy host twin
    (core.pairwise._scores_host) produce bit-identical scores -- top-k
    tie ordering depends on it."""
    interf = inter.astype(jnp.float32)
    qc = q_card.astype(jnp.float32)
    oc = cards.astype(jnp.float32)
    if metric == "jaccard":
        denom = qc + oc - interf
    elif metric == "cosine":
        denom = sqrt_rn(qc * oc)
    elif metric == "containment":
        denom = jnp.broadcast_to(qc, oc.shape)
    else:
        raise ValueError(metric)
    return jnp.where(denom > 0, div_rn(interf, denom),
                     jnp.float32(1.0))


def candidate_inter(per_row: jax.Array, starts: jax.Array) -> jax.Array:
    """Per-candidate intersection cardinalities from per-row counts:
    candidate ``t`` owns rows ``starts[t]:starts[t+1]``; rows past
    ``starts[-1]`` (layout padding) are dropped.  A per-segment sum, NOT
    a global prefix: the grand total of intersection bits across all
    candidates can overflow int32 even though each candidate's own count
    cannot."""
    t = starts.shape[0] - 1
    seg_id = jnp.searchsorted(starts[1:], jnp.arange(per_row.shape[0]),
                              side="right")
    return jax.ops.segment_sum(per_row, seg_id, num_segments=t) \
        .astype(jnp.int32)


def topk_select(score: jax.Array, inter: jax.Array,
                k: int) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Iterative first-max top-k selection (the threshold-refinement
    pass): k rounds of argmax, ties resolved to the LOWEST index --
    exactly the order of a stable host argsort on the negated scores.
    Returns (idx (k,) int32, score (k,) float32, inter (k,) int32)."""
    idxs, scores, inters = [], [], []
    for _ in range(k):
        j = jnp.argmax(score)                   # first occurrence wins
        idxs.append(j.astype(jnp.int32))
        scores.append(score[j])
        inters.append(inter[j].astype(jnp.int32))
        score = score.at[j].set(jnp.float32(-2.0))
    return jnp.stack(idxs), jnp.stack(scores), jnp.stack(inters)


def similarity_topk(rows: jax.Array, row_col: jax.Array, starts: jax.Array,
                    q_words: jax.Array, q_card: jax.Array, cards: jax.Array,
                    exclude: jax.Array, *, metric: str, k: int
                    ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Fused similarity scoring + top-k selection oracle (one jit).

    rows:    (N, WORDS) uint32 candidate container rows, candidate-major
             (rows of candidate t occupy starts[t]:starts[t+1]).
    row_col: (N,) int32 column of each row's chunk key in ``q_words``.
    starts:  (T + 1,) int32 per-candidate row offsets.
    q_words: (C, WORDS) uint32 query containers in bitset domain, one row
             per global chunk key (zeros where the query has no container).
    q_card / cards: query / per-candidate (T,) cardinalities, int32.
    exclude: runtime int32 candidate index whose score is forced to -1
             (the query itself in an index join); -1 excludes nothing.

    Returns (idx (k,) int32, score (k,) float32, inter (k,) int32),
    best-first, ties at equal score resolved to the lowest index."""
    rows = rows.astype(jnp.uint32)
    t = starts.shape[0] - 1
    inter = candidate_inter(popcount_words(rows & q_words[row_col]), starts)
    score = similarity_scores(inter, q_card, cards, metric)
    score = jnp.where(jnp.arange(t) == exclude, jnp.float32(-1.0), score)
    return topk_select(score, inter, k)


def topk_select_ids(score: jax.Array, inter: jax.Array, gidx: jax.Array,
                    k: int) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Top-k selection over *labelled* scores: k rounds of (max score,
    LOWEST global id among the maxes), the selected id's entries masked
    to -2.0.

    This is the tie rule pinned by the pruned and sharded similarity
    paths: every entry carries its GLOBAL candidate index ``gidx``, and a
    tie group cut at the k boundary resolves to ascending global index.
    Over any labelled subset that holds the top k it reproduces the
    single-device ``topk_select`` order exactly, because both implement
    the same total order (score descending, global index ascending).

    Returns (gidx (k,) int32, score (k,) float32, inter (k,) int32).
    ``gidx`` values may repeat only for padding entries (score < -1);
    duplicates of one id are masked together in a single round."""
    big = jnp.int32(2**31 - 1)
    ids, scores, inters = [], [], []
    for _ in range(k):
        m = jnp.max(score)
        g = jnp.min(jnp.where(score == m, gidx, big))
        hit = (gidx == g) & (score == m)
        ids.append(g.astype(jnp.int32))
        scores.append(m)
        inters.append(jnp.max(jnp.where(hit, inter, 0)).astype(jnp.int32))
        score = jnp.where(hit, jnp.float32(-2.0), score)
    return jnp.stack(ids), jnp.stack(scores), jnp.stack(inters)


def similarity_topk_ids(rows: jax.Array, row_col: jax.Array,
                        starts: jax.Array, q_words: jax.Array,
                        q_card: jax.Array, cards: jax.Array,
                        gidx: jax.Array, n_valid: jax.Array,
                        exclude: jax.Array, *, metric: str, k: int,
                        axis: str | None = None
                        ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Fused score + select: :func:`similarity_topk` over a candidate
    SUBSET labelled with global ids (a pruned candidate list, whole or
    split across the shards of a mesh axis).

    Differences from the dense oracle: ``gidx`` (T,) int32 carries each
    local slot's GLOBAL candidate index (selection and exclusion key on
    it); ``n_valid`` is a runtime scalar -- slots >= n_valid are layout
    padding and score -2.0 no matter what their padded rows/cards say
    (an all-zero pad row under the cosine/zero-denominator convention
    would otherwise score 1.0 and corrupt the local top-k); ``exclude``
    is a GLOBAL candidate id (scored -1.0; -1 none).  With ``axis``
    (inside ``shard_map``) each shard holds only some of every
    candidate's rows: the per-candidate partial intersections are summed
    across the axis before scoring.

    Returns (gidx (k,) int32, score (k,) float32, inter (k,) int32),
    best-first, score ties to the lowest GLOBAL index
    (:func:`topk_select_ids`)."""
    rows = rows.astype(jnp.uint32)
    t = starts.shape[0] - 1
    inter = candidate_inter(popcount_words(rows & q_words[row_col]), starts)
    if axis is not None:
        inter = jax.lax.psum(inter, axis)
    score = similarity_scores(inter, q_card, cards, metric)
    score = jnp.where(gidx == exclude, jnp.float32(-1.0), score)
    score = jnp.where(jnp.arange(t) >= n_valid, jnp.float32(-2.0), score)
    return topk_select_ids(score, inter, gidx, k)


def merge_sorted(a_vals: jax.Array, a_card: jax.Array,
                 b_vals: jax.Array, b_card: jax.Array,
                 cap: int = 2 * ARRAY_CAP) -> tuple[jax.Array, jax.Array]:
    """Branch-free merge of two padded sorted arrays (section 4.3 oracle for
    the sorting-network merger): returns (merged (N, cap) int32 with PAD at
    the tail, total count).  PAD = CONTAINER_BITS."""
    pad = jnp.int32(CONTAINER_BITS)
    a = jnp.where(jnp.arange(a_vals.shape[1])[None] < a_card[:, None],
                  a_vals, pad)
    b = jnp.where(jnp.arange(b_vals.shape[1])[None] < b_card[:, None],
                  b_vals, pad)
    merged = jnp.sort(jnp.concatenate([a, b], axis=-1), axis=-1)[:, :cap]
    return merged, (a_card + b_card).astype(jnp.int32)


def dedup_sorted(merged: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Union-style dedup (section 4.3 store_unique oracle): keep one copy of
    each duplicated value; stable-compacts to the left, PAD at the tail."""
    pad = jnp.int32(CONTAINER_BITS)
    prev = jnp.concatenate(
        [jnp.full((merged.shape[0], 1), -1, merged.dtype), merged[:, :-1]],
        axis=-1)
    keep = (merged != prev) & (merged < pad)
    return _compact(merged, keep)


def xor_dedup_sorted(merged: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Symmetric-difference dedup (section 4.5 oracle): drop values that occur
    twice entirely (inputs are sets, so multiplicity is 1 or 2)."""
    pad = jnp.int32(CONTAINER_BITS)
    prev = jnp.concatenate(
        [jnp.full((merged.shape[0], 1), -1, merged.dtype), merged[:, :-1]],
        axis=-1)
    nxt = jnp.concatenate(
        [merged[:, 1:], jnp.full((merged.shape[0], 1), -2, merged.dtype)],
        axis=-1)
    keep = (merged != prev) & (merged != nxt) & (merged < pad)
    return _compact(merged, keep)


def _compact(vals: jax.Array, keep: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Stable left-compaction of kept values; the TPU-idiomatic stream
    compaction is a prefix sum + scatter."""
    pad = jnp.int32(CONTAINER_BITS)
    rank = jnp.cumsum(keep.astype(jnp.int32), axis=-1) - 1
    count = jnp.where(keep.any(-1), rank[:, -1] + 1, 0).astype(jnp.int32)
    dst = jnp.where(keep, rank, vals.shape[1])  # dropped -> OOB

    def one(v, d):
        return jnp.full(vals.shape[1], pad, vals.dtype).at[d].set(
            v, mode="drop")

    return jax.vmap(one)(vals, dst), count


# ---------------------------------------------------------------------------
# segmented wide-aggregation oracle (paper sec 5.8 generalized; see
# kernels/segment_ops.py for the Pallas twin)
# ---------------------------------------------------------------------------

def segment_reduce(slab: jax.Array, starts: jax.Array, op: str, *,
                   jmax: int, threshold: int = 0,
                   weights: jax.Array | None = None
                   ) -> tuple[jax.Array, jax.Array]:
    """Per-segment OR/AND/XOR/ANDNOT/threshold reduction + cardinality.

    slab: (N, WORDS) uint32 rows grouped segment-major; starts: (S + 1,)
    int32 row offsets; jmax: static max segment length.  Returns
    (words (S, WORDS) uint32, cards (S,) int32).  Empty segments reduce to
    zero words / zero cardinality for every op.

    op "andnot" treats each segment's FIRST row as the minuend and the rest
    as subtrahends: row0 & ~(row1 | row2 | ...).  ``weights`` (N,) int32 are
    per-row occurrence weights for op "threshold" (default 1 per row).
    ``threshold`` is a runtime scalar OR a (S,) int32 vector of per-segment
    thresholds (the multi-query coalescing path: every queued T-occurrence
    query becomes one segment group of the same dispatch).
    """
    slab = slab.astype(jnp.uint32)
    starts = starts.astype(jnp.int32)
    n = slab.shape[0]
    seg_len = starts[1:] - starts[:-1]                    # (S,)
    row = starts[:-1, None] + jnp.arange(jmax, dtype=jnp.int32)[None, :]
    valid = row < starts[1:, None]                        # (S, jmax)
    g = slab[jnp.minimum(row, n - 1)]                     # (S, jmax, WORDS)
    if op == "threshold":
        g = jnp.where(valid[..., None], g, jnp.uint32(0))
        if weights is None:
            w = jnp.ones((g.shape[0], jmax), jnp.int32)
        else:
            w = weights.astype(jnp.int32)[jnp.minimum(row, n - 1)]
        w = jnp.where(valid, w, 0)
        t = jnp.asarray(threshold, jnp.int32)
        if t.ndim == 1:
            t = t[:, None]                                # (S, 1) vs (S, WORDS)
        out = jnp.zeros((g.shape[0], WORDS), jnp.uint32)
        for b in range(32):
            cnt = (((g >> jnp.uint32(b)) & jnp.uint32(1)).astype(jnp.int32)
                   * w[..., None]).sum(axis=1)
            hit = (cnt >= t).astype(jnp.uint32)
            out = out | (hit << jnp.uint32(b))
    elif op == "andnot":
        g = jnp.where(valid[..., None], g, jnp.uint32(0))
        first = g[:, 0]
        rest = jax.lax.reduce(g[:, 1:], jnp.uint32(0),
                              jax.numpy.bitwise_or, dimensions=(1,))
        out = first & ~rest
    else:
        ident = jnp.uint32(0xFFFFFFFF if op == "and" else 0)
        g = jnp.where(valid[..., None], g, ident)
        if op == "or":
            comb = jax.numpy.bitwise_or
        elif op == "and":
            comb = jax.numpy.bitwise_and
        elif op == "xor":
            comb = jax.numpy.bitwise_xor
        else:
            raise ValueError(op)
        out = jax.lax.reduce(g, ident, comb, dimensions=(1,))
    out = jnp.where((seg_len > 0)[:, None], out, jnp.uint32(0))
    return out, popcount_words(out)


def segment_reduce_rows(table: jax.Array, ids: jax.Array, starts: jax.Array,
                        op: str, *, jmax: int, threshold: int = 0,
                        weights: jax.Array | None = None
                        ) -> tuple[jax.Array, jax.Array]:
    """Resident-slab twin of :func:`segment_reduce`: gather ``ids`` rows
    from a device-resident ``table`` (arena slab, optionally with a staged
    host block appended), then reduce.  Under jit the gather fuses with
    the reduce, so resident rows never round-trip through the host --
    queries move only ``ids``/``starts`` over PCIe (see core/arena.py).
    ``ids`` index ``table`` segment-major; pad ragged segments with id 0
    (the arena's reserved all-zero row)."""
    slab = jnp.take(table.astype(jnp.uint32), ids.astype(jnp.int32), axis=0)
    return segment_reduce(slab, starts, op, jmax=jmax,
                          threshold=threshold, weights=weights)


def gather_rows_dual(table: jax.Array, staged: jax.Array,
                     pos: jax.Array, sidx: jax.Array) -> jax.Array:
    """Two-source row gather: slot ``i`` reads ``table[pos[i]] |
    staged[sidx[i]]``.  Exactly one side of every slot points at a real
    row; the other points at a reserved all-zero row (``table`` row /
    position 0 is the arena's zero row, ``staged`` row 0 is the block's),
    so the OR is exact slot selection -- zero is the OR identity, never a
    blend.  ``table`` may be one shard's slab inside ``shard_map``
    (``core.aggregate._sharded_rows_fn``), whose local row 0 is not zero
    except on shard 0 -- so staged rows are routed there."""
    return (jnp.take(table.astype(jnp.uint32), pos.astype(jnp.int32),
                     axis=0)
            | jnp.take(staged.astype(jnp.uint32), sidx.astype(jnp.int32),
                       axis=0))


def segment_reduce_rows_dual(table: jax.Array, staged: jax.Array,
                             pos: jax.Array, sidx: jax.Array,
                             starts: jax.Array, op: str, *, jmax: int,
                             threshold: int = 0,
                             weights: jax.Array | None = None
                             ) -> tuple[jax.Array, jax.Array]:
    """Row-table twin of :func:`segment_reduce_rows` for the arena's
    dual-source layout: resident rows gather from ``table`` by slab
    position (a single-device slab), cold rows from a small
    per-call ``staged`` block, via :func:`gather_rows_dual`.  Unlike
    ``segment_reduce_rows`` with an appended host block, the resident
    table is never copied per call.  Pad slots point both indices at the
    zero rows."""
    slab = gather_rows_dual(table, staged, pos, sidx)
    return segment_reduce(slab, starts, op, jmax=jmax,
                          threshold=threshold, weights=weights)


# ---------------------------------------------------------------------------
# bit-sliced occurrence counters (the exchange payload of the sharded
# threshold path: each shard counts locally, counters are all-gathered and
# added bit-sliced, then one comparator pass emits the result words)
# ---------------------------------------------------------------------------

def segment_counters(slab: jax.Array, starts: jax.Array, *, jmax: int,
                     planes: int,
                     weights: jax.Array | None = None) -> jax.Array:
    """Per-segment bit-sliced occurrence counters.

    Counts, for every one of the 2^16 bit positions, the (weighted) number
    of rows of the segment that set it, and returns the counts bit-sliced:
    ``(S, planes, WORDS)`` uint32 where plane ``p`` holds bit ``p`` of each
    position's count.  ``planes`` must satisfy ``max count < 2^planes``.
    """
    slab = slab.astype(jnp.uint32)
    starts = starts.astype(jnp.int32)
    n = slab.shape[0]
    row = starts[:-1, None] + jnp.arange(jmax, dtype=jnp.int32)[None, :]
    valid = row < starts[1:, None]
    g = jnp.where(valid[..., None], slab[jnp.minimum(row, n - 1)],
                  jnp.uint32(0))                          # (S, jmax, WORDS)
    if weights is None:
        w = jnp.ones((g.shape[0], jmax), jnp.int32)
    else:
        w = weights.astype(jnp.int32)[jnp.minimum(row, n - 1)]
    w = jnp.where(valid, w, 0)
    # one expensive (S, jmax, WORDS) reduction per bit position; the plane
    # extraction afterwards is cheap elementwise work
    out = [jnp.zeros((g.shape[0], WORDS), jnp.uint32) for _ in range(planes)]
    for b in range(32):
        cnt = (((g >> jnp.uint32(b)) & jnp.uint32(1)).astype(jnp.int32)
               * w[..., None]).sum(axis=1)
        for p in range(planes):
            bit = ((cnt >> p) & 1).astype(jnp.uint32)
            out[p] = out[p] | (bit << jnp.uint32(b))
    return jnp.stack(out, axis=1)


def bitsliced_add(a: jax.Array, b: jax.Array) -> jax.Array:
    """Ripple-carry add of two bit-sliced counter sets (..., planes, WORDS).

    The result keeps the same number of planes; callers must size ``planes``
    so the true sum never overflows (the sharded planner bounds it by the
    total weight across ALL shards)."""
    planes = a.shape[-2]
    carry = jnp.zeros_like(a[..., 0, :])
    out = []
    for i in range(planes):
        ai, bi = a[..., i, :], b[..., i, :]
        out.append(ai ^ bi ^ carry)
        carry = (ai & bi) | (carry & (ai ^ bi))
    return jnp.stack(out, axis=-2)


def counters_ge(planes_arr: jax.Array, t: jax.Array) -> jax.Array:
    """Bitwise magnitude comparator: positions whose bit-sliced count is
    >= t.  planes_arr: (..., planes, WORDS) uint32; t: runtime int32
    scalar, or a (S,) vector of per-segment thresholds against a
    (S, planes, WORDS) counter set (the coalesced multi-query path).
    Returns (..., WORDS) uint32 result words."""
    full = jnp.uint32(0xFFFFFFFF)
    n_planes = planes_arr.shape[-2]
    t = jnp.asarray(t, jnp.int32)
    if t.ndim == 1:
        t = t[:, None]                       # broadcast over the word lanes
    gt = jnp.zeros_like(planes_arr[..., 0, :])
    eq = jnp.full_like(gt, full)
    for i in reversed(range(n_planes)):
        ci = planes_arr[..., i, :]
        tmask = jnp.where((t >> i) & 1 == 1, full, jnp.uint32(0))
        gt = gt | (eq & ci & ~tmask)
        eq = eq & ~(ci ^ tmask)
    return gt | eq


# ---------------------------------------------------------------------------
# Roaring-masked block-sparse attention (decode step) oracle
# ---------------------------------------------------------------------------

def block_sparse_attention_decode(
        q: jax.Array,            # (B, H, D)
        k: jax.Array,            # (B, Hkv, S, D)
        v: jax.Array,            # (B, Hkv, S, D)
        block_mask_words: jax.Array,  # (B, n_blocks/32) uint32 roaring bitset
        kv_len: jax.Array,       # (B,) int32 valid KV length
        block_size: int = 128,
        sm_scale: float | None = None,
        softcap: float = 0.0) -> jax.Array:
    """Reference decode attention where key/value *blocks* are visible only if
    their bit is set in a Roaring bitset container row.  Returns (B, H, D)."""
    b_, h, d = q.shape
    _, hkv, s, _ = k.shape
    n_blocks = s // block_size
    scale = (d ** -0.5) if sm_scale is None else sm_scale
    groups = h // hkv
    qg = q.reshape(b_, hkv, groups, d)
    scores = jnp.einsum("bkgd,bksd->bkgs", qg, k,
                        preferred_element_type=jnp.float32) * scale
    if softcap:
        scores = softcap * jnp.tanh(scores / softcap)
    blk = jnp.arange(s) // block_size
    visible = ((block_mask_words[:, blk >> 5] >> (blk & 31).astype(jnp.uint32))
               & jnp.uint32(1)).astype(bool)
    visible &= jnp.arange(s)[None, :] < kv_len[:, None]
    scores = jnp.where(visible[:, None, None, :], scores, -jnp.inf)
    w = jax.nn.softmax(scores, axis=-1)
    w = jnp.where(jnp.isnan(w), 0.0, w)  # fully-masked rows -> zero output
    out = jnp.einsum("bkgs,bksd->bkgd", w.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(b_, h, d).astype(q.dtype)
