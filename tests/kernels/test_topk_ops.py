"""Pallas fused top-k similarity kernel vs the pure-jnp oracle.

Random candidate slabs (ragged segments, empty candidates, layout padding
past the last candidate, one row per candidate, exclusion, every metric)
must produce identical (idx, score, inter) triples from
``topk_ops.similarity_topk`` (interpret mode) and ``ref.similarity_topk``
-- including first-max tie ordering, which the selection contract rides
on -- whether the kernel is given the layout's row-to-candidate map or
derives it from ``starts``."""

import numpy as np
import jax.numpy as jnp
import pytest

from repro.kernels import ref
from repro.kernels import topk_ops

WORDS = ref.WORDS


def random_case(rng, t, c, density=0.02, lens=None, pad=0):
    """Ragged candidate slab: each candidate owns ``lens[i]`` rows (0..4
    at random by default), each row a sparse bitset over one of ``c`` key
    columns; ``pad`` random rows follow the last candidate's (layout
    padding, which no candidate owns)."""
    if lens is None:
        lens = rng.integers(0, 5, t)
    rows, row_col, starts = [], [], [0]
    cards = []
    for n_rows in lens:
        card = 0
        for _ in range(int(n_rows)):
            w = (rng.random((WORDS,)) < density).astype(np.uint32)
            w = w * rng.integers(1, 1 << 32, WORDS, dtype=np.uint32)
            rows.append(w)
            row_col.append(int(rng.integers(0, c)))
            card += int(np.bitwise_count(w).sum())
        starts.append(len(rows))
        cards.append(card)
    for _ in range(pad):
        rows.append(rng.integers(0, 1 << 32, WORDS, dtype=np.uint32))
        row_col.append(int(rng.integers(0, c)))
    q = (rng.random((c, WORDS)) < density * 2).astype(np.uint32) \
        * rng.integers(1, 1 << 32, (c, WORDS), dtype=np.uint32)
    q_card = int(np.bitwise_count(q).sum())
    rows = np.stack(rows) if rows else np.zeros((1, WORDS), np.uint32)
    row_col = np.asarray(row_col, np.int32) if row_col else \
        np.zeros(1, np.int32)
    return (jnp.asarray(rows), jnp.asarray(row_col),
            jnp.asarray(np.asarray(starts, np.int32)), jnp.asarray(q),
            q_card, jnp.asarray(np.asarray(cards, np.int32)))


def row_candidates(starts, n_rows):
    """The row-to-candidate map by a plain loop: row r of candidate c
    maps to c, a row past the last candidate's to T."""
    st = np.asarray(starts).tolist()
    t = len(st) - 1
    seg = [t] * n_rows
    for c in range(t):
        for r in range(st[c], st[c + 1]):
            seg[r] = c
    return jnp.asarray(np.asarray(seg, np.int32))


def layouts(rng):
    """Zero-row candidates (4 of 12), the same with 5 padding rows past
    ``starts[-1]``, and one row per candidate (NETFLIX's layout)."""
    lens = [0, 2, 1, 0, 4, 1, 0, 3, 1, 2, 0, 1]
    yield random_case(rng, 12, 4, lens=lens)
    yield random_case(rng, 12, 4, lens=lens, pad=5)
    yield random_case(rng, 17, 4, lens=[1] * 17)


@pytest.mark.parametrize("given", [True, False], ids=["map_given",
                                                      "map_derived"])
@pytest.mark.parametrize("metric", ref.METRICS)
def test_kernel_matches_oracle(rng, metric, given):
    for rows, row_col, starts, q, q_card, cards in layouts(rng):
        seg = row_candidates(starts, rows.shape[0]) if given else None
        for exclude in (-1, 3):
            ki, ks, kn = topk_ops.similarity_topk(
                rows, row_col, starts, q, jnp.int32(q_card), cards,
                jnp.int32(exclude), seg, metric=metric, k=5,
                interpret=True)
            oi, os_, on = ref.similarity_topk(
                rows, row_col, starts, q, jnp.int32(q_card), cards,
                jnp.int32(exclude), metric=metric, k=5)
            assert np.array_equal(np.asarray(ki), np.asarray(oi))
            assert np.array_equal(np.asarray(ks), np.asarray(os_))
            assert np.array_equal(np.asarray(kn), np.asarray(on))
            assert exclude not in np.asarray(ki).tolist() or exclude == -1


def test_candidate_inter_drops_padding(rng):
    """Per-candidate sums by a given map equal the derived ones and a
    hand loop; rows mapped to T (padding) count for no candidate."""
    starts = jnp.asarray(np.asarray([0, 0, 3, 3, 4], np.int32))
    per_row = jnp.asarray(rng.integers(0, 1 << 12, 7, dtype=np.int32))
    seg = row_candidates(starts, 7)
    assert np.asarray(seg).tolist() == [1, 1, 1, 3, 4, 4, 4]
    pr = np.asarray(per_row)
    want = [0, int(pr[:3].sum()), 0, int(pr[3])]
    assert np.asarray(ref.candidate_inter(per_row, starts, seg)).tolist() \
        == want
    assert np.asarray(ref.candidate_inter(per_row, starts)).tolist() == want


def test_oracle_inter_and_tie_order(rng):
    """The oracle itself: inter equals a hand loop; exact ties order by
    ascending candidate index (the stable-argsort contract)."""
    rows, row_col, starts, q, q_card, cards = random_case(rng, 10, 3)
    oi, os_, on = ref.similarity_topk(rows, row_col, starts, q,
                                      jnp.int32(q_card), cards,
                                      jnp.int32(-1), metric="jaccard",
                                      k=10)
    rows_np = np.asarray(rows)
    q_np = np.asarray(q)
    st = np.asarray(starts)
    col = np.asarray(row_col)
    want_inter = []
    for t in range(10):
        tot = 0
        for r in range(st[t], st[t + 1]):
            tot += int(np.bitwise_count(rows_np[r] & q_np[col[r]]).sum())
        want_inter.append(tot)
    for i, n in zip(np.asarray(oi).tolist(), np.asarray(on).tolist()):
        assert n == want_inter[i]
    sc = np.asarray(os_)
    idx = np.asarray(oi)
    for a, b in zip(range(len(sc) - 1), range(1, len(sc))):
        assert sc[a] > sc[b] or (sc[a] == sc[b] and idx[a] < idx[b])


@pytest.mark.parametrize("given", [True, False], ids=["map_given",
                                                      "map_derived"])
def test_empty_segments_score_zero(rng, given):
    """Candidates with no rows (empty bitmaps) must score from
    inter = 0, not garbage, on both paths."""
    rows = jnp.asarray((rng.random((3, WORDS)) < 0.05)
                       .astype(np.uint32))
    row_col = jnp.asarray(np.zeros(3, np.int32))
    starts = jnp.asarray(np.asarray([0, 0, 3, 3], np.int32))  # t0/t2 empty
    seg = jnp.asarray(np.asarray([1, 1, 1], np.int32)) if given else None
    q = rows[:1]
    cards = jnp.asarray(np.asarray(
        [0, int(np.bitwise_count(np.asarray(rows)).sum()), 0], np.int32))
    q_card = int(np.bitwise_count(np.asarray(q)).sum())
    ki, ks, kn = topk_ops.similarity_topk(
        rows, row_col, starts, q, jnp.int32(q_card), cards,
        jnp.int32(-1), seg, metric="jaccard", k=3, interpret=True)
    oi, os_, on = ref.similarity_topk(
        rows, row_col, starts, q, jnp.int32(q_card), cards,
        jnp.int32(-1), metric="jaccard", k=3)
    assert np.array_equal(np.asarray(ki), np.asarray(oi))
    assert np.array_equal(np.asarray(ks), np.asarray(os_))
    assert np.array_equal(np.asarray(kn), np.asarray(on))
    assert np.asarray(kn).tolist()[1:] == [0, 0]   # the empty candidates
