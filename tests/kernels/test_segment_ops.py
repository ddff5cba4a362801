"""Segmented wide-aggregation kernel (interpret=True) vs the jnp oracle and
numpy ground truth: ragged segments, empty segments, threshold counters."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.segment_ops import counter_planes, segment_reduce

WORDS = ref.WORDS


def _np_reduce(slab, starts, op, t=0, w=None):
    s = starts.size - 1
    out = np.zeros((s, WORDS), np.uint32)
    for i in range(s):
        rows = slab[starts[i]:starts[i + 1]]
        if rows.shape[0] == 0:
            continue
        if op == "threshold":
            ws = np.ones(rows.shape[0], np.int64) if w is None else \
                w[starts[i]:starts[i + 1]].astype(np.int64)
            for b in range(32):
                cnt = (((rows >> np.uint32(b)) & 1) * ws[:, None]).sum(axis=0)
                out[i] |= np.uint32(1 << b) * (cnt >= t)
        elif op == "andnot":
            rest = np.bitwise_or.reduce(rows[1:], axis=0) \
                if rows.shape[0] > 1 else np.zeros(WORDS, np.uint32)
            out[i] = rows[0] & ~rest
        else:
            f = {"or": np.bitwise_or, "and": np.bitwise_and,
                 "xor": np.bitwise_xor}[op]
            out[i] = f.reduce(rows, axis=0)
    return out


def _segments(rng, n, s):
    cuts = np.sort(rng.choice(n + 1, s - 1, replace=True))
    return np.concatenate(([0], cuts, [n])).astype(np.int32)


@pytest.mark.parametrize("op", ["or", "and", "xor"])
@pytest.mark.parametrize("n,s", [(7, 3), (16, 1), (24, 9)])
def test_segment_reduce_vs_oracle(rng, op, n, s):
    slab = rng.integers(0, 1 << 32, (n, WORDS), dtype=np.uint32)
    starts = _segments(rng, n, s)
    jmax = max(1, int(np.diff(starts).max()))
    want = _np_reduce(slab, starts, op)
    want_c = np.bitwise_count(want).sum(axis=1)
    kw, kc = segment_reduce(jnp.asarray(slab), jnp.asarray(starts), op,
                            jmax=jmax, interpret=True)
    ow, oc = ref.segment_reduce(jnp.asarray(slab), jnp.asarray(starts), op,
                                jmax=jmax)
    assert np.array_equal(np.asarray(kw), want)
    assert np.array_equal(np.asarray(kc), want_c)
    assert np.array_equal(np.asarray(ow), want)
    assert np.array_equal(np.asarray(oc), want_c)


def test_segment_reduce_empty_and_overlong_segments(rng):
    """Empty segments reduce to zero for every op (even AND, whose step
    identity is all-ones); jmax may exceed the longest segment."""
    slab = rng.integers(0, 1 << 32, (5, WORDS), dtype=np.uint32)
    starts = np.array([0, 0, 3, 3, 5], np.int32)
    for op in ("or", "and", "xor"):
        kw, kc = segment_reduce(jnp.asarray(slab), jnp.asarray(starts), op,
                                jmax=8, interpret=True)
        want = _np_reduce(slab, starts, op)
        assert np.array_equal(np.asarray(kw), want)
        assert int(np.asarray(kc)[0]) == 0 and int(np.asarray(kc)[2]) == 0


@pytest.mark.parametrize("t", [1, 2, 4, 7])
def test_segment_threshold_vs_oracle(rng, t):
    n, s = 21, 4
    slab = rng.integers(0, 1 << 32, (n, WORDS), dtype=np.uint32)
    # adversarial extra: rows with identical words to stack exact counts
    slab[3] = slab[4] = slab[5]
    starts = np.array([0, 7, 7, 14, 21], np.int32)
    jmax = 8
    want = _np_reduce(slab, starts, "threshold", t)
    kw, kc = segment_reduce(jnp.asarray(slab), jnp.asarray(starts),
                            "threshold", jmax=jmax, threshold=t,
                            interpret=True)
    ow, oc = ref.segment_reduce(jnp.asarray(slab), jnp.asarray(starts),
                                "threshold", jmax=jmax, threshold=t)
    assert np.array_equal(np.asarray(kw), want)
    assert np.array_equal(np.asarray(ow), want)
    want_c = np.bitwise_count(want).sum(axis=1)
    assert np.array_equal(np.asarray(kc), want_c)
    assert np.array_equal(np.asarray(oc), want_c)


def test_threshold_equals_or_and():
    """T=1 over K rows == OR; T=K == AND (symmetric-function endpoints)."""
    rng = np.random.default_rng(5)
    slab = rng.integers(0, 1 << 32, (6, WORDS), dtype=np.uint32)
    starts = np.array([0, 6], np.int32)
    a = jnp.asarray(slab)
    st = jnp.asarray(starts)
    w_or, _ = segment_reduce(a, st, "or", jmax=6, interpret=True)
    w_and, _ = segment_reduce(a, st, "and", jmax=6, interpret=True)
    w_t1, _ = segment_reduce(a, st, "threshold", jmax=6, threshold=1,
                             interpret=True)
    w_t6, _ = segment_reduce(a, st, "threshold", jmax=6, threshold=6,
                             interpret=True)
    assert np.array_equal(np.asarray(w_t1), np.asarray(w_or))
    assert np.array_equal(np.asarray(w_t6), np.asarray(w_and))


def test_counter_planes():
    assert counter_planes(1) == 1
    assert counter_planes(2) == 2
    assert counter_planes(3) == 2
    assert counter_planes(4) == 3
    assert counter_planes(64) == 7


@pytest.mark.parametrize("n,s", [(7, 3), (9, 1), (16, 5)])
def test_segment_andnot_vs_oracle(rng, n, s):
    """Fused difference chain: row0 & ~(OR of the rest), including
    single-row segments (nothing subtracted) and empty segments."""
    slab = rng.integers(0, 1 << 32, (n, WORDS), dtype=np.uint32)
    starts = _segments(rng, n, s)
    jmax = max(1, int(np.diff(starts).max()))
    want = _np_reduce(slab, starts, "andnot")
    kw, kc = segment_reduce(jnp.asarray(slab), jnp.asarray(starts),
                            "andnot", jmax=jmax, interpret=True)
    ow, oc = ref.segment_reduce(jnp.asarray(slab), jnp.asarray(starts),
                                "andnot", jmax=jmax)
    want_c = np.bitwise_count(want).sum(axis=1)
    assert np.array_equal(np.asarray(kw), want)
    assert np.array_equal(np.asarray(kc), want_c)
    assert np.array_equal(np.asarray(ow), want)
    assert np.array_equal(np.asarray(oc), want_c)


def test_segment_andnot_self_and_empty(rng):
    """a - a == 0; a - nothing == a; empty segment -> zero."""
    slab = rng.integers(0, 1 << 32, (4, WORDS), dtype=np.uint32)
    slab[1] = slab[0]
    starts = np.array([0, 2, 3, 3, 4], np.int32)
    kw, kc = segment_reduce(jnp.asarray(slab), jnp.asarray(starts),
                            "andnot", jmax=4, interpret=True)
    kw, kc = np.asarray(kw), np.asarray(kc)
    assert not kw[0].any() and kc[0] == 0          # a & ~a
    assert np.array_equal(kw[1], slab[2])          # lone minuend
    assert not kw[2].any() and kc[2] == 0          # empty segment


@pytest.mark.parametrize("t", [2, 5, 11])
def test_segment_threshold_weighted_vs_oracle(rng, t):
    """Weighted counters via shift-and-add: per-row integer weights, with
    exact-count collisions from duplicated rows."""
    n, s = 14, 3
    slab = rng.integers(0, 1 << 32, (n, WORDS), dtype=np.uint32)
    slab[4] = slab[3]                              # stack exact counts
    starts = np.array([0, 6, 6, 14], np.int32)
    w = rng.integers(1, 8, n).astype(np.int32)
    jmax = 8
    totals = [int(w[starts[i]:starts[i + 1]].sum())
              for i in range(starts.size - 1)]
    planes = max(counter_planes(max(totals)), int(t).bit_length())
    wbits = int(w.max()).bit_length()
    want = _np_reduce(slab, starts, "threshold", t, w)
    want_c = np.bitwise_count(want).sum(axis=1)
    kw, kc = segment_reduce(jnp.asarray(slab), jnp.asarray(starts),
                            "threshold", jmax=jmax, threshold=t,
                            weights=jnp.asarray(w), planes=planes,
                            wbits=wbits, interpret=True)
    ow, oc = ref.segment_reduce(jnp.asarray(slab), jnp.asarray(starts),
                                "threshold", jmax=jmax, threshold=t,
                                weights=jnp.asarray(w))
    assert np.array_equal(np.asarray(kw), want)
    assert np.array_equal(np.asarray(kc), want_c)
    assert np.array_equal(np.asarray(ow), want)
    assert np.array_equal(np.asarray(oc), want_c)


def test_weight_one_degenerates_to_unweighted(rng):
    """All-ones weights must produce bit-identical output to the
    unweighted counter circuit."""
    n = 12
    slab = rng.integers(0, 1 << 32, (n, WORDS), dtype=np.uint32)
    starts = np.array([0, 5, 12], np.int32)
    for t in (1, 3, 5):
        kw0, kc0 = segment_reduce(jnp.asarray(slab), jnp.asarray(starts),
                                  "threshold", jmax=8, threshold=t,
                                  interpret=True)
        kw1, kc1 = segment_reduce(jnp.asarray(slab), jnp.asarray(starts),
                                  "threshold", jmax=8, threshold=t,
                                  weights=jnp.ones(n, jnp.int32),
                                  interpret=True)
        assert np.array_equal(np.asarray(kw0), np.asarray(kw1))
        assert np.array_equal(np.asarray(kc0), np.asarray(kc1))


def test_segment_counters_exchange_roundtrip(rng):
    """The sharded-threshold contract: counters from disjoint row splits,
    bit-slice-added, then compared, must equal the one-shot threshold."""
    n = 10
    slab = rng.integers(0, 1 << 32, (n, WORDS), dtype=np.uint32)
    starts = np.array([0, 4, 10], np.int32)
    w = rng.integers(1, 5, n).astype(np.int32)
    planes = counter_planes(int(max(w[0:4].sum(), w[4:10].sum()) * 2))
    # split each segment's rows into even/odd halves (zero-padded rows
    # keep the segment structure identical on both "shards")
    halves = []
    for par in (0, 1):
        h = slab.copy()
        hw = w.copy()
        for i in range(starts.size - 1):
            rows = np.arange(starts[i], starts[i + 1])
            drop = rows[(rows - starts[i]) % 2 != par]
            h[drop] = 0
            hw[drop] = 1                          # weight of a zero row
        halves.append(ref.segment_counters(
            jnp.asarray(h), jnp.asarray(starts), jmax=8, planes=planes,
            weights=jnp.asarray(hw)))
    tot = ref.bitsliced_add(halves[0], halves[1])
    for t in (1, 4, 9):
        got = np.asarray(ref.counters_ge(tot, jnp.int32(t)))
        want = _np_reduce(slab, starts, "threshold", t, w)
        assert np.array_equal(got, want), t


@pytest.mark.parametrize("seed", [0, 1])
def test_segment_reduce_cnf_vs_oracle(seed):
    """AND of ORs over rows gathered from a table and a staged block:
    the Pallas kernel (interpret mode) against the jnp oracle and numpy,
    padding rows, clauses and segments included."""
    from repro.kernels.segment_ops import segment_reduce_cnf
    rng = np.random.default_rng(seed)
    table = rng.integers(0, 1 << 32, (12, WORDS), dtype=np.uint32)
    table[0] = 0                                   # the reserved zero row
    staged = rng.integers(0, 1 << 32, (4, WORDS), dtype=np.uint32)
    staged[0] = 0
    clauses = [[int(rng.integers(1, 5)) for _ in range(int(
        rng.integers(1, 4)))] for _ in range(5)]
    lens = [n for per in clauses for n in per]
    n = sum(lens)
    from_table = rng.random(n) < 0.7
    pos = np.where(from_table, rng.integers(1, 12, n), 0).astype(np.int32)
    sidx = np.where(from_table, 0, rng.integers(1, 4, n)).astype(np.int32)
    rows = table[pos] | staged[sidx]
    pos = np.concatenate([pos, np.zeros(3, np.int32)])      # padding rows
    sidx = np.concatenate([sidx, np.zeros(3, np.int32)])
    # two padding clauses and three padding segments, all empty
    cstarts = np.concatenate(([0], np.cumsum(lens), [n, n])).astype(
        np.int32)
    c = len(lens)
    starts = np.concatenate(([0], np.cumsum([len(p) for p in clauses]),
                             [c, c, c])).astype(np.int32)
    want = np.zeros((starts.size - 1, WORDS), np.uint32)
    for s_, per in enumerate(clauses):
        first = int(starts[s_])
        acc = None
        for j in range(len(per)):
            lo, hi = cstarts[first + j], cstarts[first + j + 1]
            clause = np.bitwise_or.reduce(rows[lo:hi], axis=0)
            acc = clause if acc is None else acc & clause
        want[s_] = acc
    args = [jnp.asarray(x) for x in (table, staged, pos, sidx, cstarts,
                                     starts)]
    got_w, got_c = segment_reduce_cnf(*args)
    ref_w, ref_c = ref.segment_reduce_cnf(*args, cmax=8, jmax=4)
    np.testing.assert_array_equal(np.asarray(got_w), want)
    np.testing.assert_array_equal(np.asarray(ref_w), want)
    cards = np.bitwise_count(want).sum(axis=1)
    np.testing.assert_array_equal(np.asarray(got_c), cards)
    np.testing.assert_array_equal(np.asarray(ref_c), cards)
