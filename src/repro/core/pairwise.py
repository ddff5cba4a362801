"""Batched pairwise set-algebra planner: type-grouped container pairs,
one dispatch per class.

The paper's central performance contribution is *vectorized two-by-two*
set algebra over container pairs; this module is the host-side planner
that batches it.  Given one ``a ⊕ b`` (or M pairs at once -- the
similarity-join workload of "Compressed bitmap indexes: beyond unions and
intersections", Kaser & Lemire), it key-merges every pair, buckets the
matched container pairs by type class, and executes ONE batched kernel
dispatch per class instead of one per pair:

  * **bitset x bitset** (paper section 4.1.2): stacked ``(M, WORDS)`` word
    rows through ``kernels.pair_ops.bitset_pair_op`` -- a logical op id
    per row fused with the Harley-Seal cardinality (count-only twin for
    the fast-count path, section 5.9);
  * **array x array** (sections 4.2 union/4.3 intersection/4.4
    difference/4.5 symmetric difference): padded value slabs through the
    ``kernels.array_ops`` all-vs-all compare -- two-sided masks for
    materializing ops, count-only for similarity;
  * **array x bitset** (the asymmetric case of section 4.2): a vectorized
    probe of each array value against the bitset row
    (``kernels.pair_ops.array_bitset_probe``); OR/XOR promote the array
    side to the bitset domain and ride the bitset class;
  * **run containers** stay on the host fast paths (section 2.3: run ops
    are interval sweeps, already cheap at interval granularity).

Count-only planning exploits inclusion-exclusion (section 5.9): every op
count derives from the pair's intersection cardinality, so the batched
engine only ever runs AND and combines counts per pair on the host.

On CPU (no forced backend) each count class runs a vectorized numpy twin
with the same O(classes) bulk-dispatch shape and no device round-trip --
and the twins exploit the all-pairs structure directly: the array x array
class is an inverted token join (each unique container's values enter one
key-prefixed token stream; co-occurring tokens emit container-pair
counts), and the array x bitset class probes each unique array against
ALL of its key's bitsets at once.  Work scales with total postings, never
postings x pairs.  With ``backend="pallas"``/``"ref"`` or on TPU the
classes dispatch to the kernels.  Either way the O(N^2)-pair similarity
join issues a handful of batched class dispatches instead of one per
matched container pair.

The materializing single-pair merge batches by class only on a kernel
backend (that is where per-container dispatch overhead lives); on CPU a
lone pair stays on the scalar host merge, whose per-container numpy ops
are already vectorized.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from repro.core import containers as C
from repro.core.containers import (
    ArrayContainer, BitsetContainer, Container, RunContainer,
    container_from_values, positions_to_bitset,
)
from repro.kernels import ops as kops
from repro.kernels import ref as _refk
from repro.kernels.ref import ARRAY_CAP, METRICS, PAIR_OPS, WORDS

__all__ = ["pairwise_card", "jaccard_matrix", "merge_one", "OP_IDS",
           "METRICS", "SimilarityEngine"]

OP_IDS = {o: i for i, o in enumerate(PAIR_OPS)}   # the kernels' row op ids

# below this many total keys a single pair stays on the scalar host merge:
# the class bookkeeping costs more than a handful of container ops
SMALL_PAIR = 16

_HOST_BLOCK = 8192      # bitset rows per host block (8 kB each -> <= 64 MB)

_KCODE = {ArrayContainer: 1, BitsetContainer: 2, RunContainer: 3}


def _bitmap_cls():
    from repro.core.bitmap import RoaringBitmap   # deferred: bitmap imports us
    return RoaringBitmap


def _prefer_kernel(backend: str | None) -> bool:
    """Kernel classes on TPU (or when a backend is forced, e.g. in tests);
    vectorized numpy twins on CPU (same batching, no device round-trip).
    The policy is shared with the wide-aggregation planner."""
    return kops.prefer_kernel(backend)


def _words32(w64: np.ndarray) -> np.ndarray:
    return w64.view(np.uint32)


def _result_words(w32_row: np.ndarray, card: int) -> Container:
    # .copy(): a view would pin the whole (M, WORDS) batch output alive
    # for the lifetime of one surviving container
    w64 = np.ascontiguousarray(w32_row).view(np.uint64).copy()
    return C._result_from_bitset(w64, card)


# ---------------------------------------------------------------------------
# scalar host twins (the pre-planner two-by-two path, kept for small pairs)
# ---------------------------------------------------------------------------

def _merge_host(a, b, op: str):
    """Scalar key-merge (the paper's top-level layout): one container op
    per matched key.  Small pairs stay here; large pairs batch by class."""
    fn = C.OPS[op][0]
    keys, conts = [], []
    i = j = 0
    a_keys, b_keys = a.keys, b.keys
    na, nb = len(a_keys), len(b_keys)
    while i < na and j < nb:
        ka, kb = a_keys[i], b_keys[j]
        if ka == kb:
            c = fn(a.containers[i], b.containers[j])
            if c.card:
                keys.append(ka)
                conts.append(c)
            i += 1
            j += 1
        elif ka < kb:
            if op in ("or", "xor", "andnot"):
                keys.append(ka)
                conts.append(a.containers[i])
            i += 1
        else:
            if op in ("or", "xor"):
                keys.append(kb)
                conts.append(b.containers[j])
            j += 1
    if op in ("or", "xor", "andnot"):
        while i < na:
            keys.append(a_keys[i])
            conts.append(a.containers[i])
            i += 1
    if op in ("or", "xor"):
        while j < nb:
            keys.append(b_keys[j])
            conts.append(b.containers[j])
            j += 1
    return _bitmap_cls()(keys, conts)


def _and_card_host(a, b) -> int:
    """Scalar fast-count twin (paper section 5.9) for small pairs."""
    cnt = 0
    i = j = 0
    while i < len(a.keys) and j < len(b.keys):
        ka, kb = a.keys[i], b.keys[j]
        if ka == kb:
            cnt += C.container_and_card(a.containers[i], b.containers[j])
            i += 1
            j += 1
        elif ka < kb:
            i += 1
        else:
            j += 1
    return cnt


# ---------------------------------------------------------------------------
# materializing two-by-two merge (one pair, class-batched)
# ---------------------------------------------------------------------------

def merge_one(a, b, op: str, *, backend: str | None = None):
    """``a ⊕ b`` through the type-grouped pair planner: matched container
    pairs bucket by class and each class executes as one batched dispatch;
    unmatched keys pass through zero-copy exactly like the scalar merge.

    On CPU (no kernel backend) a lone pair stays on the scalar host merge
    outright: with numpy already vectorizing each container op there is no
    dispatch overhead for class batching to amortize, and the stacking
    copies would only slow the bitset classes down.  Class batching pays
    on a kernel backend (one dispatch per class instead of one per matched
    container pair) and in the many-pair count APIs (``pairwise_card``)."""
    if op not in OP_IDS:
        raise ValueError(op)
    na, nb = len(a.keys), len(b.keys)
    if na + nb <= SMALL_PAIR or not _prefer_kernel(backend):
        return _merge_host(a, b, op)
    fn = C.OPS[op][0]
    ka = np.asarray(a.keys, np.int64)
    kb = np.asarray(b.keys, np.int64)
    common, ia, ib = np.intersect1d(ka, kb, assume_unique=True,
                                    return_indices=True)
    out: dict[int, Container] = {}
    if op in ("or", "xor", "andnot"):
        for i in np.setdiff1d(np.arange(na), ia,
                              assume_unique=True).tolist():
            out[a.keys[i]] = a.containers[i]
    if op in ("or", "xor"):
        for j in np.setdiff1d(np.arange(nb), ib,
                              assume_unique=True).tolist():
            out[b.keys[j]] = b.containers[j]

    aa: list[tuple[int, np.ndarray, np.ndarray]] = []
    probe: list[tuple[int, np.ndarray, np.ndarray, bool]] = []
    bb: list[tuple[int, np.ndarray, np.ndarray]] = []
    for k, i, j in zip(common.tolist(), ia.tolist(), ib.tolist()):
        ca, cb = a.containers[i], b.containers[j]
        xa = isinstance(ca, ArrayContainer)
        xb = isinstance(cb, ArrayContainer)
        if xa and xb:
            aa.append((int(k), ca.values, cb.values))
            continue
        if isinstance(ca, RunContainer) or isinstance(cb, RunContainer):
            c = fn(ca, cb)               # run fast paths stay on host
            if c.card:
                out[int(k)] = c
        elif xa or xb:
            if op == "and":
                arr, bs = (ca, cb) if xa else (cb, ca)   # AND commutes
                probe.append((int(k), arr.values, bs.words, False))
            elif op == "andnot" and xa:
                probe.append((int(k), ca.values, cb.words, True))
            else:
                # or / xor / bitset-minuend andnot: promote the array side
                # to the bitset domain and ride the bitset class
                wa = positions_to_bitset(ca.values) if xa else ca.words
                wb = positions_to_bitset(cb.values) if xb else cb.words
                bb.append((int(k), wa, wb))
        else:
            bb.append((int(k), ca.words, cb.words))
    _merge_aa(out, aa, op, backend)
    _merge_probe(out, probe, backend)
    _merge_bb(out, bb, op, backend)
    keys = sorted(out)
    return _bitmap_cls()(keys, [out[k] for k in keys])


def _assemble_aa(x: np.ndarray, y: np.ndarray, ha: np.ndarray,
                 hb: np.ndarray, op: str) -> np.ndarray:
    """Result values of one array-array pair from the two-sided masks."""
    if op == "and":
        return x[ha]
    if op == "andnot":
        return x[~ha]
    if op == "or":
        return np.sort(np.concatenate((x, y[~hb])))
    return np.sort(np.concatenate((x[~ha], y[~hb])))          # xor


def _merge_aa(out: dict, entries: list, op: str, backend) -> None:
    """array x array class: ONE two-sided-mask dispatch feeds all ops."""
    if not entries:
        return
    m = len(entries)
    av = np.zeros((m, ARRAY_CAP), np.int32)
    bv = np.zeros((m, ARRAY_CAP), np.int32)
    ac = np.zeros(m, np.int32)
    bc = np.zeros(m, np.int32)
    for r, (_, x, y) in enumerate(entries):
        av[r, :x.size] = x
        bv[r, :y.size] = y
        ac[r], bc[r] = x.size, y.size
    ma, mb, _ = kops.array_pair_masks(
        jnp.asarray(av), jnp.asarray(ac), jnp.asarray(bv),
        jnp.asarray(bc), backend=backend)
    ma = np.asarray(ma).astype(bool)
    mb = np.asarray(mb).astype(bool)
    for r, (k, x, y) in enumerate(entries):
        vals = _assemble_aa(x, y, ma[r, :x.size], mb[r, :y.size], op)
        if vals.size:
            out[k] = container_from_values(vals)


def _mask_in(x: np.ndarray, y: np.ndarray) -> np.ndarray:
    """Membership of sorted ``x`` in sorted ``y`` (vectorized probe)."""
    if y.size == 0:
        return np.zeros(x.size, bool)
    idx = np.searchsorted(y, x)
    idx[idx == y.size] = y.size - 1
    return y[idx] == x


def _merge_probe(out: dict, entries: list, backend) -> None:
    """array x bitset class (AND / array-minuend ANDNOT): one probe
    dispatch; ``invert`` keeps the misses instead of the hits."""
    if not entries:
        return
    m = len(entries)
    vals = np.zeros((m, ARRAY_CAP), np.int32)
    cards = np.zeros(m, np.int32)
    words = np.zeros((m, WORDS), np.uint32)
    for r, (_, v, w, _) in enumerate(entries):
        vals[r, :v.size] = v
        cards[r] = v.size
        words[r] = _words32(w)
    mask, _ = kops.array_bitset_probe(
        jnp.asarray(vals), jnp.asarray(cards), jnp.asarray(words),
        backend=backend)
    mask = np.asarray(mask).astype(bool)
    for r, (k, v, _, inv) in enumerate(entries):
        hit = mask[r, :v.size]
        kept = v[~hit] if inv else v[hit]
        if kept.size:
            out[k] = ArrayContainer(kept)


def _merge_bb(out: dict, entries: list, op: str, backend) -> None:
    """bitset x bitset class: one stacked-words dispatch, op id per row."""
    if not entries:
        return
    a32 = np.stack([_words32(wa) for _, wa, _ in entries])
    b32 = np.stack([_words32(wb) for _, _, wb in entries])
    opids = np.full(len(entries), OP_IDS[op], np.int32)
    w, cards = kops.bitset_pair_op(jnp.asarray(a32), jnp.asarray(b32),
                                   opids, backend=backend)
    w = np.asarray(w)
    cards = np.asarray(cards)
    for r, (k, _, _) in enumerate(entries):
        if cards[r]:
            out[k] = _result_words(w[r], int(cards[r]))


# ---------------------------------------------------------------------------
# count-only batch (M pairs, one dispatch per class)
# ---------------------------------------------------------------------------

def pairwise_card(ops, pairs, *, backend: str | None = None) -> np.ndarray:
    """Batched count-only pairwise set algebra over M bitmap pairs.

    ``ops`` is one op name ("and" | "or" | "xor" | "andnot") or a length-M
    sequence of per-pair names; ``pairs`` is a sequence of
    ``(RoaringBitmap, RoaringBitmap)``.  Returns (M,) int64 counts.

    Every count derives from the pair's intersection cardinality by
    inclusion-exclusion (paper section 5.9), so the batched engine only
    ever runs AND over the matched container pairs -- O(container-type
    classes) dispatches regardless of M."""
    pairs = list(pairs)
    m = len(pairs)
    if isinstance(ops, str):
        op_list = [ops] * m
    else:
        op_list = [str(o) for o in ops]
        if len(op_list) != m:
            raise ValueError(
                f"need one op per pair: {len(op_list)} != {m}")
    for o in op_list:
        if o not in OP_IDS:
            raise ValueError(o)
    if m == 0:
        return np.zeros(0, np.int64)
    uniq, ia, ib = _dedupe(pairs)
    if m == 1 and len(pairs[0][0].keys) + len(pairs[0][1].keys) \
            <= SMALL_PAIR:
        inter = np.array([_and_card_host(*pairs[0])], np.int64)
    else:
        inter = _inter_counts(uniq, ia, ib, backend)
    cards = np.array([bm.cardinality for bm in uniq], np.int64)
    ca, cb = cards[ia], cards[ib]
    opv = np.array([OP_IDS[o] for o in op_list], np.int64)
    return np.where(opv == 0, inter,
                    np.where(opv == 1, ca + cb - inter,
                             np.where(opv == 2, ca + cb - 2 * inter,
                                      ca - inter)))


def _dedupe(pairs):
    """Unique bitmap objects + per-pair indices into the unique list."""
    seen: dict[int, int] = {}
    uniq = []
    for a, b in pairs:
        for bmp in (a, b):
            if id(bmp) not in seen:
                seen[id(bmp)] = len(uniq)
                uniq.append(bmp)
    ia = np.array([seen[id(a)] for a, _ in pairs], np.int64)
    ib = np.array([seen[id(b)] for _, b in pairs], np.int64)
    return uniq, ia, ib


def _tables(bitmaps):
    """Per-(bitmap, chunk-key) kind codes and container indices."""
    all_keys = sorted({k for bm in bitmaps for k in bm.keys})
    kidx = {k: i for i, k in enumerate(all_keys)}
    n, nk = len(bitmaps), len(all_keys)
    kind = np.zeros((n, nk), np.int8)
    cidx = np.zeros((n, nk), np.int32)
    for i, bm in enumerate(bitmaps):
        for j, (k, c) in enumerate(zip(bm.keys, bm.containers)):
            col = kidx[k]
            kind[i, col] = _KCODE[type(c)]
            cidx[i, col] = j
    return kind, cidx


def _inter_counts(uniq, ia, ib, backend) -> np.ndarray:
    """(M,) intersection cardinalities: vectorized key matching over a
    presence table, then one batched AND-count dispatch per class.

    The host twins exploit the all-pairs structure: a container shared by
    many pairs enters the computation ONCE (an inverted token join for
    array x array, a per-key grouped probe for array x bitset), so the
    work scales with total postings, not postings-times-pairs."""
    m = ia.size
    kind, cidx = _tables(uniq)
    if kind.shape[1] == 0:
        return np.zeros(m, np.int64)
    kind_a, kind_b = kind[ia], kind[ib]
    pe, ke = np.nonzero((kind_a > 0) & (kind_b > 0))
    if pe.size == 0:
        return np.zeros(m, np.int64)
    ja, jb = ia[pe], ib[pe]
    ka, kb = kind[ja, ke], kind[jb, ke]
    conts_a = [uniq[i].containers[cidx[i, k]]
               for i, k in zip(ja.tolist(), ke.tolist())]
    conts_b = [uniq[i].containers[cidx[i, k]]
               for i, k in zip(jb.tolist(), ke.tolist())]
    counts = np.zeros(pe.size, np.int64)

    is_run = (ka == 3) | (kb == 3)
    is_aa = (ka == 1) & (kb == 1)
    is_bb = (ka == 2) & (kb == 2)
    is_ab = ~(is_run | is_aa | is_bb)

    for e in np.flatnonzero(is_run).tolist():      # run fast paths: host
        counts[e] = C.container_and_card(conts_a[e], conts_b[e])

    idx = np.flatnonzero(is_aa)
    if idx.size:
        counts[idx] = _aa_counts(ke[idx],
                                 [conts_a[e] for e in idx.tolist()],
                                 [conts_b[e] for e in idx.tolist()],
                                 backend)
    idx = np.flatnonzero(is_ab)
    if idx.size:
        arrs, sets = [], []
        for e in idx.tolist():
            x, y = conts_a[e], conts_b[e]
            if not isinstance(x, ArrayContainer):
                x, y = y, x
            arrs.append(x)
            sets.append(y)
        counts[idx] = _ab_counts(ke[idx], arrs, sets, backend)
    idx = np.flatnonzero(is_bb)
    if idx.size:
        counts[idx] = _bb_counts([conts_a[e] for e in idx.tolist()],
                                 [conts_b[e] for e in idx.tolist()],
                                 backend)
    inter = np.zeros(m, np.int64)
    np.add.at(inter, pe, counts)
    return inter


def _aa_counts(keys_e, xs, ys, backend) -> np.ndarray:
    """array x array intersection counts.

    Kernel path: padded value slabs, one count-only all-vs-all dispatch.
    Host path: an inverted token join -- every unique container's values
    enter ONE key-prefixed token stream; tokens shared by g containers
    emit g*(g-1)/2 co-occurrence pairs (one vectorized pass per rank
    offset), accumulating a container-pair count matrix that all entries
    read off.  Work scales with total postings, never postings x pairs."""
    n = len(xs)
    if _prefer_kernel(backend):
        av = np.zeros((n, ARRAY_CAP), np.int32)
        bv = np.zeros((n, ARRAY_CAP), np.int32)
        ac = np.zeros(n, np.int32)
        bc = np.zeros(n, np.int32)
        for r, (x, y) in enumerate(zip(xs, ys)):
            av[r, :x.values.size] = x.values
            bv[r, :y.values.size] = y.values
            ac[r], bc[r] = x.values.size, y.values.size
        return np.asarray(kops.array_intersect_card(
            jnp.asarray(av), jnp.asarray(ac), jnp.asarray(bv),
            jnp.asarray(bc), backend=backend)).astype(np.int64)
    # unique containers; token = key << 16 | value, so containers of
    # different chunk keys never collide
    uid: dict[int, int] = {}
    pool: list[np.ndarray] = []
    ua = np.empty(n, np.int64)
    ub = np.empty(n, np.int64)
    for r, (k, x, y) in enumerate(zip(keys_e.tolist(), xs, ys)):
        for side, c in ((ua, x), (ub, y)):
            u = uid.get(id(c))
            if u is None:
                u = uid[id(c)] = len(pool)
                pool.append(c.values.astype(np.int64)
                            + (np.int64(k) << 16))
            side[r] = u
    nu = len(pool)
    if nu > 4096:
        # the co-occurrence matrix would be nu^2: fall back to the
        # replicated per-entry membership probe (still one bulk op)
        return _aa_counts_probe(keys_e, xs, ys)
    lens = np.array([v.size for v in pool], np.int64)
    tokens = np.concatenate(pool)
    owner = np.repeat(np.arange(nu, dtype=np.int64), lens)
    comb = tokens * nu + owner                # value-major, owner-minor
    comb.sort()
    val_of = comb // nu
    own_of = comb % nu
    g = np.zeros((nu, nu), np.int32)
    d = 1
    while d < comb.size:
        same = val_of[d:] == val_of[:-d]
        if not same.any():
            break
        np.add.at(g, (own_of[:-d][same], own_of[d:][same]), 1)
        d += 1
    res = (g[ua, ub] + g[ub, ua]).astype(np.int64)
    self_pair = ua == ub             # a container against itself: |values|
    if self_pair.any():
        res[self_pair] = lens[ua[self_pair]]
    return res


def _aa_counts_probe(keys_e, xs, ys) -> np.ndarray:
    """Replicated-entry fallback: offset-concatenate both sides (entry id
    in the high bits keeps entries apart in one sort order) and count
    matches of A's stream in B's with a single vectorized probe."""
    n = len(xs)
    lens_a = np.array([x.values.size for x in xs], np.int64)
    lens_b = np.array([y.values.size for y in ys], np.int64)
    eids = np.arange(n, dtype=np.int64) << 16
    a_all = np.concatenate([x.values for x in xs]).astype(np.int64) \
        + np.repeat(eids, lens_a)
    b_all = np.concatenate([y.values for y in ys]).astype(np.int64) \
        + np.repeat(eids, lens_b)
    hit = _mask_in(a_all, b_all)
    eid_a = np.repeat(np.arange(n), lens_a)
    return np.bincount(eid_a[hit], minlength=n).astype(np.int64)


def _ab_counts(keys_e, arrs, sets, backend) -> np.ndarray:
    """array x bitset probe counts.

    Kernel path: one batched probe dispatch.  Host path: per chunk key,
    every unique array's values probe ALL of that key's unique bitsets at
    once (word gather + bit test, segment-summed per array), so each
    value is touched once per bitset instead of once per pair."""
    n = len(arrs)
    if _prefer_kernel(backend):
        vals = np.zeros((n, ARRAY_CAP), np.int32)
        cards = np.zeros(n, np.int32)
        words = np.zeros((n, WORDS), np.uint32)
        for r, (x, y) in enumerate(zip(arrs, sets)):
            vals[r, :x.values.size] = x.values
            cards[r] = x.values.size
            words[r] = _words32(y.words)
        _, cnt = kops.array_bitset_probe(
            jnp.asarray(vals), jnp.asarray(cards), jnp.asarray(words),
            backend=backend)
        return np.asarray(cnt).astype(np.int64)
    out = np.zeros(n, np.int64)
    order = np.argsort(keys_e, kind="stable")
    bounds = np.flatnonzero(np.concatenate(
        ([True], np.diff(keys_e[order]) != 0, [True])))
    for s, e in zip(bounds[:-1].tolist(), bounds[1:].tolist()):
        ent = order[s:e]                      # entries of one chunk key
        aid: dict[int, int] = {}
        bid: dict[int, int] = {}
        a_list: list[np.ndarray] = []
        b_list: list[np.ndarray] = []
        ea = np.empty(ent.size, np.int64)
        eb = np.empty(ent.size, np.int64)
        for r, i in enumerate(ent.tolist()):
            u = aid.get(id(arrs[i]))
            if u is None:
                u = aid[id(arrs[i])] = len(a_list)
                a_list.append(arrs[i].values)
            ea[r] = u
            u = bid.get(id(sets[i]))
            if u is None:
                u = bid[id(sets[i])] = len(b_list)
                b_list.append(sets[i].words)
            eb[r] = u
        lens = np.array([v.size for v in a_list], np.int64)
        vals = np.concatenate(a_list).astype(np.int64)
        stack = np.stack(b_list)              # (nb, 1024) uint64
        bits = ((stack[:, vals >> 6]
                 >> (vals & 63).astype(np.uint64)) & np.uint64(1))
        starts = np.concatenate(([0], np.cumsum(lens)[:-1]))
        seg = np.add.reduceat(bits, starts, axis=1)   # (nb, na)
        out[ent] = seg[eb, ea]
    return out


def _bb_counts(xs, ys, backend) -> np.ndarray:
    """bitset x bitset AND-popcount counts, one dispatch."""
    n = len(xs)
    if _prefer_kernel(backend):
        a32 = np.stack([_words32(x.words) for x in xs])
        b32 = np.stack([_words32(y.words) for y in ys])
        return np.asarray(kops.bitset_pair_card(
            jnp.asarray(a32), jnp.asarray(b32),
            np.zeros(n, np.int32), backend=backend)).astype(np.int64)
    out = np.zeros(n, np.int64)
    for lo in range(0, n, _HOST_BLOCK):
        hi = min(lo + _HOST_BLOCK, n)
        a64 = np.stack([x.words for x in xs[lo:hi]])
        b64 = np.stack([y.words for y in ys[lo:hi]])
        out[lo:hi] = np.bitwise_count(a64 & b64).sum(axis=1)
    return out


# ---------------------------------------------------------------------------
# top-k similarity engine (device-resident candidate slab + pruning planner)
# ---------------------------------------------------------------------------

def _scores_host(inter, q_card, cards, metric: str) -> np.ndarray:
    """Numpy twin of ``kernels.ref.similarity_scores``: float32 with the
    SAME operation order, so host selection is bit-identical (including
    tie ordering) to the fused device kernel."""
    interf = np.asarray(inter).astype(np.float32)
    qc = np.float32(q_card)
    oc = np.asarray(cards).astype(np.float32)
    if metric == "jaccard":
        denom = qc + oc - interf
    elif metric == "cosine":
        denom = np.sqrt(qc * oc)
    elif metric == "containment":
        denom = np.broadcast_to(qc, oc.shape)
    else:
        raise ValueError(metric)
    return np.divide(interf, denom, out=np.ones_like(interf),
                     where=denom > 0)


class SimilarityEngine:
    """Top-k similarity joins against a fixed candidate set, one engine
    dispatch per query (paper section 5.9 taken to its conclusion: not
    even the scores round-trip through the host).

    Construction promotes every candidate container to the bitset domain
    ONCE into a candidate-major row slab over the global chunk-key set --
    the layout ``kernels/topk_ops.similarity_topk`` consumes -- and keeps
    a lazily-uploaded device copy, so the per-query work is one fused
    score+select dispatch (kernel backends) or a pruned vectorized
    popcount sweep (CPU).  Memory: 8 kB per candidate container (sparse
    containers inflate to bitset rows; this is a query-serving cache, the
    stored bitmaps keep their compressed kinds).

    The CPU path is the *candidate-pruning planner* (the galloping/skip
    analogue of paper section 4.2 lifted to the planner layer): candidate
    scores are bounded above by evaluating the metric at
    ``inter = min(|Q|, |C|)``, the k best bounds are scored exactly to
    establish the running k-th score, and every candidate whose bound
    cannot reach it is skipped without touching its postings.  The score
    formula is evaluated in float32 with a fixed operation order on every
    path (see ``kernels.ref.similarity_scores``), and both selectors
    break ties toward the lower candidate index, so kernel and host
    results are bit-identical -- the ``backend=`` switch can never change
    an answer.  See docs/ARCHITECTURE.md for the module map.

    With an ``arena`` (core/arena.py) the candidate slab becomes an
    **arena view**: candidates are adopted into the shared arena, the
    engine stores slab row ids instead of owning a private copy, and the
    device slab is a device-side gather from the arena's resident rows
    (the host ``rows`` mirror is gathered from the arena's host mirror --
    same bytes, so host and kernel paths stay bit-identical).  A postings
    edit then costs one :meth:`refresh` -- the arena repatches only the
    changed rows (one scatter) and the engine re-gathers, instead of
    re-promoting and re-uploading the whole candidate set.

    ``dispatches`` counts the device top-k dispatches the engine has
    issued, and ``resident_dispatches`` those of them whose scoring
    kernel held the query block in VMEM (``kops.similarity_resident``);
    ``segment_map_builds`` counts the layouts built (construction
    and each :meth:`refresh` that changed something), each with its
    row-to-candidate map ``seg``, which every dispatch then reads.  A
    query's device work is three trace spans: ``engine.query_block``
    (its query block built on the device), ``engine.dispatch`` (the top-k
    call enqueued) and ``engine.fetch`` (the host waiting for the
    answer).
    """

    def __init__(self, bitmaps, *, arena=None, mesh=None):
        """``bitmaps``: the candidate set, index-aligned with results.
        ``arena``: optional shared ``BitmapArena``; candidates are
        adopted into it and the engine becomes a view over its slab
        (see the class docstring and docs/MEMORY.md).
        ``mesh``: optional 1-D ``("wide",)`` mesh; with more than one
        device the engine runs the sharded path (:meth:`_topk_sharded`)
        over the arena's per-shard slabs -- requires ``arena``.  A
        1-device mesh degrades to the single-device engine."""
        self._bitmaps = list(bitmaps)
        self._arena = arena
        self.dispatches = 0
        self.resident_dispatches = 0
        self.segment_map_builds = 0
        self._mesh = None
        self._nshards = 1
        self._shard_axis = None
        if mesh is not None:
            from repro.dist import ctx
            m, size, axis = ctx.resolve_wide(mesh)
            if size > 1:
                if arena is None:
                    raise ValueError(
                        "sharded SimilarityEngine (mesh=) requires an "
                        "arena-backed engine")
                self._mesh, self._nshards, self._shard_axis = m, size, axis
        self._build()

    def _build(self) -> None:
        bitmaps = self._bitmaps
        arena = self._arena
        self.n = len(bitmaps)
        self.cards = np.array([bm.cardinality for bm in bitmaps],
                              np.int64)
        if self.cards.size and int(self.cards.max()) >= 2**31:
            # the kernel path carries cardinalities as int32; refuse to
            # build rather than silently wrap on one backend
            raise ValueError("candidate cardinality >= 2^31 unsupported")
        if arena is not None:
            arena.adopt_many(bitmaps)
        keys = sorted({k for bm in bitmaps for k in bm.keys})
        self.key_col = {k: i for i, k in enumerate(keys)}
        self.n_keys = len(keys)
        rows, row_col = [], []
        starts = np.zeros(self.n + 1, np.int32)
        for i, bm in enumerate(bitmaps):
            for k, c in zip(bm.keys, bm.containers):
                rows.append(arena.lookup(c) if arena is not None
                            else C.container_words64(c))
                row_col.append(self.key_col[k])
            starts[i + 1] = len(rows)
        if arena is not None:
            # arena view: keep row ids + a host-mirror gather (identical
            # bytes to promoting, without re-running promotion)
            self.row_ids = np.asarray(rows, np.int32)
            self.rows = arena.host_rows(self.row_ids) if rows else \
                np.zeros((0, 1024), np.uint64)
            self._snap = tuple((id(bm), bm._version) for bm in bitmaps)
        else:
            self.row_ids = None
            self.rows = np.stack(rows) if rows else \
                np.zeros((0, 1024), np.uint64)
            self._snap = None
        self.row_col = np.asarray(row_col, np.int32)
        self.starts = starts
        # row r belongs to candidate seg[r]: fixed until the next _build
        self.seg = np.repeat(np.arange(self.n, dtype=np.int32),
                             np.diff(starts))
        self.segment_map_builds += 1
        self._dev = None                         # lazy device upload

    def refresh(self) -> bool:
        """Generation revalidation for an arena-backed engine: re-adopt
        candidates whose ``_version`` moved (the arena repatches only
        their changed rows -- one scatter), rebuild the cheap host index
        arrays, and drop the device view so the next query re-gathers
        from the patched slab ON DEVICE.  Returns True when anything
        changed; a no-op (False) when every candidate is current.

        This is the incremental path the query server's ``slab_mismatch``
        rung uses instead of discarding the engine (docs/ARCHITECTURE.md
        §6); cost is O(changed rows) transfer instead of O(slab)."""
        if self._arena is None:
            raise ValueError("refresh() requires an arena-backed engine")
        snap = tuple((id(bm), bm._version) for bm in self._bitmaps)
        if snap == self._snap:
            return False
        self._build()
        return True

    # -- query preparation ----------------------------------------------

    def _query_words(self, query) -> np.ndarray:
        """(C, 1024) uint64 host query rows over the global keys.
        ``query`` is a candidate index (rows gathered from the cached
        slab) or any RoaringBitmap (keys outside the candidate universe
        carry no candidate rows and are dropped -- they cannot
        intersect)."""
        q64 = np.zeros((max(self.n_keys, 1), 1024), np.uint64)
        if isinstance(query, (int, np.integer)):
            s, e = int(self.starts[query]), int(self.starts[query + 1])
            q64[self.row_col[s:e]] = self.rows[s:e]
            return q64
        for k, cont in zip(query.keys, query.containers):
            col = self.key_col.get(k)
            if col is not None:
                q64[col] = C.container_words64(cont)
        return q64

    def _query_words_dev(self, query):
        """(C, WORDS) uint32 DEVICE query block with minimal transfer:
        a member query gathers its rows from the resident slab (nothing
        crosses the host bridge); a bitmap query ships only its occupied
        rows and scatters them into place on device."""
        dev_rows, dev_col = self._device()[:2]
        nc = max(self.n_keys, 1)
        zeros = jnp.zeros((nc, WORDS), jnp.uint32)
        if isinstance(query, (int, np.integer)):
            s, e = int(self.starts[query]), int(self.starts[query + 1])
            if s == e:
                return zeros
            return zeros.at[dev_col[s:e]].set(dev_rows[s:e])
        cols, rows = [], []
        for k, cont in zip(query.keys, query.containers):
            col = self.key_col.get(k)
            if col is not None:
                cols.append(col)
                rows.append(C.container_words64(cont))
        if not cols:
            return zeros
        stack = np.stack(rows).view(np.uint32).reshape(-1, WORDS)
        return zeros.at[jnp.asarray(np.asarray(cols, np.int32))] \
            .set(jnp.asarray(stack))

    def _query_words_dev_batch(self, queries):
        """(B, C, WORDS) uint32 DEVICE query block for a whole batch in
        TWO scatters (one gathering member queries' rows from the
        resident slab, one shipping bitmap queries' occupied rows) --
        the per-query ``_query_words_dev`` loop costs one jit dispatch
        per query, which dominates coalesced similarity batches."""
        dev_rows, dev_col = self._device()[:2]
        nc = max(self.n_keys, 1)
        block = jnp.zeros((len(queries), nc, WORDS), jnp.uint32)
        mem_b, mem_r = [], []            # member queries: slab row ids
        bm_b, bm_c, bm_rows = [], [], []  # bitmap queries: host words
        for b, q in enumerate(queries):
            if isinstance(q, (int, np.integer)):
                s, e = int(self.starts[q]), int(self.starts[q + 1])
                mem_b.extend([b] * (e - s))
                mem_r.extend(range(s, e))
                continue
            for k, cont in zip(q.keys, q.containers):
                col = self.key_col.get(k)
                if col is not None:
                    bm_b.append(b)
                    bm_c.append(col)
                    bm_rows.append(C.container_words64(cont))
        if mem_r:
            r = jnp.asarray(np.asarray(mem_r, np.int32))
            block = block.at[jnp.asarray(np.asarray(mem_b, np.int32)),
                             dev_col[r]].set(dev_rows[r])
        if bm_b:
            stack = np.stack(bm_rows).view(np.uint32).reshape(-1, WORDS)
            block = block.at[jnp.asarray(np.asarray(bm_b, np.int32)),
                             jnp.asarray(np.asarray(bm_c, np.int32))
                             ].set(jnp.asarray(stack))
        return block

    def _device(self):
        if self._dev is None:
            if self._arena is not None and self.row_ids is not None \
                    and self.row_ids.size:
                # arena view: gather the candidate rows from the resident
                # slab ON DEVICE -- container words never cross PCIe here
                dev_rows = jnp.take(self._arena.device_slab(),
                                    jnp.asarray(self.row_ids), axis=0)
                self._arena.stats.device_gathers += 1
            elif self.rows.size:
                dev_rows = jnp.asarray(
                    self.rows.view(np.uint32).reshape(-1, WORDS))
            else:
                dev_rows = jnp.zeros((1, WORDS), jnp.uint32)
            self._dev = (
                dev_rows,
                jnp.asarray(self.row_col if self.row_col.size else
                            np.zeros(1, np.int32)),
                jnp.asarray(self.starts),
                jnp.asarray(self.cards.astype(np.int32)),
                # the one padding row of an empty slab maps to T: dropped
                jnp.asarray(self.seg if self.seg.size else
                            np.full(1, self.n, np.int32)),
            )
        return self._dev

    # -- the query surface ----------------------------------------------

    def topk(self, query, k: int, metric: str = "jaccard", *,
             backend: str | None = None
             ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Top-k most similar candidates to ``query``.

        query:  candidate index (int; excluded from its own result) or a
                RoaringBitmap.
        k:      results wanted; clamped to the candidate count.
        metric: "jaccard" | "cosine" | "containment" (all derived from
                the AND cardinality by inclusion-exclusion).
        backend: kernel override; None = fused kernel on TPU, pruned
                host sweep on CPU; "host" forces the jax-free host sweep
                (the query server's degradation path).  Results are
                bit-identical on every path.

        Returns (idx (k',) int64, score (k',) float32, inter (k',) int64)
        best-first; ties at equal score order by ascending index.
        Complexity: one dispatch over the resident slab (kernel) or
        O(rows of unpruned candidates) popcounts (host).
        """
        if metric not in METRICS:
            raise ValueError(metric)
        if isinstance(query, (int, np.integer)):
            exclude = int(query)
            if not 0 <= exclude < self.n:
                raise IndexError(f"candidate index {exclude} out of "
                                 f"range [0, {self.n})")
            qc = int(self.cards[exclude])
        else:
            exclude = None
            qc = query.cardinality
        n_cand = self.n - (1 if exclude is not None else 0)
        k = min(int(k), n_cand)
        if k <= 0:
            return (np.zeros(0, np.int64), np.zeros(0, np.float32),
                    np.zeros(0, np.int64))
        if qc >= 2**31:                          # int32 on the kernel path
            raise ValueError("query cardinality >= 2^31 unsupported")
        if self.rows.shape[0] == 0:              # all-empty candidates
            score = _scores_host(np.zeros(self.n, np.int64), qc,
                                 self.cards, metric)
            if exclude is not None:
                score[exclude] = np.float32(-1.0)
            order = np.argsort(-score, kind="stable")[:k]
            return (order.astype(np.int64), score[order],
                    np.zeros(k, np.int64))
        if self._mesh is not None and backend != "host":
            return self._topk_sharded(query, qc, k, metric, exclude,
                                      backend)
        if backend != "host" and _prefer_kernel(backend):
            dev_rows, dev_col, dev_starts, dev_cards, dev_seg = \
                self._device()
            with TraceAnnotation("engine.query_block"):
                q_words = self._query_words_dev(query)
            with TraceAnnotation("engine.dispatch"):
                res = kops.similarity_topk(
                    dev_rows, dev_col, dev_starts, q_words, qc, dev_cards,
                    metric=metric, k=k,
                    exclude=-1 if exclude is None else exclude,
                    seg=dev_seg, backend=backend)
                self.dispatches += 1
                self.resident_dispatches += kops.similarity_resident(
                    q_words.shape[0], backend)
            return self._fetch(res)
        return self._topk_host(self._query_words(query), qc, k, metric,
                               exclude)

    @staticmethod
    def _fetch(res):
        """Bring one dispatch's (idx, score, inter) to the host."""
        with TraceAnnotation("engine.fetch"):
            idx, score, inter = res
            return (np.asarray(idx).astype(np.int64), np.asarray(score),
                    np.asarray(inter).astype(np.int64))

    # -- sharded path (per-shard arena slabs, shard-local row gathers) --

    @staticmethod
    def _query_block(q64: np.ndarray):
        """(C, WORDS) uint32 device query block from host query words:
        only the occupied key columns cross the host bridge and scatter
        into a zero block on device."""
        cols = np.flatnonzero(q64.any(axis=1))
        zeros = jnp.zeros((q64.shape[0], WORDS), jnp.uint32)
        if not cols.size:
            return zeros
        rows = np.ascontiguousarray(q64[cols]).view(np.uint32)
        return zeros.at[jnp.asarray(cols.astype(np.int32))].set(
            jnp.asarray(rows.reshape(-1, WORDS)))

    def _survivors(self, q64, qc, k, metric, exclude) -> np.ndarray:
        """The SAME pruning derivation as :meth:`_topk_host` (bounds -> k
        seed exact scores -> running k-th score tau -> survivors = bound
        >= tau): ascending global ids of the candidates that can still
        reach the top k (at least k of them: the seeds survive)."""
        ub = _scores_host(np.minimum(qc, self.cards), qc, self.cards,
                          metric)
        if exclude is not None:
            ub[exclude] = np.float32(-1.0)
        seeds = np.argsort(-ub, kind="stable")[:k]
        seed_score = _scores_host(self._host_inter(seeds, q64), qc,
                                  self.cards[seeds], metric)
        tau = seed_score.min()
        # exact seed scores are <= their bounds, so seeds survive; the
        # excluded candidate's bound is -1 < 0 <= tau, so it never does
        return np.where(ub >= tau)[0]

    def _plan_sharded(self, cands: np.ndarray, shards):
        """Pack the sharded dispatch for ascending candidates ``cands``:
        every candidate row is scored on the shard whose slab holds it
        (arena row ``r`` on shard ``r % S``, ``ShardSlabs.locate``), so a
        shard gathers only from its own slab and a candidate's rows may
        sit on several shards.

        Returns ``(gidx, cards, starts, lpos, col)``: global candidate
        ids (L,) (pad: ``self.n``, masked by ``n_valid = cands.size``),
        their cardinalities (L,), each shard's row offsets per candidate
        (S, L+1), and each shard's local slab indices (S, R) and key
        columns (S, R) of those rows (pad: 0, past the last offset, so
        never counted).  L and R are padded to powers of two so jit
        retraces stay bounded."""
        S = self._nshards
        n = cands.size
        lpad = 1 << (max(n, 1) - 1).bit_length()
        gidx = np.full(lpad, self.n, np.int32)
        gidx[:n] = cands
        cards = np.zeros(lpad, np.int32)
        cards[:n] = self.cards[cands]
        lens = (self.starts[cands + 1] - self.starts[cands]).astype(np.int64)
        tot = int(lens.sum())
        ridx = np.arange(tot) - np.repeat(np.cumsum(lens) - lens, lens) \
            + np.repeat(self.starts[cands].astype(np.int64), lens)
        slot = np.repeat(np.arange(n), lens)     # candidate slot per row
        shard, local = shards.locate(self.row_ids[ridx])
        counts = np.bincount(shard, minlength=S)
        rpad = 1 << (max(int(counts.max()), 1) - 1).bit_length()
        starts = np.zeros((S, lpad + 1), np.int32)
        lpos = np.zeros((S, rpad), np.int32)
        col = np.zeros((S, rpad), np.int32)
        for s in range(S):
            mine = shard == s                    # candidate-major order kept
            lpos[s, : counts[s]] = local[mine]
            col[s, : counts[s]] = self.row_col[ridx[mine]]
            starts[s, 1:] = np.cumsum(np.bincount(slot[mine],
                                                  minlength=lpad))
        return gidx, cards, starts, lpos, col

    def _topk_sharded(self, query, qc, k, metric, exclude, backend):
        """The sharded query path: the host pruning planner (same bound /
        seed / tau derivation as :meth:`_topk_host`, so the SAME
        candidates survive) selects the survivor set; in ONE dispatch
        every shard counts the query's intersections with the survivor
        rows its own slab holds (ids over the bridge, never candidate
        words), the per-candidate counts are summed across shards, and
        every shard selects the same global top-k.  Ties resolve to the
        lowest GLOBAL candidate index, so results are bit-identical to
        the single-device path."""
        shards = self._arena.shard_slabs(self._mesh)
        q64 = self._query_words(query)           # host mirror, no PCIe
        surv = self._survivors(q64, qc, k, metric, exclude)
        gidx, cards, starts, lpos, col = self._plan_sharded(surv, shards)
        for st in shards.stats:
            st.device_gathers += 1
        fn = _sharded_topk(self._mesh, self._shard_axis, metric, k, backend)
        with TraceAnnotation("engine.query_block"):
            q_words = self._query_block(q64)
        with TraceAnnotation("engine.dispatch"):
            res = fn(
                shards.assembled(), jnp.asarray(lpos), jnp.asarray(col),
                jnp.asarray(starts), q_words,
                jnp.asarray(np.int32(qc)), jnp.asarray(cards),
                jnp.asarray(gidx), jnp.asarray(np.int32(surv.size)),
                jnp.asarray(np.int32(-1 if exclude is None else exclude)))
            self.dispatches += 1
            self.resident_dispatches += kops.similarity_resident(
                q_words.shape[0], backend)
        return self._fetch(res)

    def topk_batch(self, queries, k: int, metric: str = "jaccard", *,
                   backend: str | None = None) -> list:
        """Batched ``topk``: score many queries against the SAME resident
        candidate slab (the query server's similarity coalescing path).

        On the jnp-oracle kernel backend every query sharing an effective
        ``k`` lowers to ONE vmapped score+select dispatch over the cached
        slab.  The Pallas kernel (the default on a TPU) and the sharded
        path dispatch once per query, and the pruned host sweep (the
        default on a CPU) dispatches nothing: a per-query loop that still
        shares every cached structure.  ``dispatches`` shows which.  Returns
        ``[self.topk(q, k, metric) for q in queries]`` bit for bit on
        every path (asserted by the test suite)."""
        queries = list(queries)
        if metric not in METRICS:
            raise ValueError(metric)
        out: list = [None] * len(queries)
        batch: dict[int, list[int]] = {}          # effective k -> indices
        use_vmap = (backend != "host" and _prefer_kernel(backend)
                    and not kops._use_pallas(backend)
                    and self._mesh is None
                    and self.rows.shape[0] > 0)
        for i, q in enumerate(queries):
            if not use_vmap:
                out[i] = self.topk(q, k, metric, backend=backend)
                continue
            n_cand = self.n - (1 if isinstance(q, (int, np.integer))
                               else 0)
            kk = min(int(k), n_cand)
            if kk <= 0:
                out[i] = self.topk(q, k, metric, backend=backend)
            else:
                batch.setdefault(kk, []).append(i)
        for kk, idxs in batch.items():
            dev_rows, dev_col, dev_starts, dev_cards, _ = self._device()
            q_card, excl = [], []
            for i in idxs:
                q = queries[i]
                if isinstance(q, (int, np.integer)):
                    if not 0 <= int(q) < self.n:
                        raise IndexError(f"candidate index {int(q)} out "
                                         f"of range [0, {self.n})")
                    qc, ex = int(self.cards[int(q)]), int(q)
                else:
                    qc, ex = q.cardinality, -1
                if qc >= 2**31:
                    raise ValueError(
                        "query cardinality >= 2^31 unsupported")
                q_card.append(qc)
                excl.append(ex)
            with TraceAnnotation("engine.query_block"):
                q_words = self._query_words_dev_batch(
                    [queries[i] for i in idxs])
            with TraceAnnotation("engine.dispatch"):
                res = _batched_topk(metric, kk)(
                    dev_rows, dev_col, dev_starts, q_words,
                    jnp.asarray(q_card, jnp.int32), dev_cards,
                    jnp.asarray(excl, jnp.int32))
                self.dispatches += 1
            idx, score, inter = self._fetch(res)
            for j, i in enumerate(idxs):
                out[i] = (idx[j], score[j], inter[j])
        return out

    # -- pruned host path -----------------------------------------------

    def _host_inter(self, sel: np.ndarray, q64: np.ndarray) -> np.ndarray:
        """Exact intersection cardinalities of the selected candidates:
        gather their cached rows, AND against the query's key columns,
        popcount, segment-sum per candidate."""
        out = np.zeros(sel.size, np.int64)
        lens = (self.starts[sel + 1] - self.starts[sel]).astype(np.int64)
        total = int(lens.sum())
        if total == 0:
            return out
        offs = np.repeat(np.cumsum(lens) - lens, lens)
        ridx = np.arange(total) - offs + np.repeat(
            self.starts[sel].astype(np.int64), lens)
        per = np.bitwise_count(
            self.rows[ridx] & q64[self.row_col[ridx]]).sum(axis=1)
        np.add.at(out, np.repeat(np.arange(sel.size), lens),
                  per.astype(np.int64))
        return out

    def _topk_host(self, q64, qc, k, metric, exclude):
        """The pruning planner: score upper bounds from cardinalities
        alone (metric at ``inter = min(|Q|, |C|)`` -- monotone in inter,
        so a true float32 bound), exact-score the k best bounds to pin
        the running k-th score, and skip every candidate whose bound
        falls strictly below it."""
        ub = _scores_host(np.minimum(qc, self.cards), qc, self.cards,
                          metric)
        if exclude is not None:
            ub[exclude] = np.float32(-1.0)
        order_ub = np.argsort(-ub, kind="stable")
        seeds = order_ub[:k]
        score = np.full(self.n, np.float32(-1.0), np.float32)
        inter = np.zeros(self.n, np.int64)
        inter[seeds] = self._host_inter(seeds, q64)
        score[seeds] = _scores_host(inter[seeds], qc, self.cards[seeds],
                                    metric)
        tau = score[seeds].min()                 # running k-th score
        rest = order_ub[k:]
        survivors = rest[ub[rest] >= tau]        # bound < tau: skipped
        if survivors.size:
            inter[survivors] = self._host_inter(survivors, q64)
            score[survivors] = _scores_host(
                inter[survivors], qc, self.cards[survivors], metric)
        if exclude is not None:
            score[exclude] = np.float32(-1.0)
        order = np.argsort(-score, kind="stable")[:k]
        return order.astype(np.int64), score[order], inter[order]


@functools.lru_cache(maxsize=64)
def _sharded_topk(mesh, axis: str, metric: str, k: int, backend):
    """One jit'd sharded top-k per (mesh, metric, k, backend): under
    ``shard_map`` each shard gathers its rows from its OWN slab (the
    assembled per-shard slab splits back over ``axis``) and runs
    ``kops.similarity_topk_ids`` with ``axis``, which sums the partial
    intersection counts across shards before scoring and selecting.
    Only (L,) int32 counts cross the interconnect; no row leaves its
    shard, so a device holds its slab plus its own survivors' rows."""
    from jax.sharding import PartitionSpec as P

    def body(slab, lpos, col, starts, q, qc, cards, gidx, nval, ex):
        rows = jnp.take(slab, lpos[0], axis=0)
        return kops.similarity_topk_ids(
            rows, col[0], starts[0], q, qc, cards, gidx, metric=metric,
            k=k, n_valid=nval, exclude=ex, axis=axis, backend=backend)

    sp, rep = P(axis), P()
    return jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(sp, sp, sp, sp) + (rep,) * 6,
        out_specs=(rep, rep, rep), check_vma=False))


@functools.lru_cache(maxsize=64)
def _batched_topk(metric: str, k: int):
    """One jit'd vmap of the similarity oracle per (metric, k) class:
    in_axes batch the query block / cardinality / exclusion index while
    the resident candidate slab broadcasts."""
    fn = functools.partial(_refk.similarity_topk, metric=metric, k=k)
    return jax.jit(jax.vmap(fn, in_axes=(None, None, None, 0, 0, None, 0)))


# ---------------------------------------------------------------------------
# similarity joins
# ---------------------------------------------------------------------------

def jaccard_matrix(bitmaps, *, backend: str | None = None) -> np.ndarray:
    """(N, N) Jaccard similarity matrix over N bitmaps: the all-pairs
    similarity join, planned as one batched AND-count dispatch per
    container-type class over all N*(N-1)/2 pairs (not one per pair)."""
    bitmaps = list(bitmaps)
    n = len(bitmaps)
    out = np.ones((n, n), np.float64)
    if n < 2:
        return out
    iu, ju = np.triu_indices(n, k=1)
    pairs = [(bitmaps[i], bitmaps[j]) for i, j in zip(iu.tolist(),
                                                      ju.tolist())]
    inter = pairwise_card("and", pairs, backend=backend).astype(np.float64)
    cards = np.array([bm.cardinality for bm in bitmaps], np.float64)
    union = cards[iu] + cards[ju] - inter
    sim = np.divide(inter, union, out=np.ones_like(inter),
                    where=union > 0)
    out[iu, ju] = sim
    out[ju, iu] = sim
    return out
