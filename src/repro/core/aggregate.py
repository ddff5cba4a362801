"""Wide-aggregation planner: K-bitmap OR/AND/XOR/threshold, one dispatch.

The paper's wide union (section 5.8, ``roaring_bitmap_or_many``) streams
containers through an in-register accumulator; sections 4.1.2 and 5.9 insist
the logical op and the population count happen in the same pass.  "Compressed
bitmap indexes: beyond unions and intersections" (Kaser & Lemire) extends
wide aggregation past OR/AND, and "Threshold and Symmetric Functions over
Bitmaps" (Kaser & Lemire) motivates the T-occurrence query implemented here.

The planner walks the K input bitmaps' key lists once and groups containers
by 16-bit chunk key.  Each key is then either

  * a **pass-through** -- singleton keys (OR/XOR) are shared zero-copy;
    full-chunk runs short-circuit OR; groups a host fast path can finish
    cheaply stay on the host: run-only groups reduce with a vectorized
    boundary sweep at interval granularity (never touching 2^16 bits),
    array-only XOR/threshold groups count occurrences with bincount,
    small all-array unions concatenate, and AND anchors on the smallest
    member with vectorized membership filtering in cardinality-ascending
    order;
  * or a **slab segment** -- every remaining container is promoted to the
    device bitset layout (array containers of one OR/XOR group collapse into
    a single indicator row first), the rows are stacked segment-major into
    one ``(N, WORDS)`` uint32 slab, and a single
    ``kernels.ops.segment_reduce`` dispatch produces each segment's reduced
    words fused with its Harley-Seal cardinality -- O(1) dispatches
    regardless of K or container count, with the cardinality computed
    lazily once per segment (never per accumulation step).

Kernel results are repacked via ``optimize`` (run_optimize semantics), so
the output uses the memory-optimal container kind per chunk.

AND runs the paper's cardinality-ascending planning at the top level too:
key sets intersect cheapest-bitmap-first and the whole query exits early the
moment the candidate key set goes empty.

**Sharded multi-device path.**  When a 1-D device mesh is supplied (or
installed with ``set_default_mesh``), each slab segment's rows are
round-robined across the mesh axis and every shard runs the same
``segment_reduce`` kernel on its local rows.  Partials combine with a
``psum``-style all-reduce (``all_gather`` + an exact bitwise fold):

  * OR / XOR partials fold with the op itself (both are associative and
    commutative over disjoint row sets, so results are bit-identical to the
    single-device plan);
  * ANDNOT replicates the minuend row on every shard -- local partials
    ``a & ~local_or`` then fold with AND, since
    ``(a & ~x) & (a & ~y) == a & ~(x | y)``;
  * threshold exchanges the bit-sliced occurrence counters themselves
    (``kernels.ref.segment_counters``): local counters are all-gathered,
    ripple-carry added in the bit-sliced domain, and one comparator pass
    emits the result words;
  * AND exchanges a per-shard occupancy mask with the partials: shards
    holding no rows of a segment contribute the all-ones identity (the
    kernel's empty-segment convention is all-zeros, which would be wrong
    to fold), and a segment occupied by no shard resolves to empty.

A one-device mesh falls back transparently to the single-dispatch path.

With an ``arena`` (core/arena.py), the sharded path never re-stages
resident rows: each row is reduced on the shard whose slab holds it,
gathered from that LOCAL slab inside one jit (``_shard_reduce_arena``,
mirroring ``pairwise._topk_sharded``), so warm sharded aggregates move
only segment ids over the bridge, no row leaves its shard, and partials
fold on device -- zero container rows over PCIe, stat-asserted per
shard.  Cold rows ride a small replicated staged block whose row 0 is
reserved zero and reduce on shard 0, whose local row 0 is the arena's
zero row, making ``slab[pos] | staged[sidx]`` exact slot selection.
ANDNOT there reduces minuends and subtrahends as separate OR segments
and folds ``minuend & ~subtrahends`` after the exchange.
"""

from __future__ import annotations

import dataclasses

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import containers as C
from repro.core.containers import (
    ARRAY_MAX, CHUNK, ArrayContainer, BitsetContainer, Container,
    RunContainer, optimize,
)
from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.kernels.ref import WORDS
from repro.kernels.segment_ops import counter_planes

__all__ = ["or_many", "and_many", "xor_many", "andnot_many",
           "threshold_many", "set_default_mesh", "WidePlan", "plan_wide",
           "execute_plans", "execute_plan_host"]

def set_default_mesh(mesh) -> None:
    """Install a mesh used by every wide aggregate that is not given an
    explicit ``mesh=``; pass None to restore the single-device path.

    The mesh is stored in ``repro.dist.ctx`` (the single mesh source of
    truth shared with the model sharding layer); this function and
    ``ctx.set_wide_mesh`` / ``ctx.install_wide_mesh`` are interchangeable.
    """
    from repro.dist import ctx
    ctx.set_wide_mesh(mesh)


def _resolve_mesh(mesh):
    from repro.dist import ctx
    return ctx.resolve_wide(mesh)[0]


def _mesh_size(mesh) -> int:
    return int(np.prod(mesh.devices.shape))


def _mesh_axis(mesh) -> str:
    # one shared 1-D rule for every wide path (ctx.resolve_wide)
    from repro.dist import ctx
    return ctx.resolve_wide(mesh)[2]


def _bitmap_cls():
    from repro.core.bitmap import RoaringBitmap  # deferred: bitmap imports us
    return RoaringBitmap


def _pow2(x: int) -> int:
    return 1 if x <= 1 else 1 << (x - 1).bit_length()


def _group(bitmaps) -> dict[int, list[Container]]:
    groups: dict[int, list[Container]] = {}
    for bm in bitmaps:
        for k, c in zip(bm.keys, bm.containers):
            groups.setdefault(k, []).append(c)
    return groups


def _shallow(bm):
    RB = _bitmap_cls()
    return RB(list(bm.keys), list(bm.containers))


def _build(merged: dict[int, Container]):
    RB = _bitmap_cls()
    keys = sorted(merged)
    return RB(keys, [merged[k] for k in keys])


def _full_run() -> RunContainer:
    return RunContainer(np.array([[0, CHUNK - 1]], np.int32))


def _is_full(c: Container) -> bool:
    """card == 2^16 without touching the O(runs) card property."""
    if isinstance(c, RunContainer):
        return (c.runs.shape[0] == 1 and int(c.runs[0, 0]) == 0
                and int(c.runs[0, 1]) == CHUNK - 1)
    return c.card == CHUNK


def _prefer_kernel(backend: str | None) -> bool:
    """Whether dense array-only groups should ride the slab kernel
    (kernels.ops.prefer_kernel: TPU or a forced backend).  Run-only
    groups always use the interval sweep: it is strictly cheaper than
    bit-level promotion on every backend."""
    return kops.prefer_kernel(backend)


# ---------------------------------------------------------------------------
# promotion helpers (host side of the slab)
# ---------------------------------------------------------------------------

_words_row = C.container_words64      # container -> (1024,) uint64 words


def _array_indicator(arrays: list[ArrayContainer], op: str) -> np.ndarray:
    """(CHUNK,) 0/1 indicator of the OR / XOR of the group's arrays.

    OR: duplicate values across members are harmless, so plain indicator
    stores suffice.  XOR: the parity of the occurrence counts (bincount is
    a counting sort: O(values), no comparison sort)."""
    vals = arrays[0].values if len(arrays) == 1 else \
        np.concatenate([a.values for a in arrays])
    if op == "or" or len(arrays) == 1:
        ind = np.zeros(CHUNK, np.uint8)
        ind[vals] = 1
        return ind
    return (np.bincount(vals, minlength=CHUNK) & 1).astype(np.uint8)


def _indicator_row(arrays: list[ArrayContainer], op: str) -> np.ndarray:
    """Collapse every array container of one group into a single bitset
    row of the slab."""
    return np.packbits(_array_indicator(arrays, op),
                       bitorder="little").view(np.uint64)


def _row_ref(c: Container, arena):
    """Slab-row reference for one container: the arena row id (int) when
    the container is resident, else its promoted (1024,) uint64 words.
    ``_dispatch`` gathers int refs on-device (zero PCIe) and stages only
    the ndarray refs per call (see core/arena.py)."""
    if arena is not None:
        rid = arena.lookup(c)
        if rid is not None:
            return rid
    return _words_row(c)


def _array_rows(arrays: list[ArrayContainer], op: str, arena) -> list:
    """Slab rows for one group's array containers.  Without an arena the
    group collapses into a single indicator row (host bincount).  With an
    arena, resident arrays keep their individual device rows -- reducing
    them row-wise is bit-identical to the collapsed indicator for "or" /
    "xor" (parity per value is associative) -- and only the cold remainder
    collapses into one staged indicator row."""
    if not arrays:
        return []
    if arena is None:
        return [_indicator_row(arrays, op)]
    rows: list = []
    cold: list[ArrayContainer] = []
    for a in arrays:
        rid = arena.lookup(a)
        if rid is not None:
            rows.append(rid)
        else:
            cold.append(a)
    if cold:
        rows.append(_indicator_row(cold, op))
    return rows


def _from_indicator(ind: np.ndarray) -> Container | None:
    """(CHUNK,) 0/1 indicator -> optimal container (None when empty)."""
    card = int(ind.sum())
    if card == 0:
        return None
    if card <= ARRAY_MAX:
        return optimize(ArrayContainer(np.flatnonzero(ind).astype(np.uint16)))
    words = np.packbits(ind.astype(np.uint8),
                        bitorder="little").view(np.uint64)
    return optimize(BitsetContainer(words, card))


def _count_arrays(arrays: list[ArrayContainer], op: str,
                  t: int) -> Container | None:
    """All-array group fast path: occurrence counting via bincount, entirely
    on the host.  op "xor" keeps odd counts, "threshold" counts >= t."""
    vals = arrays[0].values if len(arrays) == 1 else \
        np.concatenate([a.values for a in arrays])
    cnt = np.bincount(vals, minlength=CHUNK)
    ind = (cnt & 1) if op == "xor" else (cnt >= t)
    return _from_indicator(ind.astype(np.uint8))


_SUB = np.int64(1) << 40        # andnot sweep: subtrahend coverage marker


def _sweep_run_groups(run_groups: list[tuple], op: str,
                      t: int) -> dict[int, Container]:
    """Run-only groups, ALL reduced in one vectorized boundary sweep at
    *interval* granularity (never expanding to 2^16 bits) -- the host twin
    of the slab's single dispatch.

    Each group is ``(key, containers)`` or ``(key, containers, weights)``;
    runs are lifted into a global coordinate space (``key << 16 | start``);
    chunks never overlap, so one sweep serves every group.  Each member's
    runs are disjoint, hence the (weighted) coverage count over an
    elementary interval equals the summed weight of members containing it:
    OR is count >= 1, AND count == K (per group), XOR odd count, threshold
    count >= t.  ANDNOT weights the minuend (the FIRST container of each
    group) 1 and every subtrahend ``_SUB``, keeping intervals with coverage
    exactly 1.  ``run_groups`` must be key-sorted."""
    out: dict[int, Container] = {}
    if not run_groups:
        return out
    starts_l, ends_l, delta_l = [], [], []
    for grp in run_groups:
        k, conts = grp[0], grp[1]
        wts = grp[2] if len(grp) > 2 else None
        if op == "andnot":
            wts = [1] + [_SUB] * (len(conts) - 1)
        r = conts[0].runs if len(conts) == 1 else \
            np.concatenate([c.runs for c in conts])
        if wts is not None:                 # weighted / andnot groups only
            delta_l.append(np.repeat(np.asarray(wts, np.int64),
                                     [c.runs.shape[0] for c in conts]))
        s = r[:, 0].astype(np.int64) + (np.int64(k) << 16)
        starts_l.append(s)
        ends_l.append(s + r[:, 1] + 1)                  # exclusive
    starts = np.concatenate(starts_l)
    ends = np.concatenate(ends_l)
    if delta_l:
        wdelta = np.concatenate(delta_l)
    else:
        wdelta = np.ones(starts.size, np.int64)
    pts = np.concatenate((starts, ends))
    delta = np.concatenate((wdelta, -wdelta))
    order = np.argsort(pts, kind="stable")
    upts, first = np.unique(pts[order], return_index=True)
    cov = np.cumsum(np.add.reduceat(delta[order], first))[:-1]  # / interval
    if op == "or":
        keep = cov >= 1
    elif op == "xor":
        keep = (cov & 1) == 1
    elif op == "and":
        gk = np.array([g[0] for g in run_groups], np.int64)
        gn = np.array([len(g[1]) for g in run_groups], np.int64)
        need = gn[np.searchsorted(gk, upts[:-1] >> 16)]
        keep = cov >= need                 # gap intervals have cov 0 < need
    elif op == "andnot":
        keep = cov == 1                    # minuend present, no subtrahend
    else:
        keep = cov >= t
    lo, hi = upts[:-1][keep], upts[1:][keep]
    if lo.size == 0:
        return out
    # merge contiguous intervals, but never across a chunk-key border
    same_key = (lo[1:] >> 16) == ((hi[:-1] - 1) >> 16)
    brk = np.concatenate(([True], (lo[1:] > hi[:-1]) | ~same_key))
    si = np.flatnonzero(brk)
    ei = np.concatenate((si[1:] - 1, [lo.size - 1]))
    rlo, rhi = lo[si], hi[ei]
    rkey = rlo >> 16
    runs_all = np.stack([rlo - (rkey << 16), rhi - 1 - rlo],
                        axis=1).astype(np.int32)
    uk, kfirst = np.unique(rkey, return_index=True)
    bounds = np.concatenate((kfirst, [rkey.size]))
    for i, k in enumerate(uk.tolist()):
        out[int(k)] = optimize(RunContainer(runs_all[bounds[i]:bounds[i + 1]]))
    return out


def _member_mask(vals: np.ndarray, c: Container) -> np.ndarray:
    """Boolean membership of the sorted uint16 ``vals`` in container ``c``
    (the AND / ANDNOT fast paths' vectorized membership probe)."""
    if isinstance(c, BitsetContainer):
        return C.bitset_test_many(c.words, vals)
    if isinstance(c, ArrayContainer):
        if c.values.size == 0:
            return np.zeros(vals.size, bool)
        idx = np.searchsorted(c.values, vals)
        idx[idx == c.values.size] = c.values.size - 1
        return c.values[idx] == vals
    starts = c.runs[:, 0]
    v = vals.astype(np.int32)
    i = np.searchsorted(starts, v, side="right") - 1
    i_c = np.maximum(i, 0)
    return (i >= 0) & (v <= starts[i_c] + c.runs[i_c, 1])


def _filter_values(vals: np.ndarray, c: Container) -> np.ndarray:
    """Keep the sorted uint16 ``vals`` that are members of ``c``."""
    if vals.size == 0:
        return vals
    return vals[_member_mask(vals, c)]


def _filter_values_out(vals: np.ndarray, c: Container) -> np.ndarray:
    """Keep the sorted uint16 ``vals`` that are NOT members of ``c``."""
    if vals.size == 0:
        return vals
    return vals[~_member_mask(vals, c)]


# ---------------------------------------------------------------------------
# the single kernel dispatch (and its sharded multi-device twin)
# ---------------------------------------------------------------------------

def _planes_for(totals: list[int], threshold: int) -> int:
    """Bit-sliced counter width for a threshold dispatch: wide enough for
    the largest attainable per-segment count AND for every bit of ``t``
    (the comparator reads t bit-by-bit; truncating high bits would compare
    against t mod 2^planes)."""
    return max(counter_planes(max(totals)), int(threshold).bit_length())


def _repack_segments(seg_keys, words, cards) -> dict[int, Container]:
    """(words, card) per segment -> optimal container kind per chunk."""
    out: dict[int, Container] = {}
    for key, w32, card in zip(seg_keys, np.asarray(words), np.asarray(cards)):
        card = int(card)
        if card == 0:
            continue
        w64 = np.ascontiguousarray(w32).view(np.uint64).copy()
        out[key] = optimize(C._result_from_bitset(w64, card))
    return out


def _dispatch(seg_keys: list, seg_rows: list[list[np.ndarray]],
              op: str, threshold, backend,
              seg_weights: list[list[int]] | None = None,
              mesh=None, arena=None) -> dict:
    """Stack per-segment rows into one slab, reduce in one kernel call,
    repack each segment's (words, card) into the optimal container kind.
    With a multi-device mesh, rows shard across the mesh axis instead
    (see ``_shard_reduce``).

    ``seg_keys`` are opaque hashable identities (plain chunk keys for one
    query; ``(query, chunk-key)`` tuples on the coalesced multi-query
    path).  ``threshold`` is an int, or -- for op "threshold" -- a
    per-segment sequence aligned with ``seg_keys`` (each coalesced query
    carries its own T into the same dispatch).  With an ``arena``
    (core/arena.py), row entries may be int slab-row ids: those gather
    from the resident device slab (no per-call staging) and only ndarray
    rows ride a staged block appended after it."""
    if not seg_keys:
        return {}
    tvec = None if isinstance(threshold, (int, np.integer)) else \
        [int(x) for x in threshold]

    def _t(i: int) -> int:
        return tvec[i] if tvec is not None else threshold

    # peel single-row segments: reducing one row is the identity (a lone
    # minuend for "andnot"; for "threshold" the row survives iff its own
    # weight reaches t), so a host popcount beats the pad/stack/transfer
    # of a kernel dispatch.  This is the small-K hot path: collapsed
    # array groups contribute exactly one indicator row per key.  Arena-
    # resident singletons (int row ids) are NOT peeled: their words are
    # already on device, so the device gather beats pulling them back to
    # the host just to popcount.
    peeled: dict = {}
    keep = [i for i, rows in enumerate(seg_rows)
            if len(rows) > 1 or not isinstance(rows[0], np.ndarray)]
    if len(keep) != len(seg_keys):
        for i, (key, rows) in enumerate(zip(seg_keys, seg_rows)):
            if len(rows) != 1 or not isinstance(rows[0], np.ndarray):
                continue
            if op == "threshold" and \
                    (seg_weights[i][0] if seg_weights else 1) < _t(i):
                continue
            card = int(np.bitwise_count(rows[0]).sum())
            if card:
                peeled[key] = optimize(C._result_from_bitset(rows[0], card))
        seg_keys = [seg_keys[i] for i in keep]
        seg_rows = [seg_rows[i] for i in keep]
        if seg_weights is not None:
            seg_weights = [seg_weights[i] for i in keep]
        if tvec is not None:
            tvec = [tvec[i] for i in keep]
        if not seg_keys:
            return peeled
    mesh = _resolve_mesh(mesh)
    if mesh is not None and _mesh_size(mesh) > 1:
        lens = [len(r) for r in seg_rows]
        tmax = max(tvec) if tvec is not None else threshold
        planes = None
        if op == "threshold" and seg_weights is not None:
            planes = _planes_for([sum(w) for w in seg_weights], tmax)
        t_arg = threshold if tvec is None else np.asarray(tvec, np.int32)
        if arena is not None:
            # resident rows reduce on the shard that holds them, gathered
            # from its LOCAL slab inside the jit -- ids over the bridge,
            # zero container rows over PCIe
            words, cards = _shard_reduce_arena(
                arena, seg_rows, lens, seg_weights, op, t_arg,
                backend, mesh, planes=planes, tmax=tmax)
        else:
            slab64 = np.stack([w for rows in seg_rows for w in rows])
            slab32 = slab64.view(np.uint32).reshape(slab64.shape[0],
                                                    WORDS)
            words, cards = _shard_reduce(
                jnp.asarray(slab32), lens, seg_weights, op, t_arg,
                backend, mesh, planes=planes, tmax=tmax)
        peeled.update(_repack_segments(seg_keys, words, cards))
        return peeled
    # bucket segments by padded depth: the reduce materializes an
    # (S, jmax, WORDS) gather, so one deep segment would inflate every
    # shallow coalesced query's compute to the global jmax.  Per-depth
    # kernel calls (<= log2 of the deepest segment, each at its own
    # power-of-two depth) keep the multi-query amortization without the
    # padding tax.  Small batches stay in ONE global-depth call: below
    # ~64 segments the extra dispatches cost more than the padding they
    # avoid (measured in the query_throughput bench at 64 concurrent).
    by_depth: dict[int, list[int]] = {}
    if len(seg_rows) >= 64:
        for i, rows in enumerate(seg_rows):
            by_depth.setdefault(_pow2(len(rows)), []).append(i)
    else:
        by_depth[_pow2(max(len(r) for r in seg_rows))] = \
            list(range(len(seg_rows)))
    for jmax, idxs in sorted(by_depth.items()):
        rows_g = [seg_rows[i] for i in idxs]
        lens = [len(r) for r in rows_g]
        n = sum(lens)
        wts_g = None if seg_weights is None else \
            [seg_weights[i] for i in idxs]
        tv_g = None if tvec is None else [tvec[i] for i in idxs]
        planes = None
        wbits = 1
        if op == "threshold" and wts_g is not None:
            planes = _planes_for([sum(w) for w in wts_g],
                                 max(tv_g) if tv_g is not None
                                 else threshold)
            wbits = max(int(w).bit_length() for ws in wts_g for w in ws)
        t_arg = threshold if tv_g is None else np.asarray(tv_g, np.int32)
        starts = np.zeros(len(lens) + 1, np.int32)
        starts[1:] = np.cumsum(lens)
        weights = None
        if wts_g is not None:
            weights = np.concatenate(
                [np.asarray(w, np.int32) for w in wts_g])
        # pad rows / segments to powers of two so jit and kernel
        # specializations are reused across calls
        n_pad = _pow2(n)
        if weights is not None and n_pad != n:
            weights = np.concatenate(
                [weights, np.ones(n_pad - n, np.int32)])
        s = len(lens)
        s_pad = _pow2(s)
        if s_pad != s:
            starts = np.concatenate(
                [starts, np.full(s_pad - s, starts[-1], np.int32)])
            if tv_g is not None:
                # padded segments are empty (zero rows): their T is inert
                t_arg = np.concatenate(
                    [t_arg, np.ones(s_pad - s, np.int32)])
        t_kw = t_arg if tv_g is None else jnp.asarray(t_arg)
        w_kw = None if weights is None else jnp.asarray(weights)
        if arena is None:
            slab64 = np.stack([w for rows in rows_g for w in rows])
            slab32 = slab64.view(np.uint32).reshape(n, WORDS)
            if n_pad != n:
                slab32 = np.concatenate(
                    [slab32, np.zeros((n_pad - n, WORDS), np.uint32)])
            words, cards = kops.segment_reduce(
                jnp.asarray(slab32), jnp.asarray(starts), op, jmax=jmax,
                threshold=t_kw, weights=w_kw,
                planes=planes, wbits=wbits, backend=backend)
        else:
            pos, sidx, staged = _stage_arena_rows(arena, rows_g, n_pad)
            if staged is None:              # warm: pure resident gather
                words, cards = kops.segment_reduce_rows(
                    arena.device_slab(), pos, jnp.asarray(starts), op,
                    jmax=jmax, threshold=t_kw, weights=w_kw,
                    planes=planes, wbits=wbits, backend=backend)
            else:
                words, cards = kops.segment_reduce_rows_dual(
                    arena.device_slab(), staged, pos, sidx,
                    jnp.asarray(starts), op, jmax=jmax, threshold=t_kw,
                    weights=w_kw, planes=planes, wbits=wbits,
                    backend=backend)
        peeled.update(_repack_segments(
            [seg_keys[i] for i in idxs], words[:s], cards[:s]))
    return peeled


def _stage_arena_rows(arena, rows_g: list[list], n_pad: int):
    """Turn one depth bucket's row refs into dual-source gather inputs
    ``(pos, sidx, staged)``: resident ids index the arena's device slab
    by position, cold ndarray rows stage into a small pow2-padded host
    block (row 0 reserved zero) indexed by ``sidx``.  Exactly one side of
    each slot is a real row; the other points at a zero row, so
    ``table[pos] | staged[sidx]`` is exact slot selection
    (``kernels.ops.segment_reduce_rows_dual``) and the resident slab is
    never copied per call.  Padding slots point both indices at the zero
    rows (the kernel masks padding by segment length anyway).  Warm
    queries return ``staged=None``: the only host->device traffic is the
    position vector itself."""
    pos: list[int] = []
    sidx: list[int] = []
    host: list[np.ndarray] = []
    for rows in rows_g:
        for r in rows:
            if isinstance(r, np.ndarray):
                pos.append(0)               # arena row 0: reserved zero
                sidx.append(1 + len(host))
                host.append(r)
            else:
                pos.append(int(r))
                sidx.append(0)              # staged row 0: reserved zero
    pos.extend([0] * (n_pad - len(pos)))
    sidx.extend([0] * (n_pad - len(sidx)))
    staged = None
    if host:
        h_pad = _pow2(1 + len(host))
        hb = np.zeros((h_pad, 1024), np.uint64)
        hb[1: 1 + len(host)] = np.stack(host)
        staged = jnp.asarray(hb.view(np.uint32).reshape(h_pad, WORDS))
        arena.stats.host_rows_staged += len(host)
    arena.stats.device_gathers += 1
    return (jnp.asarray(np.asarray(pos, np.int32)),
            jnp.asarray(np.asarray(sidx, np.int32)), staged)


def _shard_plan(seg_sizes: list[int], d: int, op: str,
                seg_weights: list[list[int]] | None):
    """Round-robin each segment's rows across ``d`` shards.

    Returns per-device (row ids into the segment-major slab, per-row
    weights, segment starts); every device sees the SAME segment structure
    (some local segments may be empty -> the kernel's identity).  For
    "andnot" the minuend (each segment's row 0) is REPLICATED on every
    shard so the local partials ``a & ~local_or`` fold with AND."""
    ids = [[] for _ in range(d)]
    wts = [[] for _ in range(d)]
    starts = [[0] for _ in range(d)]
    base = 0
    for si, nrow in enumerate(seg_sizes):
        w = None if seg_weights is None else seg_weights[si]
        for dev in range(d):
            if op == "andnot":
                mine = [base] + list(range(base + 1 + dev, base + nrow, d))
                mw = [1] * len(mine)
            else:
                mine = list(range(base + dev, base + nrow, d))
                mw = [1] * len(mine) if w is None else \
                    [w[i - base] for i in mine]
            ids[dev].extend(mine)
            wts[dev].extend(mw)
            starts[dev].append(len(ids[dev]))
        base += nrow
    return ids, wts, starts


def _shard_reduce(slab: jax.Array, seg_sizes: list[int],
                  seg_weights: list[list[int]] | None, op: str,
                  threshold, backend, mesh, planes: int | None = None,
                  tmax: int | None = None):
    """Sharded segmented reduce: split rows across the mesh axis, reduce
    per shard with the SAME segment kernel, all-reduce the partials.

    slab: (N, WORDS) uint32 rows, segment-major (segment s owns
    ``sum(seg_sizes[:s]) : sum(seg_sizes[:s+1])``).  Returns
    (words (S, WORDS), cards (S,)) identical to the single-device plan:
    OR/XOR partials fold with the op, ANDNOT partials (minuend replicated)
    fold with AND, threshold all-gathers the bit-sliced occurrence
    counters and adds them before one comparator pass, and AND exchanges a
    per-shard *occupancy mask* alongside the partials: a shard holding no
    rows of a segment contributes the all-ones identity (masked in after
    the kernel, whose empty-segment convention is all-zeros), and a
    segment no shard occupies resolves to empty -- the shard-safe
    empty-shard identity the single-device plan never needed.
    """
    from jax.sharding import PartitionSpec

    d = _mesh_size(mesh)
    axis = _mesh_axis(mesh)
    s = len(seg_sizes)
    ids, wts, starts = _shard_plan(seg_sizes, d, op, seg_weights)
    n_pad = _pow2(max(max(len(i) for i in ids), 1))
    s_pad = _pow2(s)
    ids_all = np.zeros((d, n_pad), np.int32)
    w_all = np.ones((d, n_pad), np.int32)
    starts_all = np.zeros((d, s_pad + 1), np.int32)
    jmax = 1
    for dev in range(d):
        k = len(ids[dev])
        ids_all[dev, :k] = ids[dev]
        w_all[dev, :k] = wts[dev]
        st = np.asarray(starts[dev], np.int32)
        starts_all[dev, :s + 1] = st
        starts_all[dev, s + 1:] = st[-1]
        jmax = max(jmax, int(np.diff(st).max(initial=1)))
    jmax = _pow2(jmax)
    if op == "threshold" and planes is None:
        planes = _planes_for(
            seg_sizes if seg_weights is None else
            [sum(w) for w in seg_weights],
            tmax if tmax is not None else threshold)
    if op == "threshold" and not isinstance(threshold, (int, np.integer)) \
            and s_pad != s:
        # per-segment T vector must match the padded segment count; the
        # padded segments are empty, so T=1 keeps their zero counters inert
        threshold = np.concatenate([np.asarray(threshold, np.int32),
                                    np.ones(s_pad - s, np.int32)])
    slab_all = jnp.take(slab.astype(jnp.uint32),
                        jnp.asarray(ids_all.reshape(-1)),
                        axis=0).reshape(d, n_pad, WORDS)

    def body(slab_d, starts_d, w_d):
        slab_l, starts_l, w_l = slab_d[0], starts_d[0], w_d[0]
        if op == "threshold":
            local = kops.segment_counters(
                slab_l, starts_l, jmax=jmax, planes=planes, weights=w_l,
                backend=backend)
            allp = jax.lax.all_gather(local, axis)      # (D, S, L, WORDS)
            tot = allp[0]
            for i in range(1, d):
                tot = kref.bitsliced_add(tot, allp[i])
            words = kref.counters_ge(tot, jnp.asarray(threshold, jnp.int32))
        elif op == "and":
            pw, _ = kops.segment_reduce(slab_l, starts_l, op, jmax=jmax,
                                        backend=backend)
            occ = (starts_l[1:] - starts_l[:-1]) > 0    # local occupancy
            pw = jnp.where(occ[:, None], pw, jnp.uint32(0xFFFFFFFF))
            allw = jax.lax.all_gather(pw, axis)         # (D, S, WORDS)
            allo = jax.lax.all_gather(occ, axis)        # (D, S)
            words, any_occ = allw[0], allo[0]
            for i in range(1, d):
                words = words & allw[i]
                any_occ = any_occ | allo[i]
            words = jnp.where(any_occ[:, None], words, jnp.uint32(0))
        else:
            pw, _ = kops.segment_reduce(slab_l, starts_l, op, jmax=jmax,
                                        backend=backend)
            allw = jax.lax.all_gather(pw, axis)         # (D, S, WORDS)
            comb = {"or": jnp.bitwise_or, "xor": jnp.bitwise_xor,
                    "andnot": jnp.bitwise_and}[op]
            words = allw[0]
            for i in range(1, d):
                words = comb(words, allw[i])
        return words, kref.popcount_words(words)

    spec = PartitionSpec(axis)
    with mesh:
        words, cards = jax.shard_map(
            body, mesh=mesh, in_specs=(spec, spec, spec),
            out_specs=(PartitionSpec(), PartitionSpec()),
            check_vma=False)(slab_all, jnp.asarray(starts_all),
                             jnp.asarray(w_all))
    return words[:s], cards[:s]


_SHARD_JIT: dict = {}       # (mesh, op, backend, d, jmax, planes) -> fn


def _sharded_rows_fn(mesh, axis: str, op: str, backend, d: int,
                     jmax: int, planes: int | None):
    """One jit'd sharded dispatch per (mesh, op, backend, depth) class --
    the boolean twin of ``pairwise._sharded_topk``: under ``shard_map``
    every shard gathers its rows from its OWN slab by local index (the
    assembled per-shard slab splits back over ``axis``), OR'd with a
    small replicated staged block (cold rows, all routed to shard 0,
    whose local row 0 is the arena's reserved zero row), reduces them
    with the segment kernel, and the partials fold with the
    ``_shard_reduce`` exchange rules.  ANDNOT reduces with OR over
    twice the segments -- subtrahends, then each minuend on the shard
    that holds it -- and folds ``minuend & ~subtrahends`` after the
    exchange.  The threshold rides as a traced argument (scalar or
    per-segment vector), so T-sweeps and coalesced batches reuse one
    compilation."""
    key = (mesh, op, backend, d, jmax, planes)
    fn = _SHARD_JIT.get(key)
    if fn is not None:
        return fn
    from jax.sharding import PartitionSpec as P

    def body(slab, staged, lpos, sidx, starts_d, w_d, t):
        rows = kref.gather_rows_dual(slab, staged, lpos[0], sidx[0])
        starts_l, w_l = starts_d[0], w_d[0]
        if op == "threshold":
            local = kops.segment_counters(
                rows, starts_l, jmax=jmax, planes=planes, weights=w_l,
                backend=backend)
            allp = jax.lax.all_gather(local, axis)      # (D, S, L, WORDS)
            tot = allp[0]
            for i in range(1, d):
                tot = kref.bitsliced_add(tot, allp[i])
            words = jnp.asarray(kref.counters_ge(tot, t))
        elif op == "and":
            pw, _ = kops.segment_reduce(rows, starts_l, op, jmax=jmax,
                                        backend=backend)
            occ = (starts_l[1:] - starts_l[:-1]) > 0    # local occupancy
            pw = jnp.where(occ[:, None], pw, jnp.uint32(0xFFFFFFFF))
            allw = jax.lax.all_gather(pw, axis)         # (D, S, WORDS)
            allo = jax.lax.all_gather(occ, axis)        # (D, S)
            words, any_occ = allw[0], allo[0]
            for i in range(1, d):
                words = words & allw[i]
                any_occ = any_occ | allo[i]
            words = jnp.where(any_occ[:, None], words, jnp.uint32(0))
        else:
            pw, _ = kops.segment_reduce(
                rows, starts_l, "or" if op == "andnot" else op,
                jmax=jmax, backend=backend)
            allw = jax.lax.all_gather(pw, axis)         # (D, S, WORDS)
            comb = jnp.bitwise_xor if op == "xor" else jnp.bitwise_or
            words = allw[0]
            for i in range(1, d):
                words = comb(words, allw[i])
            if op == "andnot":                          # [subtrahends | minuends]
                half = words.shape[0] // 2
                words = words[half:] & ~words[:half]
        return words, kref.popcount_words(words)

    sp = P(axis)
    fn = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(sp, P(), sp, sp, sp, sp, P()),
        out_specs=(P(), P()), check_vma=False))
    _SHARD_JIT[key] = fn
    return fn


def _shard_reduce_arena(arena, seg_rows: list[list], seg_sizes: list[int],
                        seg_weights: list[list[int]] | None, op: str,
                        threshold, backend, mesh,
                        planes: int | None = None,
                        tmax: int | None = None):
    """Sharded segmented reduce over arena row refs, end-to-end through
    ``ShardSlabs``: every resident row is reduced on the shard whose slab
    holds it (arena row ``r`` on shard ``r % S``, ``ShardSlabs.locate``)
    and gathered there inside one jit -- ids over the bridge, zero
    container rows over PCIe, and no row ever leaves its shard.  Cold
    ndarray rows ride a small replicated staged block (row 0 reserved
    zero) and reduce on shard 0.  The partials fold with the
    ``_shard_reduce`` exchange rules (see :func:`_sharded_rows_fn`), so
    results are bit-identical to the single-device plan: every fold is
    exact whichever shard a row lands on."""
    shards = arena.shard_slabs(mesh)
    d, axis = shards.size, shards.axis
    s = len(seg_sizes)
    s_pad = _pow2(s)
    n_seg = 2 * s_pad if op == "andnot" else s_pad
    seg: list[int] = []
    wts: list[int] = []
    res: list[int] = []                     # global row (cold: 0, the zero row)
    sidx: list[int] = []
    host: list[np.ndarray] = []
    for si, rows in enumerate(seg_rows):
        for j, r in enumerate(rows):
            # andnot: each minuend forms its own segment s_pad + si
            seg.append(s_pad + si if op == "andnot" and j == 0 else si)
            wts.append(1 if seg_weights is None else seg_weights[si][j])
            if isinstance(r, np.ndarray):
                host.append(r)
                sidx.append(len(host))      # staged row 0: reserved zero
                res.append(0)
            else:
                sidx.append(0)
                res.append(int(r))
    seg_a = np.asarray(seg, np.int64)
    sidx_a = np.asarray(sidx, np.int32)
    w_a = np.asarray(wts, np.int32)
    dev, lpos = shards.locate(np.asarray(res, np.int64))
    counts = np.bincount(dev, minlength=d)
    n_pad = _pow2(max(int(counts.max()), 1))
    lpos_all = np.zeros((d, n_pad), np.int32)   # pad: past the last start
    sidx_all = np.zeros((d, n_pad), np.int32)
    w_all = np.ones((d, n_pad), np.int32)
    starts_all = np.zeros((d, n_seg + 1), np.int32)
    jmax = 1
    for k in range(d):
        mine = np.flatnonzero(dev == k)
        mine = mine[np.argsort(seg_a[mine], kind="stable")]  # segment-major
        c = mine.size
        lpos_all[k, :c] = lpos[mine]
        sidx_all[k, :c] = sidx_a[mine]
        w_all[k, :c] = w_a[mine]
        per_seg = np.bincount(seg_a[mine], minlength=n_seg)
        starts_all[k, 1:] = np.cumsum(per_seg)
        jmax = max(jmax, int(per_seg.max(initial=1)))
    jmax = _pow2(jmax)
    h_pad = _pow2(1 + len(host))
    hb = np.zeros((h_pad, 1024), np.uint64)
    if host:
        hb[1: 1 + len(host)] = np.stack(host)
        arena.stats.host_rows_staged += len(host)
    if op == "threshold" and planes is None:
        planes = _planes_for(
            seg_sizes if seg_weights is None else
            [sum(w) for w in seg_weights],
            tmax if tmax is not None else threshold)
    if isinstance(threshold, (int, np.integer)):
        t_dev = np.int32(threshold)
    else:
        t_dev = np.asarray(threshold, np.int32)
        if s_pad != s:      # padded segments are empty: T=1 stays inert
            t_dev = np.concatenate(
                [t_dev, np.ones(s_pad - s, np.int32)])
    for st_ in shards.stats:
        st_.device_gathers += 1
    fn = _sharded_rows_fn(mesh, axis, op, backend, d, jmax, planes)
    staged = jnp.asarray(hb.view(np.uint32).reshape(h_pad, WORDS))
    words, cards = fn(shards.assembled(), staged,
                      jnp.asarray(lpos_all), jnp.asarray(sidx_all),
                      jnp.asarray(starts_all), jnp.asarray(w_all),
                      jnp.asarray(t_dev))
    return words[:s], cards[:s]


# ---------------------------------------------------------------------------
# query plans: planning separated from dispatch so N queries can coalesce
# into ONE dispatch per op class (the query server's engine tick)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class WidePlan:
    """One wide aggregate, planned but not yet dispatched.

    ``merged`` holds every chunk the host fast paths already resolved
    (zero-copy pass-throughs, run sweeps, bincount groups); ``seg_keys`` /
    ``seg_rows`` describe the dense remainder awaiting the slab kernel.
    ``execute_plans`` coalesces many plans into one ``segment_reduce``
    dispatch per op class -- a query id is just another segment
    coordinate -- and ``execute_plan_host`` is the numpy-only twin the
    query server degrades to when a kernel batch fails (bit-identical by
    construction: same rows, same repack).

    With an ``arena`` (core/arena.py), ``seg_rows`` entries may be int
    device-slab row ids instead of promoted uint64 rows: those never
    cross PCIe at dispatch.  ``execute_plans`` only coalesces plans that
    share the same arena (or its absence)."""
    op: str                               # dispatch class (OPS member)
    threshold: int                        # per-plan T (0 off-threshold)
    merged: dict[int, Container]          # host-resolved chunks
    seg_keys: list[int]                   # chunk key per pending segment
    seg_rows: list[list]                  # uint64 row | arena row id each
    seg_weights: list[list[int]] | None = None
    arena: object | None = None           # BitmapArena owning the id rows

    def slab_bytes(self) -> int:
        """Bytes this plan contributes to a coalesced slab (the admission
        queue's max-bytes accounting)."""
        return sum(len(r) for r in self.seg_rows) * 8192


def plan_wide(op: str, bitmaps, t: int = 0, weights=None, *,
              backend: str | None = None, arena=None) -> WidePlan:
    """Plan one wide aggregate without dispatching it.

    ``op`` is "or" | "and" | "xor" | "andnot" | "threshold"; for "andnot"
    the FIRST bitmap is the minuend and the rest are subtrahends; for
    "threshold", ``t`` / ``weights`` follow ``threshold_many`` (t == 1
    degenerates to an "or" plan and coalesces with the or class).
    Validation errors (bad op, t < 1, bad weights) raise here, at
    admission time -- never inside a dispatch batch.

    ``arena``: a ``core.arena.BitmapArena``; containers already resident
    in it plan as device-slab row ids (no promotion, no staging at
    dispatch).  Containers the arena does not know stage per-call exactly
    as without one -- results are bit-identical either way, residency is
    purely a transfer optimization (adopt bitmaps first to get warm
    plans)."""
    bitmaps = list(bitmaps)
    if op == "or":
        return _plan_or(bitmaps, backend, arena)
    if op == "xor":
        return _plan_xor(bitmaps, backend, arena)
    if op == "and":
        return _plan_and(bitmaps, backend, arena)
    if op == "andnot":
        if not bitmaps:
            raise ValueError("andnot needs at least the minuend")
        return _plan_andnot(bitmaps[0], bitmaps[1:], backend, arena)
    if op == "threshold":
        return _plan_threshold(bitmaps, t, weights, backend, arena)
    raise ValueError(f"unknown wide op {op!r}")


def _finish(plan: WidePlan, backend, mesh):
    merged = dict(plan.merged)
    merged.update(_dispatch(plan.seg_keys, plan.seg_rows, plan.op,
                            plan.threshold, backend,
                            seg_weights=plan.seg_weights, mesh=mesh,
                            arena=plan.arena))
    return _build(merged)


def execute_plans(plans, *, backend: str | None = None,
                  mesh=None) -> list:
    """Execute many ``WidePlan``s with ONE slab dispatch per op class.

    Every plan's pending segments join one slab per op (threshold plans
    ride together via per-segment T -- see ``kernels.ops.segment_reduce``),
    so a batch of N queries costs O(op classes) dispatches, not O(N).
    Returns one RoaringBitmap per plan, bit-identical to finishing each
    plan alone: segment results are independent by construction, and the
    repack path is shared."""
    plans = list(plans)
    results = [dict(p.merged) for p in plans]
    by_op: dict[tuple, list[int]] = {}       # (op, arena identity) class
    for i, p in enumerate(plans):
        if p.seg_keys:
            by_op.setdefault((p.op, id(p.arena)), []).append(i)
    for (op, _), idxs in by_op.items():
        keys: list = []
        rows: list[list] = []
        wts: list[list[int]] = []
        ts: list[int] = []
        any_w = any(plans[i].seg_weights is not None for i in idxs)
        for i in idxs:
            p = plans[i]
            keys.extend((i, k) for k in p.seg_keys)
            rows.extend(p.seg_rows)
            ts.extend([p.threshold] * len(p.seg_keys))
            if any_w:
                wts.extend(p.seg_weights if p.seg_weights is not None
                           else [[1] * len(r) for r in p.seg_rows])
        out = _dispatch(keys, rows, op,
                        ts if op == "threshold" else 0, backend,
                        seg_weights=wts if any_w else None, mesh=mesh,
                        arena=plans[idxs[0]].arena)
        for (i, k), cont in out.items():
            results[i][k] = cont
    return [_build(r) for r in results]


def execute_plan_host(plan: WidePlan):
    """Numpy-only execution of one plan: the query server's graceful-
    degradation path when a kernel batch keeps failing.

    Reduces each pending segment's uint64 rows with exact host bit math
    (the same rows the slab dispatch would consume) and repacks through
    the same ``optimize(C._result_from_bitset(...))`` path, so the result
    is bit-identical to the kernel plan -- only slower.  Touches no jax
    API at all: arena row ids resolve through the arena's authoritative
    HOST mirror, never the device slab."""
    merged = dict(plan.merged)
    for i, (key, seg) in enumerate(zip(plan.seg_keys, plan.seg_rows)):
        if plan.arena is not None:
            seg = [r if isinstance(r, np.ndarray)
                   else plan.arena.host_row(r) for r in seg]
        stack = np.stack(seg)                       # (R, 1024) uint64
        if plan.op == "or":
            w = np.bitwise_or.reduce(stack, axis=0)
        elif plan.op == "and":
            w = np.bitwise_and.reduce(stack, axis=0)
        elif plan.op == "xor":
            w = np.bitwise_xor.reduce(stack, axis=0)
        elif plan.op == "andnot":
            w = stack[0]
            if stack.shape[0] > 1:
                w = w & ~np.bitwise_or.reduce(stack[1:], axis=0)
        elif plan.op == "threshold":
            bits = np.unpackbits(stack.view(np.uint8), axis=1,
                                 bitorder="little").astype(np.int64)
            if plan.seg_weights is not None:
                bits *= np.asarray(plan.seg_weights[i],
                                   np.int64)[:, None]
            keepbits = bits.sum(axis=0) >= plan.threshold
            w = np.packbits(keepbits, bitorder="little").view(np.uint64)
        else:
            raise ValueError(plan.op)
        card = int(np.bitwise_count(w).sum())
        if card:
            merged[key] = optimize(C._result_from_bitset(w.copy(), card))
    return _build(merged)


# ---------------------------------------------------------------------------
# public wide aggregates
# ---------------------------------------------------------------------------

def or_many(bitmaps, *, backend: str | None = None, mesh=None,
            arena=None):
    """Union of K bitmaps in one kernel dispatch (paper section 5.8);
    with a multi-device ``mesh``, one sharded dispatch per shard.
    ``arena``: resident containers dispatch from the device slab without
    per-call staging (see ``plan_wide``)."""
    return _finish(plan_wide("or", bitmaps, backend=backend,
                             arena=arena), backend, mesh)


def _plan_or(bitmaps, backend, arena=None) -> WidePlan:
    if len(bitmaps) <= 1:
        return WidePlan("or", 0,
                        dict(zip(bitmaps[0].keys, bitmaps[0].containers))
                        if bitmaps else {}, [], [])
    prefer_kernel = _prefer_kernel(backend)
    groups = _group(bitmaps)
    merged: dict[int, Container] = {}
    seg_keys: list[int] = []
    seg_rows: list[list[np.ndarray]] = []
    run_groups: list[tuple[int, list[RunContainer]]] = []
    for k in sorted(groups):
        g = groups[k]
        if len(g) == 1:
            merged[k] = g[0]                       # zero-copy pass-through
            continue
        if all(isinstance(c, RunContainer) for c in g):
            run_groups.append((k, g))              # interval-level union
            continue
        if any(_is_full(c) for c in g):
            merged[k] = _full_run()                # full-chunk short-circuit
            continue
        arrays = [c for c in g if isinstance(c, ArrayContainer)]
        others = [c for c in g if not isinstance(c, ArrayContainer)]
        if not others:
            if sum(a.card for a in arrays) <= ARRAY_MAX:
                merged[k] = ArrayContainer(
                    np.unique(np.concatenate([a.values for a in arrays])))
                continue
            if not prefer_kernel:
                c = _from_indicator(_array_indicator(arrays, "or"))
                if c is not None:
                    merged[k] = c
                continue
        rows = _array_rows(arrays, "or", arena)
        rows.extend(_row_ref(c, arena) for c in others)
        seg_keys.append(k)
        seg_rows.append(rows)
    merged.update(_sweep_run_groups(run_groups, "or", 0))
    return WidePlan("or", 0, merged, seg_keys, seg_rows, arena=arena)


def xor_many(bitmaps, *, backend: str | None = None, mesh=None,
             arena=None):
    """Wide symmetric difference: a value survives iff it occurs in an odd
    number of inputs (K-ary XOR).  ``arena``: resident containers dispatch
    from the device slab without per-call staging (see ``plan_wide``)."""
    return _finish(plan_wide("xor", bitmaps, backend=backend,
                             arena=arena), backend, mesh)


def _plan_xor(bitmaps, backend, arena=None) -> WidePlan:
    if len(bitmaps) <= 1:
        return WidePlan("xor", 0,
                        dict(zip(bitmaps[0].keys, bitmaps[0].containers))
                        if bitmaps else {}, [], [])
    groups = _group(bitmaps)
    merged: dict[int, Container] = {}
    seg_keys: list[int] = []
    seg_rows: list[list[np.ndarray]] = []
    run_groups: list[tuple[int, list[RunContainer]]] = []
    for k in sorted(groups):
        g = groups[k]
        if len(g) == 1:
            merged[k] = g[0]
            continue
        if all(isinstance(c, RunContainer) for c in g):
            run_groups.append((k, g))              # interval-level parity
            continue
        arrays = [c for c in g if isinstance(c, ArrayContainer)]
        others = [c for c in g if not isinstance(c, ArrayContainer)]
        if not others:
            c = _count_arrays(arrays, "xor", 0)    # host occurrence parity
            if c is not None:
                merged[k] = c
            continue
        rows = _array_rows(arrays, "xor", arena)
        rows.extend(_row_ref(c, arena) for c in others)
        seg_keys.append(k)
        seg_rows.append(rows)
    merged.update(_sweep_run_groups(run_groups, "xor", 0))
    return WidePlan("xor", 0, merged, seg_keys, seg_rows, arena=arena)


def and_many(bitmaps, *, backend: str | None = None, mesh=None,
             arena=None):
    """Intersection of K bitmaps: cardinality-ascending key pruning with
    empty-key early exit, array-anchored host filtering for sparse groups,
    one kernel dispatch for the dense remainder.

    With a multi-device ``mesh``, dense segments shard across the mesh
    axis like the other aggregates: each shard ANDs its local rows and
    exchanges an occupancy mask with its partial, so shards holding no
    rows of a segment contribute the all-ones identity instead of the
    kernel's empty-segment zeros (see ``_shard_reduce``).  ``arena``:
    resident containers dispatch from the device slab without per-call
    staging (see ``plan_wide``)."""
    return _finish(plan_wide("and", bitmaps, backend=backend,
                             arena=arena), backend, mesh)


def _plan_and(bitmaps, backend, arena=None) -> WidePlan:
    if len(bitmaps) <= 1:
        return WidePlan("and", 0,
                        dict(zip(bitmaps[0].keys, bitmaps[0].containers))
                        if bitmaps else {}, [], [])
    order = sorted(bitmaps, key=lambda b: b.cardinality)
    common = set(order[0].keys)
    for bm in order[1:]:
        common &= set(bm.keys)
        if not common:
            return WidePlan("and", 0, {}, [], [])  # empty-key early exit
    lookup = [dict(zip(bm.keys, bm.containers)) for bm in bitmaps]
    merged: dict[int, Container] = {}
    seg_keys: list[int] = []
    seg_rows: list[list[np.ndarray]] = []
    run_groups: list[tuple[int, list[RunContainer]]] = []
    for k in sorted(common):
        g = sorted((lk[k] for lk in lookup), key=lambda c: c.card)
        if all(isinstance(c, RunContainer) for c in g):
            run_groups.append((k, g))              # interval intersection
            continue
        smallest = g[0]
        if isinstance(smallest, RunContainer) and smallest.card <= ARRAY_MAX:
            smallest = ArrayContainer(smallest.to_array_values())
        if isinstance(smallest, ArrayContainer):
            # array-anchored: the result is a subset of the smallest member,
            # so vectorized membership probes beat promoting the group
            vals = smallest.values
            for c in g[1:]:
                vals = _filter_values(vals, c)
                if vals.size == 0:
                    break
            if vals.size:
                merged[k] = ArrayContainer(vals)
            continue
        seg_keys.append(k)
        seg_rows.append([_row_ref(c, arena) for c in g])
    merged.update(_sweep_run_groups(run_groups, "and", 0))
    return WidePlan("and", 0, merged, seg_keys, seg_rows, arena=arena)


def andnot_many(minuend, subtrahends, *, backend: str | None = None,
                mesh=None, arena=None):
    """Difference chain ``a - (b1 | b2 | ...)`` as ONE plan: subtrahends
    OR-reduce segment-wise and a fused ANDNOT finalizes in the kernel
    ("Compressed bitmap indexes: beyond unions and intersections",
    Kaser & Lemire -- never materializes the intermediate union).

    Keys absent from every subtrahend pass through zero-copy; keys whose
    subtrahend group contains a full chunk drop immediately; array-probe
    and interval-sweep fast paths mirror the other aggregates.
    ``arena``: resident containers dispatch from the device slab without
    per-call staging (see ``plan_wide``)."""
    return _finish(plan_wide("andnot", [minuend, *subtrahends],
                             backend=backend, arena=arena), backend,
                   mesh)


def _plan_andnot(minuend, subtrahends, backend, arena=None) -> WidePlan:
    if not subtrahends:
        return WidePlan("andnot", 0,
                        dict(zip(minuend.keys, minuend.containers)),
                        [], [])
    sub_groups = _group(subtrahends)
    merged: dict[int, Container] = {}
    seg_keys: list[int] = []
    seg_rows: list[list[np.ndarray]] = []
    run_groups: list[tuple[int, list[Container]]] = []
    for k, c in zip(minuend.keys, minuend.containers):
        g = sub_groups.get(k)
        if g is None:
            merged[k] = c                          # zero-copy pass-through
            continue
        if any(_is_full(x) for x in g):
            continue                               # chunk fully subtracted
        if isinstance(c, RunContainer) and \
                all(isinstance(x, RunContainer) for x in g):
            run_groups.append((k, [c] + g))        # interval-level diff
            continue
        cc = c
        if isinstance(cc, RunContainer) and cc.card <= ARRAY_MAX:
            cc = ArrayContainer(cc.to_array_values())
        if isinstance(cc, ArrayContainer):
            # array-anchored: the result is a subset of the minuend, so
            # vectorized NOT-member probes beat promoting the group
            vals = cc.values
            for x in sorted(g, key=lambda q: -q.card):
                vals = _filter_values_out(vals, x)
                if vals.size == 0:
                    break
            if vals.size:
                merged[k] = ArrayContainer(vals)
            continue
        arrays = [x for x in g if isinstance(x, ArrayContainer)]
        others = [x for x in g if not isinstance(x, ArrayContainer)]
        rows = [_row_ref(c, arena)]                # minuend is row 0
        rows.extend(_array_rows(arrays, "or", arena))
        rows.extend(_row_ref(x, arena) for x in others)
        seg_keys.append(k)
        seg_rows.append(rows)
    merged.update(_sweep_run_groups(run_groups, "andnot", 0))
    return WidePlan("andnot", 0, merged, seg_keys, seg_rows, arena=arena)


def _check_weights(weights, k: int) -> list[int] | None:
    """Validate per-bitmap threshold weights; None when they degenerate to
    the unweighted path (all 1).  The total weight must fit int32: the
    kernel's counters and the jnp oracle accumulate in int32 (the host
    fast paths are int64, and results must not depend on container kind).
    """
    if weights is None:
        return None
    w = [int(x) for x in weights]
    if len(w) != k:
        raise ValueError(f"need one weight per bitmap: {len(w)} != {k}")
    if any(x < 1 for x in w):
        raise ValueError(f"weights must be >= 1, got {w}")
    if sum(w) >= 1 << 31:
        raise ValueError(
            f"total weight {sum(w)} overflows the int32 counter domain")
    return None if all(x == 1 for x in w) else w


def threshold_many(bitmaps, t: int, *, weights=None,
                   backend: str | None = None, mesh=None, arena=None):
    """T-occurrence query: values whose (weighted) occurrence count over
    the K inputs reaches ``t`` (Kaser & Lemire's threshold function; T=1 is
    union, unweighted T=K intersection).

    ``weights`` are per-bitmap positive integers added into the same
    bit-sliced counter circuit (weight 1 everywhere degenerates to the
    unweighted plan, bit for bit).  Keys whose total attainable weight
    stays below ``t`` are pruned on the host.  ``arena``: resident
    containers dispatch from the device slab without per-call staging
    (see ``plan_wide``)."""
    return _finish(plan_wide("threshold", bitmaps, t, weights,
                             backend=backend, arena=arena), backend,
                   mesh)


def _plan_threshold(bitmaps, t, weights, backend, arena=None) -> WidePlan:
    t = int(t)
    if t < 1:
        raise ValueError(f"threshold must be >= 1, got {t}")
    weights = _check_weights(weights, len(bitmaps))
    if not bitmaps or (weights is None and t > len(bitmaps)) or \
            (weights is not None and t > sum(weights)):
        return WidePlan("threshold", t, {}, [], [])
    if t == 1:
        return _plan_or(bitmaps, backend, arena)   # coalesces with "or"
    if weights is not None:
        return _plan_threshold_weighted(bitmaps, t, weights, backend,
                                        arena)
    groups = _group(bitmaps)
    merged: dict[int, Container] = {}
    seg_keys: list[int] = []
    seg_rows: list[list[np.ndarray]] = []
    run_groups: list[tuple[int, list[RunContainer]]] = []
    for k in sorted(groups):
        g = groups[k]
        if len(g) < t:
            continue                               # can never reach T
        if all(isinstance(c, RunContainer) for c in g):
            run_groups.append((k, g))              # interval-level counting
            continue
        if all(isinstance(c, ArrayContainer) for c in g):
            c = _count_arrays(g, "threshold", t)   # host occurrence counts
            if c is not None:
                merged[k] = c
            continue
        seg_keys.append(k)
        seg_rows.append([_row_ref(c, arena) for c in g])
    merged.update(_sweep_run_groups(run_groups, "threshold", t))
    return WidePlan("threshold", t, merged, seg_keys, seg_rows,
                    arena=arena)


def _plan_threshold_weighted(bitmaps, t: int, weights: list[int],
                             backend, arena=None) -> WidePlan:
    """Weighted threshold body: identical planning shape, with per-member
    weights threaded through the sweep, the bincount fast path, and the
    kernel's shift-and-add counter circuit."""
    groups: dict[int, list[tuple[Container, int]]] = {}
    for bm, w in zip(bitmaps, weights):
        for k, c in zip(bm.keys, bm.containers):
            groups.setdefault(k, []).append((c, w))
    merged: dict[int, Container] = {}
    seg_keys: list[int] = []
    seg_rows: list[list[np.ndarray]] = []
    seg_wts: list[list[int]] = []
    run_groups: list[tuple] = []
    for k in sorted(groups):
        g = groups[k]
        if sum(w for _, w in g) < t:
            continue                               # can never reach T
        if all(isinstance(c, RunContainer) for c, _ in g):
            run_groups.append((k, [c for c, _ in g], [w for _, w in g]))
            continue
        if all(isinstance(c, ArrayContainer) for c, _ in g):
            vals = np.concatenate([c.values for c, _ in g])
            wrep = np.repeat(np.asarray([w for _, w in g], np.int64),
                             [c.values.size for c, _ in g])
            # bincount's float64 sums are exact for int totals < 2^53
            # (weights are bounded to the int32 domain by _check_weights)
            cnt = np.bincount(vals, weights=wrep, minlength=CHUNK)
            c = _from_indicator((cnt >= t).astype(np.uint8))
            if c is not None:
                merged[k] = c
            continue
        seg_keys.append(k)
        seg_rows.append([_row_ref(c, arena) for c, _ in g])
        seg_wts.append([w for _, w in g])
    merged.update(_sweep_run_groups(run_groups, "threshold", t))
    return WidePlan("threshold", t, merged, seg_keys, seg_rows, seg_wts,
                    arena=arena)
