"""Segmented wide-aggregation kernel: K-way OR/AND/XOR/threshold reductions
over bitset-promoted containers in ONE Pallas dispatch.

The paper's wide union (section 5.8, ``roaring_bitmap_or_many``) keeps an
accumulator container hot while streaming inputs through it; sections 4.1.2
and 5.9 argue the logical op and the population count must both happen while
the words are still in vector registers.  This kernel generalizes that to a
*segmented reduce*: the host planner (``repro.core.aggregate``) stacks every
container that shares a 16-bit chunk key into contiguous rows of an
``(N, WORDS)`` uint32 slab and describes the segments with a row-offset
vector ``starts`` of shape ``(S + 1,)`` (segment ``s`` owns rows
``starts[s]:starts[s+1]``).  One ``pallas_call`` then produces, per segment,
the reduced words *and* the Harley-Seal cardinality -- the popcount runs
exactly once per segment, at finalization, never per accumulation step
(the paper's "lazy" cardinality).

Grid layout: ``(S, jmax)`` where ``jmax`` is the (static) longest segment.
The inner dimension walks a segment's rows; the output block index ignores
it, so the accumulator stays resident in VMEM across the whole segment
(the standard Pallas revisited-output accumulation pattern).  Row offsets
arrive via scalar prefetch so the input index map can address ragged
segments; steps past a segment's end contribute the op identity.  Rows
travel as squeezed ``(1, WORDS)`` blocks of an ``(N, 1, WORDS)`` view (the
TPU refuses a ``(1, WORDS)`` block of an ``(N, WORDS)`` array), and a
dispatch runs in chunks of ``SEG_CHUNK`` segments so the prefetched
offsets fit in SMEM.

``threshold`` extends the same engine to T-occurrence queries ("Threshold
and Symmetric Functions over Bitmaps", Kaser & Lemire): a bit-sliced
ripple-carry counter (one uint32 plane per counter bit, ``L = ceil(log2(
jmax + 1))`` planes in VMEM scratch) counts how many inputs set each of the
2^16 bits, and finalization runs a bitwise magnitude comparator against
``T`` -- a runtime scalar (scalar prefetch), so threshold sweeps over the
same inputs reuse one compiled kernel.  Per-row integer weights (a
``(1, 1)`` VMEM block beside each row, static bit width) generalize the
counter to WEIGHTED threshold queries via shift-and-add: weight bit ``b``
feeds the row's plane into the counter at plane ``b``.

``andnot`` runs difference chains ``a - (b1 | b2 | ...)`` as one plan: the
minuend (each segment's first row) parks in the output block while the
subtrahends OR into a VMEM accumulator; the ANDNOT and the popcount fuse
into finalization ("Compressed bitmap indexes: beyond unions and
intersections", Kaser & Lemire).

``segment_reduce_cnf`` reduces an AND of ORs (a filter in conjunctive
normal form) in one pass over the rows: its grid walks rows, not
``(segment, depth)``, so clauses and segments of any length cost one step
a row.  A VMEM accumulator ORs each clause (restarted at the clause's
first row, the way ``andnot`` parks its minuend), each finished clause
ANDs into its segment's output block, and the segment's popcount fuses
into its last row.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.harley_seal import harley_seal_reduce
from repro.kernels.ref import WORDS

_FULL = np.uint32(0xFFFFFFFF)

OPS = ("or", "and", "xor", "andnot", "threshold")

# segments per pallas_call: 2 * SEG_CHUNK prefetched int32 scalars (row
# offsets + thresholds) stay well inside the TPU's 1 MiB of SMEM
SEG_CHUNK = 8192


def counter_planes(jmax: int) -> int:
    """Bit-sliced counter planes needed to count up to ``jmax`` inputs."""
    return max(1, int(jmax).bit_length())


def _identity(op: str):
    return _FULL if op == "and" else np.uint32(0)


def _combine(acc, x, op: str):
    if op == "or":
        return acc | x
    if op == "and":
        return acc & x
    if op == "xor":
        return acc ^ x
    raise ValueError(op)


def _finalize(words, card_ref, out_ref, seg_len):
    """Mask empty segments to zero and emit words + lazy popcount."""
    r = jnp.where(seg_len > 0, words, jnp.uint32(0))
    out_ref[...] = r
    card_ref[...] = harley_seal_reduce(r)


def _reduce_kernel(starts_ref, t_ref, slab_ref, out_ref, card_ref, *,
                   op, jmax):
    s = pl.program_id(0)
    j = pl.program_id(1)
    seg_len = starts_ref[s + 1] - starts_ref[s]
    x = jnp.where(j < seg_len, slab_ref[...], _identity(op))

    @pl.when(j == 0)
    def _():
        out_ref[...] = x

    @pl.when(j > 0)
    def _():
        out_ref[...] = _combine(out_ref[...], x, op)

    @pl.when(j == jmax - 1)
    def _():
        _finalize(out_ref[...], card_ref, out_ref, seg_len)


def _andnot_kernel(starts_ref, t_ref, slab_ref, out_ref, card_ref,
                   rest_ref, *, jmax):
    """Fused difference chain: row0 & ~(row1 | row2 | ...).

    The minuend (row 0) parks in ``out_ref`` while the subtrahends OR into
    the ``rest_ref`` VMEM accumulator; finalization masks and popcounts in
    the same pass (the planner's "OR-reduce the subtrahends, then ANDNOT
    finalize" contract)."""
    s = pl.program_id(0)
    j = pl.program_id(1)
    seg_len = starts_ref[s + 1] - starts_ref[s]
    x = jnp.where(j < seg_len, slab_ref[...], jnp.uint32(0))

    @pl.when(j == 0)
    def _():
        out_ref[...] = x
        rest_ref[...] = jnp.zeros_like(rest_ref)

    @pl.when(j > 0)
    def _():
        rest_ref[...] = rest_ref[...] | x

    @pl.when(j == jmax - 1)
    def _():
        _finalize(out_ref[...] & ~rest_ref[...], card_ref, out_ref, seg_len)


def _threshold_kernel(starts_ref, t_ref, slab_ref, w_ref, out_ref, card_ref,
                      cnt_ref, *, jmax, planes, wbits):
    s = pl.program_id(0)
    j = pl.program_id(1)
    seg_len = starts_ref[s + 1] - starts_ref[s]

    @pl.when(j == 0)
    def _():
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    # shift-and-add of one weighted input bit-plane into the bit-sliced
    # counter: weight bit b contributes the row's plane at counter plane b
    # (wbits == 1 degenerates to the unweighted ripple-carry add).  The
    # row's weight is a (1, 1) VMEM block riding the row's own index, so
    # no row-sized vector sits in SMEM.
    x = jnp.where(j < seg_len, slab_ref[...], jnp.uint32(0))
    w = w_ref[...]
    for b in range(wbits):
        carry = jnp.where((w >> b) & 1 == 1, x, jnp.uint32(0))
        for i in range(b, planes):
            ci = cnt_ref[i]
            cnt_ref[i] = ci ^ carry
            carry = ci & carry

    @pl.when(j == jmax - 1)
    def _():
        # bitwise magnitude comparator: count >= T, MSB first.  T arrives at
        # runtime (scalar prefetch) PER SEGMENT, so threshold sweeps share
        # one compile and coalesced multi-query batches carry each query's
        # own T; its bit i becomes an all-ones/all-zeros lane mask.
        t = t_ref[s]
        gt = jnp.zeros((1, WORDS), jnp.uint32)
        eq = jnp.full((1, WORDS), _FULL)
        for i in reversed(range(planes)):
            ci = cnt_ref[i]
            tmask = jnp.where((t >> i) & 1 == 1, _FULL,
                              jnp.uint32(0))
            gt = gt | (eq & ci & ~tmask)
            eq = eq & ~(ci ^ tmask)
        _finalize(gt | eq, card_ref, out_ref, seg_len)


def _segment_call(slab3, w3, starts, tval, op, jmax, planes, wbits,
                  interpret):
    """One pallas_call over the segments described by ``starts`` (absolute
    row offsets into ``slab3``).  Rows travel as squeezed (1, WORDS)
    blocks of the (N, 1, WORDS) slab, which satisfies the TPU's block
    tiling rule without padding a row to 8 sublanes."""
    n = slab3.shape[0]
    s = starts.shape[0] - 1

    def row_index(si, j, st, tv):
        return (jnp.minimum(st[si] + j, n - 1), 0, 0)

    in_specs = [pl.BlockSpec((None, 1, WORDS), row_index)]
    operands = [slab3]
    if op == "threshold":
        kernel = functools.partial(_threshold_kernel, jmax=jmax,
                                   planes=planes, wbits=wbits)
        scratch = [pltpu.VMEM((planes, 1, WORDS), jnp.uint32)]
        in_specs.append(pl.BlockSpec((None, 1, 1), row_index))
        operands.append(w3)
    elif op == "andnot":
        kernel = functools.partial(_andnot_kernel, jmax=jmax)
        scratch = [pltpu.VMEM((1, WORDS), jnp.uint32)]
    else:
        kernel = functools.partial(_reduce_kernel, op=op, jmax=jmax)
        scratch = []
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(s, jmax),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((None, 1, WORDS), lambda si, j, st, tv: (si, 0, 0)),
            pl.BlockSpec((None, 1, 1), lambda si, j, st, tv: (si, 0, 0))],
        scratch_shapes=scratch,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((s, 1, WORDS), jnp.uint32),
                   jax.ShapeDtypeStruct((s, 1, 1), jnp.int32)],
        interpret=interpret,
    )(starts, tval, *operands)


@functools.partial(jax.jit,
                   static_argnames=("op", "jmax", "planes", "wbits",
                                    "interpret"))
def segment_reduce(slab: jax.Array, starts: jax.Array, op: str, *,
                   jmax: int, threshold=0, weights: jax.Array | None = None,
                   planes: int | None = None, wbits: int = 1,
                   interpret: bool | None = None
                   ) -> tuple[jax.Array, jax.Array]:
    """Segmented K-way reduction fused with cardinality.

    slab:   (N, WORDS) uint32 bitset-promoted container rows, segment-major.
    starts: (S + 1,) int32 row offsets; segment s covers rows
            starts[s]:starts[s+1] (empty segments allowed -> card 0).
    op:     "or" | "and" | "xor" | "andnot" | "threshold".  "andnot" treats
            each segment's first row as the minuend: row0 & ~OR(rest).
    jmax:   static upper bound on segment length (>= max(diff(starts))).
    threshold: T for op="threshold"; a runtime scalar (sweeping T over the
            same inputs reuses one compilation) or a (S,) int32 vector of
            per-segment thresholds -- the multi-query coalescing path,
            where every queued T-occurrence query contributes its own
            segments to one dispatch.
    weights: (N,) int32 per-row occurrence weights for op="threshold"
            (default: 1 per row).  ``wbits`` is the static bit width of the
            largest weight and ``planes`` the static counter width; both
            must satisfy max-per-segment-total-weight < 2^planes and
            t < 2^planes.

    Segments run in chunks of at most ``SEG_CHUNK`` per ``pallas_call``:
    each chunk's row offsets and thresholds are scalar-prefetched into
    SMEM (1 MiB on TPU), so the chunk bounds what SMEM must hold however
    many segments a coalesced batch carries.

    Returns (words (S, WORDS) uint32, cards (S,) int32).
    """
    assert op in OPS, op
    assert jmax >= 1
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n = slab.shape[0]
    s = starts.shape[0] - 1
    starts = starts.astype(jnp.int32)
    # (S,) per-segment thresholds; a scalar T broadcasts to every segment
    tval = jnp.broadcast_to(
        jnp.asarray(threshold, jnp.int32).reshape(-1), (s,))
    w3 = None
    if op == "threshold":
        if planes is None:
            planes = counter_planes(jmax)
        w3 = (jnp.ones((n, 1, 1), jnp.int32) if weights is None
              else weights.astype(jnp.int32).reshape(n, 1, 1))
    slab3 = slab.astype(jnp.uint32).reshape(n, 1, WORDS)
    words, cards = [], []
    for c0 in range(0, s, SEG_CHUNK):
        c1 = min(s, c0 + SEG_CHUNK)
        w, c = _segment_call(slab3, w3, starts[c0:c1 + 1], tval[c0:c1],
                             op, jmax, planes, wbits, interpret)
        words.append(w)
        cards.append(c)
    words = words[0] if len(words) == 1 else jnp.concatenate(words)
    cards = cards[0] if len(cards) == 1 else jnp.concatenate(cards)
    return words.reshape(s, WORDS), cards.reshape(s)


@functools.partial(jax.jit,
                   static_argnames=("op", "jmax", "planes", "wbits",
                                    "interpret"))
def segment_reduce_rows(table: jax.Array, ids: jax.Array, starts: jax.Array,
                        op: str, *, jmax: int, threshold=0,
                        weights: jax.Array | None = None,
                        planes: int | None = None, wbits: int = 1,
                        interpret: bool | None = None
                        ) -> tuple[jax.Array, jax.Array]:
    """Resident-slab entry point: :func:`segment_reduce` over rows gathered
    from a device-resident ``table`` (a ``core.arena.BitmapArena`` slab,
    optionally with a per-call staged host block appended).

    ``ids`` (R,) int32 index ``table`` segment-major; pad ragged segments
    with id 0, the arena's reserved all-zero row (the op identity handling
    inside :func:`segment_reduce` masks padding anyway).  The gather runs
    on-device, so warm queries ship only ``ids``/``starts``/``threshold``
    over PCIe -- container words never leave the device.  See
    docs/MEMORY.md for the transfer accounting.
    """
    slab = jnp.take(table.astype(jnp.uint32), ids.astype(jnp.int32), axis=0)
    return segment_reduce(slab, starts, op, jmax=jmax, threshold=threshold,
                          weights=weights, planes=planes, wbits=wbits,
                          interpret=interpret)


@functools.partial(jax.jit,
                   static_argnames=("op", "jmax", "planes", "wbits",
                                    "interpret"))
def segment_reduce_rows_dual(table: jax.Array, staged: jax.Array,
                             pos: jax.Array, sidx: jax.Array,
                             starts: jax.Array, op: str, *, jmax: int,
                             threshold=0,
                             weights: jax.Array | None = None,
                             planes: int | None = None, wbits: int = 1,
                             interpret: bool | None = None
                             ) -> tuple[jax.Array, jax.Array]:
    """Dual-source row-table entry point: each slot gathers
    ``table[pos] | staged[sidx]`` on-device (exactly one side of every
    slot is a real row, the other the reserved all-zero row -- OR is
    exact slot selection), then reduces with the Pallas segment kernel.

    ``table`` is a resident single-device ``(cap, WORDS)`` arena slab
    and is NEVER copied per call;
    ``staged`` is the small per-call block of cold host rows (row 0
    zero).  Warm queries ship only ``pos``/``sidx``/``starts`` over
    PCIe."""
    slab = (jnp.take(table.astype(jnp.uint32), pos.astype(jnp.int32),
                     axis=0)
            | jnp.take(staged.astype(jnp.uint32), sidx.astype(jnp.int32),
                       axis=0))
    return segment_reduce(slab, starts, op, jmax=jmax, threshold=threshold,
                          weights=weights, planes=planes, wbits=wbits,
                          interpret=interpret)


# rows per segment_reduce_cnf call: one prefetched int32 a row in SMEM
CNF_ROW_CHUNK = 1 << 16

# flags of an AND-of-ORs row: first / last row of its clause, its clause
# the first / last of its segment
_C_FIRST, _C_LAST, _S_FIRST, _S_LAST = 1, 2, 4, 8


def _cnf_kernel(code_ref, slab_ref, out_ref, card_ref, acc_ref):
    """One row of an AND-of-ORs stream.  The row ORs into the clause
    accumulator ``acc_ref`` (restarted by a clause's first row); a
    clause's last row ANDs the clause into the segment's output block
    (set by the segment's first clause); a segment's last row emits its
    popcount.  ``code_ref[r]`` packs ``segment << 4 | flags``."""
    code = code_ref[pl.program_id(0)]
    x = slab_ref[...]
    acc = jnp.where((code & _C_FIRST) != 0, x, acc_ref[...] | x)
    acc_ref[...] = acc

    @pl.when((code & _C_LAST) != 0)
    def _():
        out_ref[...] = jnp.where((code & _S_FIRST) != 0, acc,
                                 out_ref[...] & acc)

    @pl.when((code & _S_LAST) != 0)
    def _():
        card_ref[...] = harley_seal_reduce(out_ref[...])



def _cnf_codes(n: int, clause_starts, starts):
    """Per row of an ``n``-row slab: ``segment << 4 | flags`` (first and
    last row of its clause, its clause first and last of its segment).
    Rows past the last clause go to segment ``S``, a slot of their own.
    A row's clause is the count of clause starts at or before it, less
    one (a scatter and a prefix sum, no search); a clause's segment
    likewise."""
    def owner(bounds, size):
        hits = jnp.zeros(size + 1, jnp.int32).at[bounds[:-1]].add(1)
        return jnp.cumsum(hits)[:size] - 1

    r = jnp.arange(n, dtype=jnp.int32)
    c_hi = clause_starts.shape[0] - 2
    s_pad = starts.shape[0] - 1
    clause = jnp.clip(owner(clause_starts, n), 0, c_hi)
    seg = jnp.clip(owner(starts, c_hi + 1)[clause], 0, s_pad - 1)
    c_last = r == clause_starts[clause + 1] - 1
    code = (jnp.where(r == clause_starts[clause], _C_FIRST, 0)
            | jnp.where(c_last, _C_LAST, 0)
            | jnp.where(clause == starts[seg], _S_FIRST, 0)
            | jnp.where(c_last & (clause == starts[seg + 1] - 1), _S_LAST, 0))
    pad = r >= clause_starts[-1]
    seg = jnp.where(pad, s_pad, seg)
    code = jnp.where(pad, _C_FIRST | _C_LAST | _S_FIRST | _S_LAST, code)
    return (seg << 4) | code


@functools.partial(jax.jit, static_argnames=("interpret",))
def segment_reduce_cnf(table: jax.Array, staged: jax.Array, pos: jax.Array,
                       sidx: jax.Array, clause_starts: jax.Array,
                       starts: jax.Array, *, interpret: bool | None = None
                       ) -> tuple[jax.Array, jax.Array]:
    """AND of ORs over rows gathered like
    :func:`segment_reduce_rows_dual` (``table[pos] | staged[sidx]``).

    clause_starts: (C + 1,) int32 row offsets; clause c ORs rows
            clause_starts[c]:clause_starts[c+1], at least one.
    starts: (S + 1,) int32 clause offsets; segment s ANDs clauses
            starts[s]:starts[s+1]; an empty segment reduces to zero.
    Rows past ``clause_starts[-1]`` are padding.

    One pass of one kernel over the rows in order (grid = rows): a VMEM
    accumulator ORs each clause, and the clause ANDs into its segment's
    output block, which stays in VMEM while its rows stream -- so the
    kernel reads each row once and writes each segment once, with no
    per-segment padding to a common depth.  The per-row segment ids and
    flags are scalar-prefetched (one int32 a row, in SMEM).  Returns
    (words (S, WORDS) uint32, cards (S,) int32).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    slab = (jnp.take(table.astype(jnp.uint32), pos.astype(jnp.int32),
                     axis=0)
            | jnp.take(staged.astype(jnp.uint32), sidx.astype(jnp.int32),
                       axis=0))
    n = slab.shape[0]
    starts = starts.astype(jnp.int32)
    s = starts.shape[0] - 1
    code = _cnf_codes(n, clause_starts.astype(jnp.int32), starts)

    def seg_block(r, code_ref):
        return (code_ref[r] >> 4, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n,),
        in_specs=[pl.BlockSpec((None, 1, WORDS),
                               lambda r, code_ref: (r, 0, 0))],
        out_specs=[pl.BlockSpec((None, 1, WORDS), seg_block),
                   pl.BlockSpec((None, 1, 1), seg_block)],
        scratch_shapes=[pltpu.VMEM((1, WORDS), jnp.uint32)],
    )
    words, cards = pl.pallas_call(
        _cnf_kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((s + 1, 1, WORDS), jnp.uint32),
                   jax.ShapeDtypeStruct((s + 1, 1, 1), jnp.int32)],
        interpret=interpret,
    )(code, slab.reshape(n, 1, WORDS))
    # a segment no row reaches was never written: it is empty
    full = starts[1:] > starts[:-1]
    return (jnp.where(full[:, None], words[:s, 0], jnp.uint32(0)),
            jnp.where(full, cards[:s, 0, 0], 0))
