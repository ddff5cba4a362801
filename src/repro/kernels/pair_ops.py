"""Batched pairwise container kernels: the two-by-two analogue of the
segmented wide-aggregation engine.

The paper's central performance contribution is *vectorized two-by-two* set
algebra (sections 4.2-4.5): SIMD intersection, union, difference and
symmetric difference over container pairs.  The host planner
(``repro.core.pairwise``) key-merges a batch of bitmap pairs, buckets the
matched container pairs by type class, and issues ONE dispatch per class
into the kernels here:

  * ``bitset_pair_op`` -- bitset x bitset (section 4.1.2): stacked word
    rows, a logical op *id per row* (so one dispatch can run a mixed-op
    batch), fused with the Harley-Seal cardinality.  ``bitset_pair_card``
    is the count-only twin (section 5.9: the result words never leave
    registers -- the Jaccard / cosine / intersects hot path).
  * ``array_bitset_probe`` -- array x bitset (the asymmetric case of
    section 4.2): each sorted array value probes the bitset row; the
    paper's per-value binary search degenerates to a word fetch + bit test
    in the bitset domain.  The VPU has no vector gather, so the bitset's
    words stream from SMEM one at a time, each tested against every
    value slot at once.

Array x array pairs ride ``kernels.array_ops`` (the pcmpistrm analogue),
extended by this PR with a two-sided-mask variant (feeding OR / XOR /
ANDNOT materialization) and a count-only variant.  Run containers stay on
the host planner's interval fast paths.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.array_ops import mask_invalid, sum_to_cell
from repro.kernels.harley_seal import DEFAULT_BLOCK, harley_seal_reduce
from repro.kernels.ref import ARRAY_CAP, CONTAINER_BITS, WORDS


def _mixed_op(a, b, opid):
    """Per-row op select: opid broadcasts (block, 1) against (block, WORDS).
    All four ops are computed and selected -- on the VPU the four logical
    ops cost less than a branch, exactly the paper's branch-free ethos."""
    return jnp.where(opid == 0, a & b,
                     jnp.where(opid == 1, a | b,
                               jnp.where(opid == 2, a ^ b, a & ~b)))


def _pair_op_kernel(opid_ref, a_ref, b_ref, out_ref, card_ref):
    r = _mixed_op(a_ref[...], b_ref[...], opid_ref[...])
    out_ref[...] = r
    card_ref[...] = harley_seal_reduce(r)


def _pair_card_kernel(opid_ref, a_ref, b_ref, card_ref):
    r = _mixed_op(a_ref[...], b_ref[...], opid_ref[...])
    card_ref[...] = harley_seal_reduce(r)


def _pad_rows(x, block, fill=0):
    n_pad = (-x.shape[0]) % block
    if not n_pad:
        return x
    return jnp.pad(x, ((0, n_pad),) + ((0, 0),) * (x.ndim - 1),
                   constant_values=fill)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def bitset_pair_op(a: jax.Array, b: jax.Array, opids: jax.Array, *,
                   block: int = DEFAULT_BLOCK,
                   interpret: bool | None = None
                   ) -> tuple[jax.Array, jax.Array]:
    """(M, WORDS) x2 uint32 + (M,) int32 op ids -> (words, cards).

    One dispatch for an arbitrary mixed-op batch of bitset pairs: op id
    ``i`` of row ``r`` selects ``PAIR_OPS[i]`` for that row."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n = a.shape[0]
    a, b = _pad_rows(a, block), _pad_rows(b, block)
    ops2d = _pad_rows(opids.astype(jnp.int32)[:, None], block)
    grid = (a.shape[0] // block,)
    spec = pl.BlockSpec((block, WORDS), lambda i: (i, 0))
    ospec = pl.BlockSpec((block, 1), lambda i: (i, 0))
    out, card = pl.pallas_call(
        _pair_op_kernel,
        grid=grid,
        in_specs=[ospec, spec, spec],
        out_specs=[spec, ospec],
        out_shape=[
            jax.ShapeDtypeStruct((a.shape[0], WORDS), jnp.uint32),
            jax.ShapeDtypeStruct((a.shape[0], 1), jnp.int32),
        ],
        interpret=interpret,
    )(ops2d, a.astype(jnp.uint32), b.astype(jnp.uint32))
    return out[:n], card[:n, 0]


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def bitset_pair_card(a: jax.Array, b: jax.Array, opids: jax.Array, *,
                     block: int = DEFAULT_BLOCK,
                     interpret: bool | None = None) -> jax.Array:
    """Count-only mixed-op batch: result words stay in registers (paper
    section 5.9) -- the similarity-join inner loop."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n = a.shape[0]
    a, b = _pad_rows(a, block), _pad_rows(b, block)
    ops2d = _pad_rows(opids.astype(jnp.int32)[:, None], block)
    grid = (a.shape[0] // block,)
    spec = pl.BlockSpec((block, WORDS), lambda i: (i, 0))
    ospec = pl.BlockSpec((block, 1), lambda i: (i, 0))
    card = pl.pallas_call(
        _pair_card_kernel,
        grid=grid,
        in_specs=[ospec, spec, spec],
        out_specs=ospec,
        out_shape=jax.ShapeDtypeStruct((a.shape[0], 1), jnp.int32),
        interpret=interpret,
    )(ops2d, a.astype(jnp.uint32), b.astype(jnp.uint32))
    return card[:n, 0]


def _probe_kernel(vals_ref, words_smem, mask_ref, count_ref):
    """Each of the pair's 4096 value slots (a (32, 128) vector; invalid
    slots hold a sentinel past the last word) tests its bit: one step per
    bitset word, broadcast from SMEM against every slot at once."""
    v = vals_ref[...]
    widx, shift = v >> 5, v & 31

    def step(w, hit):
        bit = jax.lax.shift_right_logical(
            jnp.broadcast_to(words_smem[w], v.shape), shift) & 1
        return hit | jnp.where(widx == w, bit, 0)

    mask = jax.lax.fori_loop(0, WORDS, step, jnp.zeros(v.shape, jnp.int32))
    mask_ref[...] = mask
    count_ref[...] = sum_to_cell(mask)


@functools.partial(jax.jit, static_argnames=("interpret",))
def array_bitset_probe(vals: jax.Array, card: jax.Array,
                       words: jax.Array, *,
                       interpret: bool | None = None
                       ) -> tuple[jax.Array, jax.Array]:
    """Batched array x bitset probe.

    vals: (M, ARRAY_CAP) int32 sorted uint16-valued (slots >= card
    ignored); card: (M,) int32; words: (M, WORDS) uint32 bitset rows.
    Returns (mask (M, ARRAY_CAP) int32 over the array's slots, count (M,)).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n = vals.shape[0]
    v = mask_invalid(vals, card, CONTAINER_BITS)
    vspec = pl.BlockSpec((None, ARRAY_CAP // 128, 128), lambda i: (i, 0, 0))
    mask, count = pl.pallas_call(
        _probe_kernel,
        grid=(n,),
        in_specs=[vspec,
                  pl.BlockSpec((WORDS,), lambda i: (i,),
                               memory_space=pltpu.SMEM)],
        out_specs=[vspec, pl.BlockSpec((None, 1, 1), lambda i: (i, 0, 0))],
        out_shape=[
            jax.ShapeDtypeStruct((n, ARRAY_CAP // 128, 128), jnp.int32),
            jax.ShapeDtypeStruct((n, 1, 1), jnp.int32),
        ],
        interpret=interpret,
    )(v.reshape(n, ARRAY_CAP // 128, 128),
      jax.lax.bitcast_convert_type(words.astype(jnp.uint32),
                                   jnp.int32).reshape(-1))
    return mask.reshape(n, ARRAY_CAP), count[:, 0, 0]
