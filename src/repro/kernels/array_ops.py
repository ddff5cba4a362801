"""Vectorized sorted-array intersection (paper section 4.2) as a Pallas TPU
kernel: the pcmpistrm analogue.

The paper divides both arrays into blocks and uses the SSE4.1 string-compare
instruction for an all-vs-all equality test between two blocks.  The TPU
analogue of that all-vs-all compare broadcasts one array's values, one at
a time from SMEM, against the other array held as a dense (32, 128) vector
of 4096 slots: each step compares one value against every slot at once.
(An outer-product compare would need the values on both the lane and the
sublane axis, a relayout Mosaic does not lower.)  Invalid slots (past a
container's cardinality) hold sentinels that can never match, so the
kernels need no cardinality inputs.

Output is A-side: a 0/1 membership mask over A's slots plus the intersection
cardinality.  Difference (section 4.4) is the complement of this mask on
valid slots (the paper builds the difference by OR-accumulating intersection
masks and negating).  Union / symmetric difference (sections 4.3/4.5) use the
merge + dedup oracles in ref.py, or the bitset-domain plan in core.tensor.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import ARRAY_CAP, CONTAINER_BITS

_SUB, _LANES = ARRAY_CAP // 128, 128      # one array slab as (32, 128)

_VEC = pl.BlockSpec((None, _SUB, _LANES), lambda i: (i, 0, 0))
_SCALARS = pl.BlockSpec((ARRAY_CAP,), lambda i: (i,),
                        memory_space=pltpu.SMEM)
_COUNT = pl.BlockSpec((None, 1, 1), lambda i: (i, 0, 0))


def mask_invalid(vals, card, fill):
    """(N, ARRAY_CAP) int32 values with slots >= card set to ``fill``."""
    pos = jnp.arange(ARRAY_CAP, dtype=jnp.int32)[None, :]
    return jnp.where(pos < card.astype(jnp.int32)[:, None],
                     vals.astype(jnp.int32), np.int32(fill))


def _hits(vec, scalars_ref):
    """0/1 mask over ``vec``'s slots: the slot's value equals one of the
    4096 values in ``scalars_ref``."""
    def step(j, hit):
        return hit | (vec == scalars_ref[j]).astype(jnp.int32)
    return jax.lax.fori_loop(0, ARRAY_CAP, step,
                             jnp.zeros(vec.shape, jnp.int32))


def sum_to_cell(x):
    """Sum of a 2-D block as a (1, 1) block: the TPU stores vectors to
    VMEM, not scalars."""
    return jnp.sum(jnp.sum(x, axis=0, keepdims=True), axis=1,
                   keepdims=True)


def _intersect_kernel(a_ref, b_smem, mask_ref, count_ref):
    mask = _hits(a_ref[...], b_smem)
    mask_ref[...] = mask
    count_ref[...] = sum_to_cell(mask)


def _pair_masks_kernel(a_ref, b_smem, b_ref, a_smem, mask_a_ref, mask_b_ref,
                       count_ref):
    """Two-sided variant of ``_intersect_kernel``: the same compare run
    from B's side marks which B slots matched, so one dispatch feeds every
    materializing array-array op (AND keeps A's hits, ANDNOT drops them,
    OR appends B's misses, XOR keeps both sides' misses -- sections
    4.2-4.5)."""
    mask_a = _hits(a_ref[...], b_smem)
    mask_a_ref[...] = mask_a
    mask_b_ref[...] = _hits(b_ref[...], a_smem)
    count_ref[...] = sum_to_cell(mask_a)


def _intersect_card_kernel(a_ref, b_smem, count_ref):
    """Count-only intersection (paper section 5.9 applied to the section
    4.2 compare): the membership mask never leaves registers."""
    count_ref[...] = sum_to_cell(_hits(a_ref[...], b_smem))


def _sides(a_vals, a_card, b_vals, b_card):
    """A and B with distinct never-matching sentinels in invalid slots."""
    return (mask_invalid(a_vals, a_card, CONTAINER_BITS),
            mask_invalid(b_vals, b_card, CONTAINER_BITS + 1))


def _vec(x):
    return x.reshape(x.shape[0], _SUB, _LANES)


def _call(kernel, n, in_specs, n_masks, interpret):
    mask = jax.ShapeDtypeStruct((n, _SUB, _LANES), jnp.int32)
    return pl.pallas_call(
        kernel,
        grid=(n,),
        in_specs=in_specs,
        out_specs=[_VEC] * n_masks + [_COUNT],
        out_shape=[mask] * n_masks
        + [jax.ShapeDtypeStruct((n, 1, 1), jnp.int32)],
        interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=("interpret",))
def array_intersect(a_vals: jax.Array, a_card: jax.Array,
                    b_vals: jax.Array, b_card: jax.Array, *,
                    interpret: bool | None = None) -> tuple[jax.Array, jax.Array]:
    """Batched sorted-array intersection.

    a_vals/b_vals: (N, ARRAY_CAP) int32 (sorted; slots >= card ignored)
    returns: (mask (N, ARRAY_CAP) int32 over A's slots, count (N,) int32)
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n = a_vals.shape[0]
    a, b = _sides(a_vals, a_card, b_vals, b_card)
    mask, count = _call(_intersect_kernel, n, [_VEC, _SCALARS], 1,
                        interpret)(_vec(a), b.reshape(-1))
    return mask.reshape(n, ARRAY_CAP), count[:, 0, 0]


def array_difference(a_vals, a_card, b_vals, b_card, *, interpret=None):
    """Section 4.4: A \\ B = valid slots of A minus the intersection mask."""
    mask, inter = array_intersect(a_vals, a_card, b_vals, b_card,
                                  interpret=interpret)
    valid = (jnp.arange(ARRAY_CAP)[None, :] < a_card[:, None]).astype(jnp.int32)
    keep = valid * (1 - mask)
    return keep, (a_card.astype(jnp.int32) - inter)


@functools.partial(jax.jit, static_argnames=("interpret",))
def array_pair_masks(a_vals: jax.Array, a_card: jax.Array,
                     b_vals: jax.Array, b_card: jax.Array, *,
                     interpret: bool | None = None
                     ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Batched two-sided sorted-array intersection masks.

    a_vals/b_vals: (N, ARRAY_CAP) int32 (sorted; slots >= card ignored)
    returns: (mask_a (N, ARRAY_CAP), mask_b (N, ARRAY_CAP), count (N,))
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n = a_vals.shape[0]
    a, b = _sides(a_vals, a_card, b_vals, b_card)
    mask_a, mask_b, count = _call(
        _pair_masks_kernel, n, [_VEC, _SCALARS, _VEC, _SCALARS], 2,
        interpret)(_vec(a), b.reshape(-1), _vec(b), a.reshape(-1))
    return (mask_a.reshape(n, ARRAY_CAP), mask_b.reshape(n, ARRAY_CAP),
            count[:, 0, 0])


@functools.partial(jax.jit, static_argnames=("interpret",))
def array_intersect_card(a_vals: jax.Array, a_card: jax.Array,
                         b_vals: jax.Array, b_card: jax.Array, *,
                         interpret: bool | None = None) -> jax.Array:
    """Batched count-only sorted-array intersection: (N,) int32 counts."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n = a_vals.shape[0]
    a, b = _sides(a_vals, a_card, b_vals, b_card)
    (count,) = _call(_intersect_card_kernel, n, [_VEC, _SCALARS], 0,
                     interpret)(_vec(a), b.reshape(-1))
    return count[:, 0, 0]
