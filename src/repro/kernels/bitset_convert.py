"""Array-container <-> bitset-container conversion kernels (paper sections
3.1 / 3.2), adapted for TPU.

section 3.2 (x64): set bits of a bitset at indexes given by an array, with
branchless cardinality tracking (`bts` + `sbb`, or the XOR trick).  TPU has
no scatter inside a kernel: the values stream from SMEM one at a time and
each ORs its bit into the word lane that matches, across the whole
(16, 128) container at once.  The cardinality delta uses exactly the
paper's XOR trick: popcount(old ^ new).

section 3.1 (bitset -> array extraction, blsi/tzcnt loop): the TPU idiom is a
prefix sum over bit occupancy; it needs a 65536-long cumsum and binary
search, which XLA already fuses well outside a kernel -- see
`repro.kernels.ref.bitset_to_array` (used directly by ops.py; it is the
repack path, not the hot loop).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.array_ops import mask_invalid, sum_to_cell
from repro.kernels.harley_seal import _popcount_u32
from repro.kernels.ref import ARRAY_CAP, CONTAINER_BITS, WORDS

_SUB, _LANES = WORDS // 128, 128      # one container as (16, 128) words


def _a2b_body(vals_smem):
    """ARRAY_CAP values in SMEM (invalid slots hold a sentinel past the
    last word) -> (16, 128) uint32 bitset words."""
    wid = (jax.lax.broadcasted_iota(jnp.int32, (_SUB, _LANES), 0) * _LANES
           + jax.lax.broadcasted_iota(jnp.int32, (_SUB, _LANES), 1))

    def step(j, acc):
        v = vals_smem[j]
        bit = jnp.uint32(1) << (v & 31).astype(jnp.uint32)
        return acc | jnp.where(wid == (v >> 5), bit, jnp.uint32(0))

    return jax.lax.fori_loop(0, ARRAY_CAP, step,
                             jnp.zeros((_SUB, _LANES), jnp.uint32))


def _a2b_kernel(vals_smem, words_ref):
    words_ref[...] = _a2b_body(vals_smem)


def _set_many_kernel(init_ref, vals_smem, words_ref, delta_ref):
    """Fused section 3.2: new = old | onehot(values); delta = pc(old ^ new)."""
    old = init_ref[...]
    new = old | _a2b_body(vals_smem)
    words_ref[...] = new
    delta_ref[...] = sum_to_cell(_popcount_u32(old ^ new))


_WORDS_SPEC = pl.BlockSpec((None, _SUB, _LANES), lambda i: (i, 0, 0))
_VALS_SPEC = pl.BlockSpec((ARRAY_CAP,), lambda i: (i,),
                          memory_space=pltpu.SMEM)


def _sentinel_values(values, card):
    return mask_invalid(values, card, CONTAINER_BITS).reshape(-1)


@functools.partial(jax.jit, static_argnames=("interpret",))
def array_to_bitset(values: jax.Array, card: jax.Array, *,
                    interpret: bool | None = None) -> jax.Array:
    """(N, ARRAY_CAP) int32 sorted values, (N,) cards -> (N, WORDS) uint32."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n = values.shape[0]
    return pl.pallas_call(
        _a2b_kernel,
        grid=(n,),
        in_specs=[_VALS_SPEC],
        out_specs=_WORDS_SPEC,
        out_shape=jax.ShapeDtypeStruct((n, _SUB, _LANES), jnp.uint32),
        interpret=interpret,
    )(_sentinel_values(values, card)).reshape(n, WORDS)


@functools.partial(jax.jit, static_argnames=("interpret",))
def bitset_set_many(words: jax.Array, values: jax.Array, card: jax.Array, *,
                    interpret: bool | None = None) -> tuple[jax.Array, jax.Array]:
    """OR an array container into a bitset container, returning
    (new words (N, WORDS), cardinality delta (N,)) -- section 3.2 fused."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n = words.shape[0]
    new, delta = pl.pallas_call(
        _set_many_kernel,
        grid=(n,),
        in_specs=[_WORDS_SPEC, _VALS_SPEC],
        out_specs=[_WORDS_SPEC, pl.BlockSpec((None, 1, 1),
                                             lambda i: (i, 0, 0))],
        out_shape=[
            jax.ShapeDtypeStruct((n, _SUB, _LANES), jnp.uint32),
            jax.ShapeDtypeStruct((n, 1, 1), jnp.int32),
        ],
        interpret=interpret,
    )(words.astype(jnp.uint32).reshape(n, _SUB, _LANES),
      _sentinel_values(values, card))
    return new.reshape(n, WORDS), delta[:, 0, 0]
