"""Fused bitset logical op + cardinality (paper section 4.1.2) as Pallas
TPU kernels.

The paper's point: when aggregating two bitset containers you want the
population count of the result computed *in vector registers*, without a
round-trip through memory and the scalar popcnt instruction.  These kernels
do exactly that -- one pass loads both containers into VMEM, computes
AND/OR/XOR/ANDNOT, runs the Harley-Seal circuit on the result while it is
still resident, and writes words + cardinality (or, for the count-only
"fast count" variants of section 5.9, just the cardinality).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.harley_seal import DEFAULT_BLOCK, harley_seal_reduce
from repro.kernels.ref import WORDS

_OPS = ("and", "or", "xor", "andnot")


def _apply(a, b, op: str):
    if op == "and":
        return a & b
    if op == "or":
        return a | b
    if op == "xor":
        return a ^ b
    if op == "andnot":
        return a & ~b
    raise ValueError(op)


def _op_kernel(a_ref, b_ref, out_ref, card_ref, *, op):
    r = _apply(a_ref[...], b_ref[...], op)
    out_ref[...] = r
    card_ref[...] = harley_seal_reduce(r)


def _card_kernel(a_ref, b_ref, card_ref, *, op):
    r = _apply(a_ref[...], b_ref[...], op)
    card_ref[...] = harley_seal_reduce(r)


def _pad(x, block):
    n_pad = (-x.shape[0]) % block
    return jnp.pad(x, ((0, n_pad), (0, 0))) if n_pad else x


@functools.partial(jax.jit, static_argnames=("op", "block", "interpret"))
def bitset_op(a: jax.Array, b: jax.Array, op: str, *,
              block: int = DEFAULT_BLOCK,
              interpret: bool | None = None) -> tuple[jax.Array, jax.Array]:
    """(N, WORDS) x2 uint32 -> (result words (N, WORDS), cardinality (N,))."""
    assert op in _OPS, op
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n = a.shape[0]
    a, b = _pad(a, block), _pad(b, block)
    grid = (a.shape[0] // block,)
    spec = pl.BlockSpec((block, WORDS), lambda i: (i, 0))
    out, card = pl.pallas_call(
        functools.partial(_op_kernel, op=op),
        grid=grid,
        in_specs=[spec, spec],
        out_specs=[spec, pl.BlockSpec((block, 1), lambda i: (i, 0))],
        out_shape=[
            jax.ShapeDtypeStruct((a.shape[0], WORDS), jnp.uint32),
            jax.ShapeDtypeStruct((a.shape[0], 1), jnp.int32),
        ],
        interpret=interpret,
    )(a, b)
    return out[:n], card[:n, 0]


@functools.partial(jax.jit, static_argnames=("op", "block", "interpret"))
def bitset_op_card(a: jax.Array, b: jax.Array, op: str, *,
                   block: int = DEFAULT_BLOCK,
                   interpret: bool | None = None) -> jax.Array:
    """Count-only variant: never materializes the result container in HBM
    (paper section 5.9, e.g. Jaccard index numerators)."""
    assert op in _OPS, op
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n = a.shape[0]
    a, b = _pad(a, block), _pad(b, block)
    grid = (a.shape[0] // block,)
    spec = pl.BlockSpec((block, WORDS), lambda i: (i, 0))
    card = pl.pallas_call(
        functools.partial(_card_kernel, op=op),
        grid=grid,
        in_specs=[spec, spec],
        out_specs=pl.BlockSpec((block, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((a.shape[0], 1), jnp.int32),
        interpret=interpret,
    )(a, b)
    return card[:n, 0]
