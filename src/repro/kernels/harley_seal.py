"""Vectorized Harley-Seal population count (paper section 4.1.1) as a Pallas
TPU kernel.

The paper's AVX2 version streams sixteen 256-bit vectors through a carry-save
adder (CSA) circuit, accumulating into five bit-sliced accumulator vectors
(ones/twos/fours/eights/sixteens) so that the expensive per-byte popcount
runs on 5 vectors instead of 16.  On TPU the VPU register is (8, 128) x 32-bit
= 32768 bits, so one 2^16-bit Roaring bitset container is two vregs; since
WORDS = 2048 = 16 x 128, the 16 CSA circuit inputs are the container's 16
lane-aligned (block, 128) column slices and the identical circuit runs
across lanes (no relayout: Mosaic refuses a (block, 128, 16) reshape).
The op-count saving is the same as the paper's: 15 CSAs x 5 logical ops
per 16 words, then a SWAR popcount of 5 accumulators instead of 16 (TPU
has no vector popcount instruction, which is precisely the situation the
paper's circuit was designed for).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels.ref import WORDS

# numpy scalars stay literals inside Pallas kernel traces
_M1 = np.uint32(0x55555555)
_M2 = np.uint32(0x33333333)
_M4 = np.uint32(0x0F0F0F0F)
_H01 = np.uint32(0x01010101)

DEFAULT_BLOCK = 8  # containers per grid step: 8 x 8 kB = 64 kB of VMEM


def _popcount_u32(v):
    v = v - ((v >> np.uint32(1)) & _M1)
    v = (v & _M2) + ((v >> np.uint32(2)) & _M2)
    v = (v + (v >> np.uint32(4))) & _M4
    return ((v * _H01) >> np.uint32(24)).astype(jnp.int32)


def _csa(a, b, c):
    """Carry-save adder: 3 bits in, (high, low) out -- 5 logical ops."""
    u = a ^ b
    return (a & b) | (u & c), u ^ c


def harley_seal_reduce(x):
    """The 16-input Harley-Seal circuit of the paper's Fig. 3, vectorized.

    x: (bn, WORDS) uint32 rows; circuit input ``i`` is the lane-aligned
    slice ``x[:, 128 i : 128 (i + 1)]``.  Returns (bn, 1) int32 row
    popcounts.
    """
    lanes = WORDS // 16
    A = [x[:, i * lanes:(i + 1) * lanes] for i in range(16)]
    twos_a, ones = _csa(A[0], A[1], jnp.zeros_like(A[0]))
    twos_b, ones = _csa(A[2], A[3], ones)
    fours_a, twos = _csa(twos_a, twos_b, jnp.zeros_like(A[0]))
    twos_a, ones = _csa(A[4], A[5], ones)
    twos_b, ones = _csa(A[6], A[7], ones)
    fours_b, twos = _csa(twos_a, twos_b, twos)
    eights_a, fours = _csa(fours_a, fours_b, jnp.zeros_like(A[0]))
    twos_a, ones = _csa(A[8], A[9], ones)
    twos_b, ones = _csa(A[10], A[11], ones)
    fours_a, twos = _csa(twos_a, twos_b, twos)
    twos_a, ones = _csa(A[12], A[13], ones)
    twos_b, ones = _csa(A[14], A[15], ones)
    fours_b, twos = _csa(twos_a, twos_b, twos)
    eights_b, fours = _csa(fours_a, fours_b, fours)
    sixteens, eights = _csa(eights_a, eights_b, jnp.zeros_like(A[0]))
    total = (16 * _popcount_u32(sixteens)
             + 8 * _popcount_u32(eights)
             + 4 * _popcount_u32(fours)
             + 2 * _popcount_u32(twos)
             + _popcount_u32(ones))
    return jnp.sum(total, axis=1, keepdims=True, dtype=jnp.int32)


def _popcount_kernel(words_ref, out_ref):
    out_ref[...] = harley_seal_reduce(words_ref[...])


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def popcount(words: jax.Array, *, block: int = DEFAULT_BLOCK,
             interpret: bool | None = None) -> jax.Array:
    """(N, WORDS) uint32 bitset containers -> (N,) int32 cardinalities."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n = words.shape[0]
    n_pad = (-n) % block
    if n_pad:
        words = jnp.pad(words, ((0, n_pad), (0, 0)))
    grid = (words.shape[0] // block,)
    out = pl.pallas_call(
        _popcount_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((block, WORDS), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((words.shape[0], 1), jnp.int32),
        interpret=interpret,
    )(words)
    return out[:n, 0]
