"""``ref.div_rn`` and ``ref.sqrt_rn`` against IEEE float32, bit for bit.

Both sides of every ref-vs-Pallas score comparison call these routines,
so only an independent reference can catch an error in them: numpy's
float32 division and square root are correctly rounded.  The operands
are what ``ref.similarity_scores`` feeds them -- integer cardinalities
(exact below 2^24, rounded above) and, for cosine, the float32 product
of two cardinalities, far above 2^24.
"""

from __future__ import annotations

import jax
import numpy as np
import pytest

from repro.kernels import ref

N = 1 << 21             # operand pairs per case


def _cards(rng, hi_bits: int, n: int) -> np.ndarray:
    """Log-uniform integers in [1, 2^hi_bits) as float32 (rounded to the
    float32 grid above 2^24, as the score formula rounds them)."""
    return np.floor(2.0 ** rng.uniform(0, hi_bits, n)).astype(np.float32)


_div = jax.jit(ref.div_rn)
_sqrt = jax.jit(ref.sqrt_rn)


@pytest.mark.parametrize("hi_bits", [12, 24, 31])
def test_div_rn_matches_ieee(hi_bits):
    rng = np.random.default_rng(hi_bits)
    a, b = _cards(rng, hi_bits, N), _cards(rng, hi_bits, N)
    a[: N // 4] = np.minimum(a, b)[: N // 4]       # scores <= 1 ...
    a[N // 4: N // 2] = 0.0                        # ... and exactly 0
    a[N // 2: N // 2 + 1024] = b[N // 2: N // 2 + 1024]   # exactly 1
    got = np.asarray(_div(a, b))
    want = a / b
    assert np.array_equal(got.view(np.uint32), want.view(np.uint32))


def test_div_rn_thirds_are_one_value():
    k = np.arange(1, 1 << 16, dtype=np.float32)
    assert np.unique(np.asarray(_div(k, 3 * k))).size == 1


@pytest.mark.parametrize("hi_bits", [12, 24, 31])
def test_sqrt_rn_matches_ieee(hi_bits):
    rng = np.random.default_rng(100 + hi_bits)
    x = _cards(rng, hi_bits, N) * _cards(rng, hi_bits, N)   # cosine's qc * oc
    x[:1024] = 0.0
    x[1024:2048] = np.square(_cards(rng, min(hi_bits, 12), 1024))  # squares
    got = np.asarray(_sqrt(x))
    want = np.sqrt(x)
    assert np.array_equal(got.view(np.uint32), want.view(np.uint32))


def test_float_probe_tool_runs_clean():
    """tools/float_probe.py (the chip measurement behind these routines)
    runs and finds the routines exact on this backend."""
    import importlib.util
    from pathlib import Path
    path = Path(__file__).resolve().parents[2] / "tools" / "float_probe.py"
    spec = importlib.util.spec_from_file_location("float_probe", path)
    probe = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(probe)
    assert probe.main(["--n", "4096"]) == 0
