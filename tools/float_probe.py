#!/usr/bin/env python3
"""How far the default backend's float32 division and square root are
from IEEE float32, on the operands the similarity scores feed them.

    python tools/float_probe.py [--n 4194304] [--seed 0]

For three operand ranges (log-uniform integer cardinalities below 2^12,
2^24 and 2^31, rounded to float32) it draws ``--n`` seeded pairs ``a <= b``
and counts, against numpy's correctly rounded float32:

* ``div`` / ``sqrt``: the backend's own ``a / b`` and ``sqrt(a * b)``
  (on a TPU these are approximations);
* ``div_rn`` / ``sqrt_rn``: ``repro.kernels.ref``'s integer routines,
  which the score formula uses and which must match everywhere.

It also counts the distinct values of ``k / 3k`` for k < 2^16 (IEEE gives
one).  Prints one JSON object; exits non-zero if ``div_rn`` or ``sqrt_rn``
missed anywhere.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

RANGES = {"below_2^12": 12, "below_2^24": 24, "below_2^31": 31}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--n", type=int, default=1 << 22)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from repro.kernels import ref

    div = jax.jit(lambda a, b: a / b)
    sqrt = jax.jit(jnp.sqrt)
    div_rn = jax.jit(ref.div_rn)
    sqrt_rn = jax.jit(ref.sqrt_rn)

    def misses(got, want) -> int:
        return int((np.asarray(got).view(np.uint32)
                    != want.view(np.uint32)).sum())

    rng = np.random.default_rng(args.seed)
    dev = jax.devices()[0]
    out = {"device": f"{dev.platform} {dev.device_kind}", "n": args.n}
    for name, bits in RANGES.items():
        x, y = (np.floor(2.0 ** rng.uniform(0, bits, args.n))
                .astype(np.float32) for _ in range(2))
        a, b = np.minimum(x, y), np.maximum(x, y)
        prod = x * y
        out[name] = {"div": misses(div(a, b), a / b),
                     "sqrt": misses(sqrt(prod), np.sqrt(prod)),
                     "div_rn": misses(div_rn(a, b), a / b),
                     "sqrt_rn": misses(sqrt_rn(prod), np.sqrt(prod))}
    k = np.arange(1, 1 << 16, dtype=np.float32)
    out["k/3k distinct"] = {"div": int(np.unique(np.asarray(div(k, 3 * k))).size),
                            "div_rn": int(np.unique(
                                np.asarray(div_rn(k, 3 * k))).size)}
    print(json.dumps(out), flush=True)
    bad = any(out[name][f] for name in RANGES for f in ("div_rn", "sqrt_rn"))
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
