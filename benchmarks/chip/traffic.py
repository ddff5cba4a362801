"""The one traffic generator: turns a traffic file into queries.

A traffic file (``traffic/<name>.json``) is data only:

* ``loop``: ``"closed"``: ``clients`` callers, each sending its next
  query as soon as its previous one resolved.
* ``server``: keyword settings of the ``QueryServer`` the cell runs,
  such as ``max_batch`` (queries coalesced into one tick); optional.
* ``mix``: one entry ``{"kind": "similar", "share": 1, "k", "metric"}``:
  each query asks for the ``k`` candidates most similar to one
  candidate by ``metric``.

Candidates are picked uniformly without replacement, in an order drawn
from the run's seed; the warm-up draws its own queries from another
stream of the same seed, so the window meets queries it has not served.
"""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
WINDOW, WARM_UP = 3, 4          # the streams of one seed


def load(name: str, base: Path = HERE) -> dict:
    tr = json.loads((base / "traffic" / f"{name}.json").read_text())
    if tr["loop"] != "closed" or [m["kind"] for m in tr["mix"]] != [
            "similar"]:
        raise ValueError(f"traffic {name!r}: only a closed loop of "
                         "similar queries is generated")
    return tr


def stream_rng(seed: int, stream: int) -> np.random.Generator:
    """An independent generator per (seed, purpose); any whole seed."""
    return np.random.default_rng([int(seed) % (1 << 64), stream])


def similar_queries(traffic: dict, names: list, seed: int, stream: int):
    """Endless ``similar`` queries: each pass over the candidates picks
    every one once, in an order drawn from ``seed``."""
    (m,) = traffic["mix"]
    rng = stream_rng(seed, stream)
    while True:
        for i in rng.permutation(len(names)):
            yield {"kind": "similar", "terms": (names[i],), "k": m["k"],
                   "metric": m["metric"]}
