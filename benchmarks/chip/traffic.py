"""The one traffic generator: turns a traffic file into queries and the
times they are due.

A traffic file (``traffic/<name>.json``) is data only:

* ``loop``: ``"closed"``: ``clients`` callers, each sending its next
  query as soon as its previous one resolved; or ``"open"``: queries due
  at Poisson arrivals of ``rate`` queries per second, sent whether or
  not earlier ones resolved.
* ``server``: keyword settings of the ``QueryServer`` the cell runs,
  such as ``max_batch`` (queries coalesced into one tick); optional.
* ``mix``: one entry ``{"kind": <kind>, "share": 1, ...}``.  The module
  ``kinds/<kind>.py`` makes the entry's queries from its other keys,
  turns them into the program's ``Query`` and holds their plain
  reference and control (``kinds/similar.py``, ``kinds/bool.py``).

Streams of one seed: 0 orders the configuration's data, 3 gives the
window's queries, 4 the warm-up's, 5 the check's sample, 6 and 7 the
window's and the warm-up's open-loop arrivals.  The warm-up's queries
come from another stream than the window's, so the window meets queries
it has not served.
"""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
WINDOW, WARM_UP = 3, 4          # the query streams of one seed
ARRIVALS = {WINDOW: 6, WARM_UP: 7}
LOOPS = ("closed", "open")
BLOCK = 1024                    # draws per refill of an endless stream


def load(name: str, base: Path = HERE) -> dict:
    tr = json.loads((base / "traffic" / f"{name}.json").read_text())
    loop = tr.get("loop")
    if loop not in LOOPS:
        raise ValueError(f"traffic {name!r}: loop {loop!r} is not one of "
                         f"{LOOPS}")
    if loop == "closed" and int(tr.get("clients", 0)) < 1:
        raise ValueError(f"traffic {name!r}: a closed loop needs clients")
    if loop == "open" and not float(tr.get("rate", 0)) > 0:
        raise ValueError(f"traffic {name!r}: an open loop needs a rate")
    if len(tr.get("mix", ())) != 1:
        raise ValueError(f"traffic {name!r}: the mix is one entry")
    return tr


def stream_rng(seed: int, stream: int) -> np.random.Generator:
    """An independent generator per (seed, purpose); any whole seed."""
    return np.random.default_rng([int(seed) % (1 << 64), stream])


def queries(traffic: dict, kind, sets, seed: int, stream: int):
    """Endless queries of ``traffic``'s mix over ``sets`` from one stream
    of ``seed``; ``kind`` is the module of the mix entry's kind."""
    (m,) = traffic["mix"]
    return kind.queries(m, sets, stream_rng(seed, stream))


def arrivals(traffic: dict, seed: int, stream: int):
    """Endless due times, in seconds from the loop's start, of an open
    loop's queries: a Poisson process at ``rate``."""
    rate = float(traffic["rate"])
    rng = stream_rng(seed, ARRIVALS[stream])
    due = 0.0
    while True:
        for gap in rng.standard_exponential(BLOCK) / rate:
            due += gap
            yield due
