"""Similarity top-k queries: each asks for the ``k`` candidates most
similar to one candidate by ``metric``.

Mix entry: ``{"kind": "similar", "share": 1, "k", "metric"}``.
Candidates are picked uniformly without replacement, in an order drawn
from the stream: each pass over the candidates picks every one once.
The reference is ``reference.Jaccard`` (exact float32 scores, the query
itself left out, ties to the lower index); a served answer is right when
its list of ``(name, score)`` equals the reference's.  The control is
the same reference one precision below the float32 the configuration
states: its scores divided in bfloat16 on the default device (the chip,
when run there).
"""

from __future__ import annotations

import numpy as np

from reference import Jaccard


def queries(entry: dict, sets, rng):
    names = sets.names
    while True:
        for i in rng.permutation(len(names)):
            yield {"kind": "similar", "terms": (names[i],), "k": entry["k"],
                   "metric": entry["metric"]}


def to_query(q: dict):
    from repro.serve import Query
    return Query.similar(q["terms"][0], q["k"], q["metric"])


def reference(sets) -> Jaccard:
    return Jaccard(sets)


def expected(ref: Jaccard, q: dict, divide=np.divide) -> list:
    if q["metric"] != "jaccard":
        raise ValueError(f"no reference for metric {q['metric']!r}")
    return ref.topk(ref.sets.pos[q["terms"][0]], q["k"], divide)


def bf16_divide(a, b):
    import jax.numpy as jnp
    q = jnp.asarray(a, jnp.bfloat16) / jnp.asarray(b, jnp.bfloat16)
    return np.asarray(q.astype(jnp.float32))


def control(ref: Jaccard, q: dict) -> list:
    return expected(ref, q, bf16_divide)


def same(got, want) -> bool:
    return got == want
