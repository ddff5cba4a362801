"""Boolean aggregates over 2 to ``arity`` of the configuration's sets:
the program's flat kinds ``and`` and ``or``.

Mix entry: ``{"kind": "bool", "share": 1, "op", "arity"}``.  Each query
takes a count of sets drawn uniformly from 2 to ``arity``, and that many
distinct sets, drawn uniformly.  The reference is numpy set algebra over
the configuration's sets; a served ``RoaringBitmap`` is right when its
sorted values equal the reference's.  The control, since the system
states no precision here, breaks the exactness it does state: the
reference's answer without its largest value.
"""

from __future__ import annotations

import functools

import numpy as np

OPS = {"and": np.intersect1d, "or": np.union1d}


def queries(entry: dict, sets, rng):
    op, arity = entry["op"], int(entry["arity"])
    if op not in OPS:
        raise ValueError(f"bool op {op!r} is not one of {sorted(OPS)}")
    if not 2 <= arity <= len(sets):
        raise ValueError(f"bool entry {entry}: need 2 <= arity <= the sets")
    names = sets.names
    while True:
        m = int(rng.integers(2, arity + 1))
        idx = rng.choice(len(names), m, replace=False)
        yield {"kind": "bool", "op": op,
               "terms": tuple(names[i] for i in idx)}


def to_query(q: dict):
    from repro.serve import Query
    return Query(q["op"], q["terms"])


def reference(sets):
    return sets


def expected(sets, q: dict) -> np.ndarray:
    return functools.reduce(OPS[q["op"]], [
        sets.get(sets.pos[name]).astype(np.int64) for name in q["terms"]])


def control(sets, q: dict) -> np.ndarray:
    return expected(sets, q)[:-1]


def same(got, want) -> bool:
    """``got``: a served ``RoaringBitmap``, or the control's values."""
    if hasattr(got, "to_array"):
        got = got.to_array()
    return np.array_equal(np.asarray(got, np.int64), want)
