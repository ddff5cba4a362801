"""The plain reference: numpy set algebra over the ids the seed made.

It imports nothing of the program and takes nothing the program made:
the sets come from a configuration's generator, the queries from the
traffic generator, and the answers are worked out here from first
principles -- exact float32 Jaccard scores for the top-k.  ``correct``
compares the served answers with these.
"""

from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class SetIndex:
    """Named sets of ids in one CSR layout: set ``i`` is
    ``values[starts[i]:starts[i + 1]]``, sorted and unique."""
    names: list
    starts: np.ndarray          # (n + 1,) int64
    values: np.ndarray          # concatenated ids
    universe: int               # ids lie in [0, universe)

    def __post_init__(self):
        self.pos = {name: i for i, name in enumerate(self.names)}

    def __len__(self) -> int:
        return len(self.names)

    def get(self, i: int) -> np.ndarray:
        return self.values[self.starts[i]:self.starts[i + 1]]

    def sizes(self) -> np.ndarray:
        return np.diff(self.starts)


class Jaccard:
    """Exact top-k by Jaccard over every candidate of ``sets``.

    The sets are turned around once into a list of candidates per id;
    a query's intersection sizes with every candidate are then one
    ``bincount`` over the lists of its ids.  Scores are float32
    ``inter / union`` (numpy's float32 division is correctly rounded),
    the query itself scores -1, and ties go to the lower candidate
    index."""

    def __init__(self, sets: SetIndex):
        self.sets = sets
        self.sizes = sets.sizes()
        owner = np.repeat(np.arange(len(sets), dtype=np.int32), self.sizes)
        order = np.argsort(sets.values, kind="stable")
        self.holders = owner[order]
        self.bounds = np.searchsorted(sets.values[order],
                                      np.arange(sets.universe + 1))

    def scores(self, i: int, divide=np.divide) -> np.ndarray:
        q = self.sets.get(i).astype(np.int64)
        lists = [self.holders[self.bounds[v]:self.bounds[v + 1]] for v in q]
        inter = np.bincount(np.concatenate(lists) if lists
                            else np.zeros(0, np.int32),
                            minlength=len(self.sets))
        union = self.sizes + q.size - inter
        score = divide(inter.astype(np.float32), union.astype(np.float32))
        score = np.array(score, np.float32)
        score[i] = np.float32(-1.0)
        return score

    def topk(self, i: int, k: int, divide=np.divide) -> list:
        score = self.scores(i, divide)
        k = min(k, score.size - 1)
        kth = np.partition(score, score.size - k)[score.size - k]
        sel = np.flatnonzero(score >= kth)
        best = sel[np.lexsort((sel, -score[sel]))][:k]
        return [(self.sets.names[j], float(score[j])) for j in best]
