"""Data of ``dedup_netflix``: a twin of the NETFLIX dataset of Mann,
Augsten and Bouros (PVLDB 2016), one set of movie ids per user.

The count of sets, the universe and the total of values are the
source's; the size law and the movie popularity law are assumed (see the
configuration's ``assumed``).  Every set lies in one 2^16 chunk, so each
candidate is exactly one container row.

The sets are drawn once from ``data_seed``, in bulk and without a sort:
set ``u`` takes ``d_u`` draws, generated already in order as the order
statistics of uniforms (partial sums of exponentials), mapped through
the popularity law's inverse distribution function, and repeats are
dropped.  ``d_u`` is chosen so that the expected count of distinct
movies is the set's drawn size.  A run's seed then permutes the users,
which changes every query and tie but not the work.
"""

from __future__ import annotations

import numpy as np

from reference import SetIndex

GRID = 512                    # points of the draws-to-distinct curve


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % (1 << 64), stream])


def _sizes(cfg: dict, rng: np.random.Generator) -> np.ndarray:
    """Lognormal set sizes in [1, universe] summing to ``n_values``."""
    n, universe, total = cfg["n_sets"], cfg["universe"], cfg["n_values"]
    x = rng.lognormal(0.0, cfg["size_sigma"], n)
    s = np.clip(np.floor(x * (total / x.sum())), 1, universe).astype(np.int64)
    # what rounding and the cap left over goes one value at a time to
    # sets drawn at random that have room for it
    while (short := int(total - s.sum())) != 0:
        room = np.flatnonzero(s < universe if short > 0 else s > 1)
        pick = rng.choice(room, min(abs(short), room.size), replace=False)
        s[pick] += 1 if short > 0 else -1
    return s


def _law(cfg: dict):
    """The popularity law over movies 0..universe-1: movie ``m`` is
    ``floor(x) - 1`` for ``x`` drawn with density ``x ** -a`` on
    ``[1, universe + 1)``.  Returns its inverse distribution function
    and the probability of each movie."""
    a, universe = cfg["popularity_exponent"], cfg["universe"]
    if not 0 <= a < 1:
        raise ValueError("popularity_exponent must lie in [0, 1)")
    b = 1.0 - a
    top = (universe + 1.0) ** b - 1.0

    def inverse(u):
        m = np.floor((1.0 + u * top) ** (1.0 / b)) - 1.0
        return np.clip(m, 0, universe - 1).astype(np.int64)

    edges = (np.arange(1, universe + 2, dtype=np.float64) ** b - 1.0) / top
    return inverse, np.diff(edges)


def _draws(sizes: np.ndarray, p: np.ndarray) -> np.ndarray:
    """Draws with repeats whose expected count of distinct movies is each
    set's size (the largest sets get the grid's last point)."""
    grid = np.unique(np.geomspace(1, 64 * p.size, GRID).astype(np.int64))
    log_miss = np.log1p(-p)
    distinct = np.array([p.size - np.exp(d * log_miss).sum() for d in grid])
    return np.maximum(1, np.rint(np.interp(sizes, distinct, grid))
                      ).astype(np.int64)


def _base(cfg: dict) -> SetIndex:
    rng = _rng(cfg["data_seed"], 0)
    n = int(cfg["n_sets"])
    inverse, p = _law(cfg)
    d = _draws(_sizes(cfg, rng), p)
    # d + 1 exponentials per set: the first d partial sums over the last
    # are d sorted uniforms
    seg = d + 1
    c = np.cumsum(rng.standard_exponential(int(seg.sum())))
    ends = np.cumsum(seg) - 1
    before = np.concatenate([[0.0], c[ends[:-1]]])
    keep = np.ones(c.size, bool)
    keep[ends] = False
    owner = np.repeat(np.arange(n, dtype=np.int64), d)
    u = (c[keep] - before[owner]) / (c[ends] - before)[owner]
    del c, keep
    movie = inverse(u)
    del u
    new = np.ones(movie.size, bool)
    new[1:] = (movie[1:] != movie[:-1]) | (owner[1:] != owner[:-1])
    starts = np.zeros(n + 1, np.int64)
    starts[1:] = np.cumsum(np.bincount(owner[new], minlength=n))
    return SetIndex([], starts, movie[new].astype(np.uint16),
                    int(cfg["universe"]))


def generate(cfg: dict, rng: np.random.Generator) -> SetIndex:
    """The sets of ``data_seed``, with the users in an order drawn from
    the run's ``rng``."""
    base = _base(cfg)
    n = len(base.starts) - 1
    perm = rng.permutation(n)
    sizes = np.diff(base.starts)[perm]
    starts = np.zeros(n + 1, np.int64)
    starts[1:] = np.cumsum(sizes)
    take = (np.repeat(base.starts[:-1][perm] - starts[:-1], sizes)
            + np.arange(starts[-1]))
    return SetIndex([f"u{i}" for i in range(n)], starts, base.values[take],
                    base.universe)


def postings(sets: SetIndex) -> dict:
    """The program's candidates: one single-container RoaringBitmap each."""
    from repro.core import RoaringBitmap
    from repro.core.containers import container_from_values
    vals, starts = sets.values, sets.starts
    return {name: RoaringBitmap(
        [0], [container_from_values(vals[starts[i]:starts[i + 1]])])
        for i, name in enumerate(sets.names)}
