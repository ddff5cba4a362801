"""The plain reference against the per-candidate loop it replaces, and
the configuration's generator at small sizes: exact sizes, sorted sets,
the same work for every seed."""

import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import harness  # noqa: E402
import reference  # noqa: E402

ROOT = Path(__file__).resolve().parents[3]


def _sets(rng, n, universe, max_size):
    names, parts = [], []
    for i in range(n):
        size = int(rng.integers(1, max_size))
        parts.append(np.unique(rng.integers(0, universe, size))
                     .astype(np.uint32))
        names.append(f"s{i}")
    starts = np.zeros(n + 1, np.int64)
    starts[1:] = np.cumsum([p.size for p in parts])
    return reference.SetIndex(names, starts, np.concatenate(parts), universe)


def loop_similar(sets, i, k):
    """The per-candidate loop (float32 scores, ties to the lower index,
    the query itself excluded) that ``Jaccard`` vectorises."""
    q = sets.get(i)
    score = np.empty(len(sets), np.float32)
    for j in range(len(sets)):
        o = sets.get(j)
        inter = np.float32(np.intersect1d(q, o, assume_unique=True).size)
        denom = np.float32(q.size) + np.float32(o.size) - inter
        score[j] = inter / denom if denom > 0 else np.float32(1.0)
    score[i] = -1.0
    order = np.argsort(-score, kind="stable")[:k]
    return [(sets.names[j], float(score[j])) for j in order]


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_jaccard_matches_the_loop(seed):
    rng = np.random.default_rng(seed)
    # a small universe makes many equal scores, so ties are exercised
    sets = _sets(rng, 300, 64 if seed == 2 else 4096, 40)
    ref = reference.Jaccard(sets)
    for i in rng.choice(len(sets), 20, replace=False):
        assert ref.topk(int(i), 10) == loop_similar(sets, int(i), 10)


def _netflix(n_sets=2000, mean=60):
    spec = harness.resolve(harness.load_manifest(ROOT),
                           "netflix.similar.closed8")
    cfg = dict(spec["config"], n_sets=n_sets, n_values=n_sets * mean)
    return spec["generator"], cfg


def test_sizes_are_exact_and_capped():
    gen, cfg = _netflix()
    sizes = gen._sizes(cfg, np.random.default_rng(0))
    assert sizes.sum() == cfg["n_values"]
    assert sizes.min() >= 1 and sizes.max() <= cfg["universe"]


@pytest.mark.parametrize("n_sets,mean", [(2000, 60), (500, 2000)])
def test_generated_sets_are_sorted_and_near_their_sizes(n_sets, mean):
    gen, cfg = _netflix(n_sets, mean)
    sets = gen.generate(cfg, np.random.default_rng(1))
    assert len(sets) == n_sets
    v = sets.values.astype(np.int64)
    own = np.repeat(np.arange(n_sets), sets.sizes())
    assert np.all((v[1:] > v[:-1]) | (own[1:] != own[:-1]))
    assert v.min() >= 0 and v.max() < cfg["universe"]
    assert abs(sets.sizes().sum() / cfg["n_values"] - 1) < 0.02


def test_every_seed_holds_the_same_sets_in_another_order():
    gen, cfg = _netflix()
    a = gen.generate(cfg, np.random.default_rng(7))
    b = gen.generate(cfg, np.random.default_rng(7))
    c = gen.generate(cfg, np.random.default_rng(2**63 + 11))
    assert np.array_equal(a.values, b.values)
    assert not np.array_equal(a.sizes(), c.sizes())
    assert sorted(map(bytes, (a.get(i).tobytes() for i in range(len(a))))) \
        == sorted(map(bytes, (c.get(i).tobytes() for i in range(len(c)))))

