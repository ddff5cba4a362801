"""The ``ssb_sf1`` configuration and the ``ssb`` kind at the CPU size:
the reference over the tables against set algebra over the program's
bitmaps, each template's published clause widths, the control caught,
and the generator refusing a program without the ``and_of_or`` kind
before it builds anything."""

import functools
import itertools
import json
import sys
import time
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import harness  # noqa: E402
import traffic  # noqa: E402

SEED = 2**31 + 977


@pytest.fixture(scope="module")
def config():
    cfg = json.loads((HERE / "configs" / "ssb_sf1.json").read_text())
    return dict(cfg, **cfg["tiny"])


@pytest.fixture(scope="module")
def gen():
    return harness._module(HERE / "configs" / "ssb_sf1.py", "config_ssb_sf1")


@pytest.fixture(scope="module")
def kind():
    return harness._module(HERE / "kinds" / "ssb.py", "kind_ssb")


@pytest.fixture(scope="module")
def built(config, gen):
    data = gen.generate(config, traffic.stream_rng(SEED, 0))
    return data, gen.postings(data)


def _queries(kind, data, n, stream=traffic.WINDOW):
    return list(itertools.islice(
        kind.queries({"kind": "ssb", "share": 1}, data,
                     traffic.stream_rng(SEED, stream)), n))


def test_tiny_is_four_chunks_of_every_indexed_value(built, config):
    data, postings = built
    assert (data.universe - 1) >> 16 == 3
    attrs = {name.split("=")[0] for name in postings}
    assert attrs == set(config["indexed"])
    assert len(data) == len(postings) == data.sizes().size
    assert int(data.sizes().sum()) == data.universe * len(attrs)


def test_the_reference_matches_set_algebra_over_the_bitmaps(built, kind):
    data, postings = built
    ref = kind.reference(data)
    for q in _queries(kind, data, 60):
        clauses = kind.to_query(q).terms
        want = functools.reduce(np.intersect1d, [
            functools.reduce(np.union1d, [
                postings[t].to_array().astype(np.int64) if t in postings
                else np.zeros(0, np.int64) for t in clause])
            for clause in clauses])
        assert np.array_equal(kind.expected(ref, q), want), q["template"]


def test_every_template_keeps_its_published_clause_widths(built, kind):
    data, postings = built
    seen = set()
    for q in _queries(kind, data, 1500):
        seen.add(q["template"])
        widths = tuple(len(c) for c in q["clauses"])
        assert widths == kind.WIDTHS[q["template"]], q
        for clause in q["clauses"]:
            assert len({attr for attr, _ in clause}) == 1
            assert len({value for _, value in clause}) == len(clause)
            assert all(v in data.values[a] for a, v in clause)
    assert seen == set(kind.TEMPLATES) and len(seen) == 13


def test_the_control_is_caught(built, kind):
    """The control answers each query without its last clause: a
    superset of the answer, and another answer wherever the clause
    selects (at this size some answers are empty either way)."""
    data, _ = built
    ref = kind.reference(data)
    caught = 0
    for q in _queries(kind, data, 100):
        got, want = kind.control(ref, q), kind.expected(ref, q)
        assert np.isin(want, got).all()
        caught += not kind.same(got, want)
    assert caught >= 80


def test_the_generator_fails_fast_without_and_of_or(config, gen,
                                                     monkeypatch):
    from repro.serve import query_server
    monkeypatch.setattr(query_server, "BOOLEAN_KINDS", tuple(
        k for k in query_server.BOOLEAN_KINDS if k != "and_of_or"))
    t0 = time.monotonic()
    with pytest.raises(RuntimeError, match="and_of_or"):
        gen.generate(dict(config, orders=10**9), None)
    assert time.monotonic() - t0 < 1.0
