"""Query kinds, loops and chip counts as files: the window's and the
warm-up's query streams of the committed cell pinned bit for bit, the
``bool`` kind's draws, and scratch cells of a new configuration, the
``bool`` kind and an open loop -- made only of new files in a copy of
this directory -- run end to end through the harness, on one device and
on four, with a broken answer and the kind's control read as not
correct."""

import hashlib
import itertools
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))

import harness  # noqa: E402
import reference  # noqa: E402
import traffic  # noqa: E402

SEED = 2**31 + 977
# sha256 of the JSON of the first 2,000 queries of streams 3 (window) and
# 4 (warm-up) of ``similar.closed8`` over the 480,189 NETFLIX users for
# SEED, as the generator drew them before query kinds were files
STREAM_DIGESTS = {
    traffic.WINDOW:
        "db07b2d7f794dcee86e1335cdede07551734feae161fb8b83dedcf793c2cdea3",
    traffic.WARM_UP:
        "7edac6dbb497609cbdc097a7b88d1294e1dc995788cc9cedb456afbbdcdeed65",
}
BOOL_OPEN = {"loop": "open", "rate": 200,
             "mix": [{"kind": "bool", "share": 1, "op": "and",
                      "arity": 4}]}
OR_OPEN = {"loop": "open", "rate": 100,
           "mix": [{"kind": "bool", "share": 1, "op": "or", "arity": 64}]}
SIMILAR_CLOSED = {"loop": "closed", "clients": 4,
                  "mix": [{"kind": "similar", "share": 1, "k": 5,
                           "metric": "jaccard"}]}


def _names_only(n):
    return reference.SetIndex([f"u{i}" for i in range(n)],
                              np.zeros(n + 1, np.int64),
                              np.zeros(0, np.uint16), 1)


@pytest.mark.parametrize("stream", [traffic.WINDOW, traffic.WARM_UP])
def test_the_netflix_streams_are_unchanged(stream):
    spec = harness.resolve(harness.load_manifest(ROOT),
                           "netflix.similar.closed8")
    sets = _names_only(spec["config"]["n_sets"])
    got = list(itertools.islice(traffic.queries(
        spec["traffic"], spec["kind"], sets, SEED, stream), 2000))
    digest = hashlib.sha256(json.dumps(got).encode()).hexdigest()
    assert digest == STREAM_DIGESTS[stream]


def _bool_kind():
    return harness._module(HERE / "kinds" / "bool.py", "kind_bool")


@pytest.mark.parametrize("op", ["and", "or"])
def test_bool_draws_distinct_sets_up_to_a_wide_arity(op):
    """64 distinct sets of 256, 2,000 times, drawn without a redraw."""
    kind = _bool_kind()
    entry = {"kind": "bool", "share": 1, "op": op, "arity": 64}
    t0 = time.monotonic()
    qs = list(itertools.islice(kind.queries(
        entry, _names_only(256), traffic.stream_rng(SEED, traffic.WINDOW)),
        2000))
    assert time.monotonic() - t0 < 5.0
    sizes = [len(q["terms"]) for q in qs]
    assert min(sizes) == 2 and max(sizes) == 64
    assert all(len(set(q["terms"])) == len(q["terms"]) for q in qs)
    assert all(q["op"] == op for q in qs)


def test_the_bool_control_drops_a_value():
    sets = reference.SetIndex(["a", "b"], np.array([0, 3, 5]),
                              np.array([1, 4, 9, 4, 9], np.uint16), 16)
    kind = _bool_kind()
    q = {"kind": "bool", "op": "and", "terms": ("a", "b")}
    want = kind.expected(sets, q)
    assert want.tolist() == [4, 9]
    assert kind.same(np.array([4, 9]), want)
    assert not kind.same(kind.control(sets, q), want)


# ---------------------------------------------------------------------------
# scratch cells made only of new files
# ---------------------------------------------------------------------------

def scratch(tmp_path, tr, chips=1):
    """A copy of this directory with a new configuration, a new traffic
    file and a cell of them in a copy of the manifest."""
    base = tmp_path / "chip"
    shutil.copytree(HERE, base, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    cfg = json.loads((base / "configs" / "dedup_netflix.json").read_text())
    (base / "configs" / "scratch_sets.json").write_text(json.dumps(
        dict(cfg, name="scratch_sets", tiny={"n_sets": 256,
                                             "n_values": 256 * 60})))
    shutil.copy(base / "configs" / "dedup_netflix.py",
                base / "configs" / "scratch_sets.py")
    (base / "traffic" / "scratch.json").write_text(json.dumps(tr))
    m = harness.load_manifest(ROOT)
    m["configs"].append(dict(m["configs"][0], name="scratch_sets",
                             file="benchmarks/chip/configs/scratch_sets.json"))
    m["workloads"].append({"name": "scratch.cell", "config": "scratch_sets",
                           "traffic": "scratch", "chips": chips,
                           "why": "scratch"})
    return base, m


@pytest.fixture
def short_warm_up(monkeypatch):
    monkeypatch.setattr(harness, "WARM_PASS_S", 0.3)
    monkeypatch.setattr(harness, "WARM_MAX_S", 1.0)
    monkeypatch.setattr(harness, "DRAIN_S", 5.0)


def _run(base, m, **kw):
    tiny = harness.resolve(m, "scratch.cell", base)["config"]["tiny"]
    return harness.run(ROOT, "scratch.cell", SEED, 0.5, False,
                       t_start=time.monotonic(), overrides=tiny, manifest=m,
                       log=lambda *a: None, base=base, **kw)


@pytest.mark.parametrize("tr", [BOOL_OPEN, OR_OPEN], ids=["and", "or"])
def test_a_scratch_bool_open_loop_cell_is_correct(tmp_path, short_warm_up,
                                                   tr):
    base, m = scratch(tmp_path, tr)
    line = _run(base, m)
    assert line["correct"] is True
    assert line["attempted"] > 20 and line["failed"] == 0
    assert line["checks"]["wrong_answers"]["value"] == 0
    assert {"p50_ms", "p95_ms", "qps", "setup_s"} <= set(line["metrics"])


def test_a_dropped_value_in_a_served_bitmap_is_not_correct(
        tmp_path, short_warm_up, monkeypatch):
    """One served bitmap, the first non-empty one of the window, loses
    its largest value where the program produces it."""
    from repro.core import RoaringBitmap, aggregate
    base, m = scratch(tmp_path, BOOL_OPEN)
    real = aggregate.execute_plans
    armed, dropped = [False], []

    def faulty(plans, **kw):
        out = real(plans, **kw)
        for j, bm in enumerate(out):
            if armed[0] and not dropped and len(bm):
                out[j] = RoaringBitmap.from_values(bm.to_array()[:-1])
                dropped.append(j)
        return out

    real_window = harness.Run.window

    def window(self):
        armed[0] = True
        return real_window(self)

    monkeypatch.setattr(aggregate, "execute_plans", faulty)
    monkeypatch.setattr(harness.Run, "window", window)
    line = _run(base, m)
    assert dropped
    assert line["attempted"] <= harness.CHECK_SAMPLE
    assert line["correct"] is False
    assert line["checks"]["wrong_answers"]["value"] == 1


def test_the_bool_control_fails_a_scratch_cell(tmp_path, short_warm_up):
    base, m = scratch(tmp_path, BOOL_OPEN)
    line = _run(base, m, control=True)
    assert line["correct"] is False
    assert line["checks"]["wrong_answers"]["value"] > 0


FOUR = """
import json, sys, time
sys.path.insert(0, sys.argv[1])
import harness
harness.WARM_PASS_S, harness.WARM_MAX_S, harness.DRAIN_S = 0.3, 1.0, 5.0
m = json.loads(sys.argv[2])
base = harness.Path(sys.argv[1])
tiny = harness.resolve(m, "scratch.cell", base)["config"]["tiny"]
line = harness.run(harness.Path(sys.argv[3]), "scratch.cell", int(sys.argv[4]),
                   0.5, False, t_start=time.monotonic(), overrides=tiny,
                   manifest=m, log=lambda *a: None, base=base)
import jax
print(json.dumps(dict(line, n_devices=len(jax.devices()))))
"""


@pytest.mark.parametrize("tr", [BOOL_OPEN, SIMILAR_CLOSED],
                         ids=["bool.open", "similar.closed"])
def test_a_scratch_cell_on_four_devices(tmp_path, tr):
    base, m = scratch(tmp_path, tr, chips=4)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(ROOT / "src"))
    out = subprocess.run(
        [sys.executable, "-c", FOUR, str(base), json.dumps(m),
         str(tmp_path), str(SEED)], env=env, capture_output=True, text=True,
        timeout=300)
    assert out.returncode == 0, out.stderr[-4000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["n_devices"] == 4 and line["device"]["count"] == 4
    assert line["correct"] is True and line["failed"] == 0
    assert line["checks"]["wrong_answers"]["value"] == 0
    assert line["attempted"] > 20
    assert "memory_peak_bytes of" in out.stderr
