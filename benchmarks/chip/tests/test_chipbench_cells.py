"""Every cell end to end at a tiny size on the CPU, through the harness's
internal entry: generator, traffic, window, reducers and the check, and
the shape of the last line.  Then the faults and the control that
``correct`` has to catch, and the real command without a TPU."""

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

SEED = 2**31 + 977            # past 32 signed bits, as the driver's are
CELLS = [w["name"] for w in harness.load_manifest(ROOT)["workloads"]]


@pytest.fixture(scope="module")
def manifest():
    return harness.load_manifest(ROOT)


@pytest.fixture(autouse=True)
def short_warm_up(monkeypatch):
    monkeypatch.setattr(harness, "WARM_PASS_S", 0.3)
    monkeypatch.setattr(harness, "WARM_MAX_S", 1.0)
    monkeypatch.setattr(harness, "DRAIN_S", 5.0)


def run_cell(manifest, workload, trace=False, seed=SEED, control=False):
    tiny = harness.resolve(manifest, workload)["config"]["tiny"]
    return harness.run(ROOT, workload, seed, 0.5, trace,
                       t_start=time.monotonic(), overrides=tiny,
                       manifest=manifest, log=lambda *a: None,
                       control=control)


def check_line(line, manifest, workload, trace):
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "checks"
    json.dumps(line)
    assert line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["count"] == 1
    for c in line["checks"].values():
        assert set(c) == {"value", "limit"}
    group = "per_layer" if trace else "end_to_end"
    want = {m["name"]: m["unit"] for m in manifest[group]
            if workload in m.get("workloads", [workload])}
    for name, m in line["metrics"].items():
        assert want[name] == m["unit"]
        assert np.isfinite(m["value"])
    if trace:
        assert {"busy_s", "window_s"} <= set(line["device"])
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
        # device metrics need a chip; the host ones are read on the CPU
        host = {m["name"] for m in manifest[group]
                if m["source"] != "device_trace" and m["name"] in want}
        assert host <= set(line["metrics"])
    else:
        assert set(line["metrics"]) == set(want)


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("trace", [False, True])
def test_cell_end_to_end_on_cpu(manifest, workload, trace):
    check_line(run_cell(manifest, workload, trace), manifest, workload,
               trace)


def _altered_score(monkeypatch):
    """A top-k answer altered where it is produced: one score moved by
    one float32 step."""
    from repro.core.pairwise import SimilarityEngine
    real = SimilarityEngine.topk_batch

    def altered(self, queries, k, metric="jaccard", **kw):
        out = []
        for idx, score, inter in real(self, queries, k, metric, **kw):
            score = score.copy()
            score[0] = np.nextafter(score[0], np.float32(2))
            out.append((idx, score, inter))
        return out

    monkeypatch.setattr(SimilarityEngine, "topk_batch", altered)


def _half_the_batch(monkeypatch):
    """Half of each batch left out: its queries get the answers of the
    half that was scored."""
    from repro.core.pairwise import SimilarityEngine
    real = SimilarityEngine.topk_batch

    def half(self, queries, k, metric="jaccard", **kw):
        queries = list(queries)
        kept = real(self, queries[:(len(queries) + 1) // 2], k, metric,
                    **kw)
        return [kept[i % len(kept)] for i in range(len(queries))]

    monkeypatch.setattr(SimilarityEngine, "topk_batch", half)


def _step_unchanged(monkeypatch):
    """A server tick that returns with its state unchanged."""
    from repro.serve import QueryServer
    monkeypatch.setattr(QueryServer, "step", lambda self, *a, **kw: None)


@pytest.mark.parametrize("fault", [_altered_score, _half_the_batch,
                                   _step_unchanged])
def test_a_broken_timed_path_is_not_correct(manifest, monkeypatch, fault):
    fault(monkeypatch)
    line = run_cell(manifest, CELLS[0])
    assert line["correct"] is False
    checks = line["checks"]
    assert checks["wrong_answers"]["value"] + checks["unanswered"][
        "value"] > 0 or line["attempted"] == 0 or line["failed"] > 0


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("seed", [1, SEED])
def test_the_control_fails_the_check(manifest, workload, seed):
    line = run_cell(manifest, workload, seed=seed, control=True)
    assert line["correct"] is False
    assert line["checks"]["wrong_answers"]["value"] > 0


def _command(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         CELLS[0], "--seed", str(SEED), "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_the_command_without_a_tpu_prints_no_result():
    out = _command(ROOT)
    assert out.returncode != 0
    assert "{" not in out.stdout


def test_the_control_without_a_tpu_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "benchmarks/chip/control.py", "--workload",
         CELLS[0], "--seconds", "1", "--seeds", "1", str(SEED)], cwd=ROOT,
        env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert "{" not in out.stdout


def test_the_command_in_a_bare_copy_prints_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _command(tmp_path, {"PYTHONPATH": ""})
    assert out.returncode != 0
    assert "{" not in out.stdout
