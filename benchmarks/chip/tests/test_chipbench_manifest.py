"""BENCHMARK.json against the benchmark's contract: every cell resolves
its files by name, names and units use the allowed characters, every
per-layer metric's cells report the metric it moves, and a scratch cell
made only of new files resolves without an edit."""

import json
import re
import shutil
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))

import harness  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def test_top_level_keys(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert manifest["paths"] == ["benchmarks/chip"]
    assert manifest["command"] == ["python3", "benchmarks/chip/run.py"]
    assert 1 <= manifest["run_seconds"] <= 51
    assert len(json.dumps(manifest)) < 64 * 1024


def test_every_cell_resolves(manifest):
    for cell in manifest["workloads"]:
        spec = harness.resolve(manifest, cell["name"])
        assert spec["config"]["name"] == cell["config"]
        assert spec["traffic"]["loop"] in ("open", "closed")
        assert cell["chips"] in (1, 4)
        for m in spec["end_to_end"] + spec["per_layer"]:
            assert callable(harness.reader(m["name"]).read)


def test_config_files(manifest):
    files = set()
    for c in manifest["configs"]:
        assert c["file"].startswith("benchmarks/chip/")
        assert (ROOT / c["file"]).is_file()
        files.add(c["file"])
        cfg = json.loads((ROOT / c["file"]).read_text())
        for key in c["reduced"]:
            assert NAME.match(key) and key in cfg
        # the sizes the CPU tests run it at, each a key of the sizes
        assert cfg["tiny"] and set(cfg["tiny"]) <= set(cfg)
        assert any(w["config"] == c["name"] for w in manifest["workloads"])
    assert len(files) == len(manifest["configs"])


def test_names_and_units(manifest):
    groups = ("configs", "workloads", "end_to_end", "per_layer")
    for g in groups:
        names = [e["name"] for e in manifest[g]]
        assert len(names) == len(set(names)), g
        for n in names:
            assert NAME.match(n), n
    for w in manifest["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    pairs = [(w["config"], w["traffic"]) for w in manifest["workloads"]]
    assert len(pairs) == len(set(pairs))
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in manifest["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25


def test_each_cell_reports_setup_and_what_its_layers_move(manifest):
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert "setup_s" in e2e
    cells = {w["name"] for w in manifest["workloads"]}

    def reports(metric, cell):
        return cell in metric.get("workloads", cells)

    for cell in cells:
        assert reports(e2e["setup_s"], cell)
        assert any(reports(m, cell) for n, m in e2e.items()
                   if n != "setup_s")
        assert any(reports(m, cell) for m in manifest["per_layer"])
    for m in manifest["per_layer"]:
        assert m["moves"] in e2e
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
        for cell in m["workloads"]:
            assert cell in cells
            assert reports(e2e[m["moves"]], cell)


def test_files_under_paths_are_named_from_name_characters():
    for p in HERE.rglob("*"):
        rel = p.relative_to(ROOT).as_posix()
        if "__pycache__" in rel or not p.is_file():
            continue
        assert re.match(r"^[A-Za-z0-9_./-]+$", rel), rel


def test_a_scratch_cell_of_new_files_resolves(manifest, tmp_path):
    base = tmp_path / "chip"
    shutil.copytree(HERE, base, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    (base / "configs" / "scratch_cfg.json").write_text(json.dumps(
        dict(json.loads((base / "configs" / "dedup_netflix.json")
                        .read_text()), name="scratch_cfg")))
    shutil.copy(base / "configs" / "dedup_netflix.py",
                base / "configs" / "scratch_cfg.py")
    (base / "traffic" / "scratch.closed8.json").write_text(json.dumps(
        {"loop": "closed", "clients": 8,
         "mix": [{"kind": "similar", "share": 1, "k": 5,
                  "metric": "jaccard"}]}))
    (base / "metrics" / "scratch_metric.py").write_text(
        "def read(run):\n    return 1.0\n")
    m = json.loads(json.dumps(manifest))
    m["configs"].append(dict(m["configs"][-1], name="scratch_cfg"))
    m["workloads"].append({"name": "scratch.cell", "config": "scratch_cfg",
                           "traffic": "scratch.closed8", "chips": 1,
                           "why": "scratch"})
    m["per_layer"].append({"name": "scratch_metric", "unit": "%",
                           "better": "higher", "source": "device_trace",
                           "layer": "kernels", "moves": "qps",
                           "workloads": ["scratch.cell"]})
    spec = harness.resolve(m, "scratch.cell", base)
    assert spec["traffic"]["clients"] == 8
    assert [x["name"] for x in spec["per_layer"]] == ["scratch_metric"]
    assert harness.reader("scratch_metric", base).read(None) == 1.0
