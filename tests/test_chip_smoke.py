"""chip_smoke.py keeps working: both of its phases at rehearsal size on
the CPU, with the kernel path forced on and the Pallas kernels in
interpret mode (the CPU default would answer from the numpy host paths
and never reach a kernel).  Also the compile-cache placement rule the
script and the benchmark runner share."""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[1]

_FORCE_KERNELS = """
import sys
sys.path.insert(0, {src!r})
from repro.kernels import ops
ops.prefer_kernel = lambda backend: True
ops._use_pallas = lambda backend: True
sys.path.insert(0, {root!r})
import chip_smoke
sys.exit(chip_smoke.main({argv!r}))
"""


def _run(argv, devices: int):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    code = _FORCE_KERNELS.format(src=str(ROOT / "src"), root=str(ROOT),
                                 argv=argv)
    return subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)


@pytest.mark.parametrize("chips,devices", [(1, 1), (4, 4)])
def test_smoke_phases_rehearse_on_cpu(chips, devices):
    out = _run(["--rehearse", "--chips", str(chips)], devices)
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    lines = out.stdout.strip().splitlines()
    assert json.loads(lines[-1]) == {
        "ok": True, "rehearsal": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": devices}}
    report = "\n".join(lines)
    assert "answers bit-identical to host twin" in report
    assert '"host_fallbacks": 0' in report


def test_smoke_refuses_without_accelerator():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


@pytest.fixture
def compile_cache():
    from repro.launch import compile_cache
    was = jax.config.jax_compilation_cache_dir
    yield compile_cache
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_follows_environment(compile_cache, monkeypatch,
                                           tmp_path):
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path / "outside"))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache(ROOT) == \
        str(tmp_path / "outside")
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_checkout(compile_cache, monkeypatch):
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    path = compile_cache.enable_compile_cache(ROOT)
    assert path == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
