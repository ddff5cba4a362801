"""dist.ctx: off-mesh no-op degradation, head plans, and the shared wide
mesh wiring with core.aggregate."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import aggregate
from repro.core.bitmap import RoaringBitmap
from repro.dist import ctx
from repro.launch.mesh import make_local_mesh


class FakeMesh:
    def __init__(self, shape=(16, 16), axes=("data", "model")):
        self.axis_names = axes
        self.devices = np.empty(shape, object)


@pytest.fixture(autouse=True)
def _reset():
    yield
    ctx.set_pure_dp(False)
    ctx.set_wide_mesh(None)


# ---------------------------------------------------------------------------
# off-mesh degradation
# ---------------------------------------------------------------------------

def test_off_mesh_is_noop():
    assert ctx.current_mesh() is None
    assert ctx.axis_sizes() == {}
    assert ctx.dp_axes() == ("data",)
    assert ctx.model_axis_size() == 1
    x = jnp.ones((4, 4))
    assert ctx.constrain(x, {0: ctx.dp_axes(), 1: "model"}) is x


def test_attn_head_plan_off_mesh_is_dp():
    assert ctx.attn_head_plan(8, 4, 128) == "dp"


# ---------------------------------------------------------------------------
# with a mesh
# ---------------------------------------------------------------------------

def test_activate_sets_and_restores():
    mesh = make_local_mesh()
    with ctx.activate(mesh):
        assert ctx.current_mesh() is mesh
        assert ctx.axis_sizes() == {"data": 1, "model": 1}
        assert ctx.dp_axes() == ("data",)
        y = ctx.constrain(jnp.ones((4, 4)), {0: "data"})
        np.testing.assert_array_equal(np.asarray(y), np.ones((4, 4)))
    assert ctx.current_mesh() is None


def test_constrain_under_jit_traces():
    mesh = make_local_mesh()

    def f(x):
        return ctx.constrain(x, {0: ctx.dp_axes(), 1: "model"}) * 2
    with ctx.activate(mesh):
        out = jax.jit(f)(jnp.ones((4, 4)))
    np.testing.assert_array_equal(np.asarray(out), 2 * np.ones((4, 4)))


def test_constrain_drops_absent_and_non_dividing_axes(monkeypatch):
    monkeypatch.setattr(ctx, "_ACTIVE_MESH", FakeMesh())
    x = jnp.ones((7, 5))
    # 7 % 16 and 5 % 16: both constraints drop -> identity (no jax call,
    # which would fail against the fake mesh)
    assert ctx.constrain(x, {0: "data", 1: "model"}) is x
    # an axis the mesh doesn't have drops too
    assert ctx.constrain(x, {0: "wide"}) is x


def test_axis_queries_against_mesh_shape(monkeypatch):
    monkeypatch.setattr(
        ctx, "_ACTIVE_MESH", FakeMesh((2, 4, 8), ("pod", "data", "model")))
    assert ctx.axis_sizes() == {"pod": 2, "data": 4, "model": 8}
    assert ctx.dp_axes() == ("pod", "data")
    assert ctx.model_axis_size() == 8
    ctx.set_pure_dp(True)
    assert ctx.dp_axes() == ("pod", "data", "model")
    assert ctx.model_axis_size() == 1


def test_constrain_pure_dp_no_duplicate_axes():
    # under pure-dp, dp_axes() includes "model"; a call constraining both
    # the batch dim and an explicit "model" dim (models/mlp.py MoE path)
    # must dedupe instead of building an invalid duplicate-axis spec
    mesh = make_local_mesh()
    ctx.set_pure_dp(True)
    with ctx.activate(mesh):
        assert ctx.dp_axes() == ("data", "model")
        out = ctx.constrain(jnp.ones((4, 4)), {0: ctx.dp_axes(), 1: "model"})
    np.testing.assert_array_equal(np.asarray(out), np.ones((4, 4)))


def test_attn_head_plan_divisibility(monkeypatch):
    monkeypatch.setattr(ctx, "_ACTIVE_MESH", FakeMesh((1, 16)))
    assert ctx.attn_head_plan(16, 4, 128) == "hkv"
    assert ctx.attn_head_plan(2, 16, 128) == "g"
    assert ctx.attn_head_plan(8, 2, 128) == "auto"   # joint 16 divides
    assert ctx.attn_head_plan(3, 5, 128) == "qc"
    assert ctx.attn_head_plan(3, 5, 127) == "dp"
    ctx.set_pure_dp(True)
    assert ctx.attn_head_plan(16, 4, 128) == "dp"


# ---------------------------------------------------------------------------
# one wide-mesh source of truth with core.aggregate
# ---------------------------------------------------------------------------

def test_aggregate_default_mesh_is_ctx_state():
    mesh = object()
    aggregate.set_default_mesh(mesh)
    assert ctx.wide_mesh() is mesh
    assert aggregate._resolve_mesh(None) is mesh
    ctx.set_wide_mesh(None)
    assert aggregate._resolve_mesh(None) is None


def test_install_wide_mesh_feeds_aggregates():
    mesh = ctx.install_wide_mesh()
    try:
        assert mesh.axis_names == ("wide",)
        assert aggregate._resolve_mesh(None) is mesh
        # 1-device host: aggregates fall back transparently and stay exact
        bms = [RoaringBitmap.from_values([1, 5, 70000 + i])
               for i in range(4)]
        got = RoaringBitmap.or_many(bms).to_array().tolist()
        assert got == sorted({1, 5} | {70000 + i for i in range(4)})
    finally:
        aggregate.set_default_mesh(None)
