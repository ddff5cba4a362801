"""Mesh / axis context shared by the models, the wide aggregates and the
dry-run.

One mesh source of truth:

  * ``activate(mesh)`` makes a mesh current for model-side sharding
    constraints (``constrain`` / ``dp_axes`` / ``axis_sizes``) AND for
    jax (``jax.set_mesh``), so ``with_sharding_constraint`` with bare
    ``PartitionSpec``s resolves against it.  Meshes are built with
    ``Auto`` axes (``launch.mesh``), so a constraint stays a hint to the
    partitioner;
  * ``install_wide_mesh()`` builds ``launch.mesh.make_wide_mesh`` and
    installs it as the default mesh of every wide bitmap aggregate
    (``core.aggregate.set_default_mesh`` stores through :func:`set_wide_mesh`
    here, so the two never disagree).

Everything degrades to a no-op off-mesh: single-device tests, examples and
the serve engine call the same code paths with no mesh active and get the
identity behaviour back.
"""

from __future__ import annotations

import contextlib

import jax
from jax.sharding import PartitionSpec as P

MODEL_AXIS = "model"
WIDE_AXIS = "wide"

_PURE_DP = False
_ACTIVE_MESH = None     # set by activate()
_WIDE_MESH = None       # storage behind core.aggregate.set_default_mesh


# ---------------------------------------------------------------------------
# pure-dp switch (configs with pure_dp=True ignore the model axis entirely)
# ---------------------------------------------------------------------------

def set_pure_dp(flag: bool) -> None:
    """Treat every mesh axis (except ``wide``) as data-parallel: the model
    axis is never assigned to weights, activations or head plans."""
    global _PURE_DP
    _PURE_DP = bool(flag)


def pure_dp() -> bool:
    return _PURE_DP


# ---------------------------------------------------------------------------
# current mesh
# ---------------------------------------------------------------------------

def current_mesh():
    """The mesh made current by :func:`activate`, else None (off-mesh:
    every helper degrades to a no-op).  Read at trace time, so jitted
    model code sees the mesh the dry-run lowers under."""
    return _ACTIVE_MESH


@contextlib.contextmanager
def activate(mesh):
    """Make ``mesh`` current for this context AND for jax's sharding
    machinery (``jax.set_mesh``)."""
    global _ACTIVE_MESH
    prev = _ACTIVE_MESH
    _ACTIVE_MESH = mesh
    try:
        with jax.set_mesh(mesh):
            yield mesh
    finally:
        _ACTIVE_MESH = prev


def axis_sizes_of(mesh) -> dict:
    """{axis name: size} for any mesh-shaped object exposing
    ``.axis_names`` / ``.devices`` -- the one derivation shared by ctx
    and the sharding rules."""
    return dict(zip(mesh.axis_names, tuple(mesh.devices.shape)))


def dp_axes_of(mesh, pure_dp: bool) -> tuple:
    """Axes a batch dim shards over on ``mesh``: every axis except
    ``model`` / ``wide`` (all but ``wide`` under pure-dp)."""
    excl = {WIDE_AXIS} if pure_dp else {WIDE_AXIS, MODEL_AXIS}
    return tuple(a for a in mesh.axis_names if a not in excl)


def axis_sizes() -> dict:
    """{axis name: size} of the current mesh ({} off-mesh)."""
    m = current_mesh()
    return {} if m is None else axis_sizes_of(m)


def dp_axes() -> tuple:
    """:func:`dp_axes_of` on the current mesh.  Off-mesh the conventional
    ``("data",)`` is returned -- harmless, because :func:`constrain` is a
    no-op there."""
    m = current_mesh()
    if m is None:
        return ("data",)
    return dp_axes_of(m, _PURE_DP)


def model_axis_size() -> int:
    if _PURE_DP:
        return 1
    return int(axis_sizes().get(MODEL_AXIS, 1))


# ---------------------------------------------------------------------------
# model-side helpers
# ---------------------------------------------------------------------------

def attn_head_plan(hkv: int, g: int, qc: int) -> str:
    """Which flash-attention tile dim carries the model axis.

    ``"hkv"`` / ``"g"`` / ``"qc"`` name the dim to constrain; ``"auto"``
    leaves GSPMD to split the model axis jointly over (hkv, g) from the
    projection's head sharding; ``"dp"`` constrains only the batch dim
    (pure-dp, size-1 model axis, or nothing divides)."""
    ms = model_axis_size()
    if ms <= 1:
        return "dp"
    if hkv % ms == 0:
        return "hkv"
    if g % ms == 0:
        return "g"
    if (hkv * g) % ms == 0:
        return "auto"
    if qc % ms == 0:
        return "qc"
    return "dp"


def constrain(x, dims: dict):
    """``with_sharding_constraint`` x with {dim index: axis | axes tuple}.

    Off-mesh this is the identity.  Axes absent from the current mesh are
    dropped (model code names ``"model"`` unconditionally; a wide-only or
    data-only mesh simply ignores it), as are axes whose size does not
    divide the dim (GSPMD would pad; mid-model that is never worth it)
    and axes already claimed by a lower dim (under pure-dp ``dp_axes()``
    includes the model axis, so a call constraining both the batch dim
    and an explicit ``"model"`` dim must not duplicate it)."""
    m = current_mesh()
    if m is None:
        return x
    sizes = axis_sizes_of(m)
    entries: list = [None] * x.ndim
    used: set = set()
    for d in sorted(dims):
        ax = dims[d]
        axes = (ax,) if isinstance(ax, str) else tuple(ax or ())
        axes = tuple(a for a in axes if a in sizes and a not in used)
        n = 1
        for a in axes:
            n *= sizes[a]
        if not axes or (n > 1 and x.shape[d] % n != 0):
            continue
        used.update(axes)
        entries[d] = axes[0] if len(axes) == 1 else axes
    if all(e is None for e in entries):
        return x
    return jax.lax.with_sharding_constraint(x, P(*entries))


# ---------------------------------------------------------------------------
# wide-aggregation mesh (shared with core.aggregate)
# ---------------------------------------------------------------------------

def set_wide_mesh(mesh) -> None:
    """Install (or clear, with None) the default mesh for every wide
    bitmap aggregate.  ``core.aggregate.set_default_mesh`` delegates here,
    so model code and bitmap code read one mesh state."""
    global _WIDE_MESH
    _WIDE_MESH = mesh


def wide_mesh():
    return _WIDE_MESH


def install_wide_mesh(n: int | None = None):
    """Build ``launch.mesh.make_wide_mesh(n)`` and install it as the wide
    aggregation default; returns the mesh.  A 1-device mesh is safe: the
    aggregates fall back to the single-dispatch path."""
    from repro.launch.mesh import make_wide_mesh
    mesh = make_wide_mesh(n)
    set_wide_mesh(mesh)
    return mesh


def resolve_wide(mesh):
    """Resolve a wide-aggregation mesh request to ``(mesh, size, axis)``.

    ``mesh=None`` falls back to the installed :func:`wide_mesh`; no mesh
    anywhere resolves to ``(None, 1, None)`` -- the single-device
    identity every sharded code path (``core.aggregate``,
    ``core.pairwise.SimilarityEngine``, ``serve.QueryServer``) degrades
    to.  A resolved mesh must be 1-D (one shard axis): the wide paths
    round-robin rows over a single axis, and a silent flatten of a 2-D
    mesh would scramble the shard <-> device mapping the arena's
    per-shard slabs key on."""
    if mesh is None:
        mesh = wide_mesh()
    if mesh is None:
        return None, 1, None
    names = getattr(mesh, "axis_names", None)
    if names is None:
        # opaque mesh-shaped stand-in (tests install sentinels): pass it
        # through untouched, size-1 -- callers that only need the mesh
        # identity keep working, sharded paths degrade to single-device
        return mesh, 1, None
    if len(names) != 1:
        raise ValueError(
            f"wide sharding needs a 1-D mesh; got axes {names!r}")
    import numpy as np
    return mesh, int(np.prod(mesh.devices.shape)), names[0]
