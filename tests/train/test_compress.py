"""Top-k gradient compression with Roaring coordinate sets."""

import jax
import jax.numpy as jnp
import numpy as np

from repro.optim import compress as GC


def test_topk_sparsify_and_densify(rng):
    g = jnp.asarray(rng.standard_normal((64, 32)), jnp.float32)
    vals, idx, res = GC.topk_sparsify(g, k=128)
    dense = GC.densify(vals, idx, g.shape)
    # kept + residual == original
    np.testing.assert_allclose(np.asarray(dense + res), np.asarray(g),
                               atol=1e-6)
    # kept entries are the largest magnitudes
    flat = np.abs(np.asarray(g).reshape(-1))
    thresh = np.sort(flat)[-128]
    assert np.abs(np.asarray(vals)).min() >= thresh - 1e-6


def test_sparse_allreduce_under_shard_map(rng):
    mesh = jax.make_mesh((1,), ("dp",))
    g = jnp.asarray(rng.standard_normal((256,)), jnp.float32)

    from jax.sharding import PartitionSpec as P

    def f(gl):
        red, res = GC.sparse_allreduce(gl, "dp", k=64)
        return red, res

    smap = jax.shard_map(f, mesh=mesh, in_specs=P(),
                         out_specs=(P(), P()), check_vma=False)
    red, res = jax.jit(smap)(g)
    # single replica: reduction == top-64 of g, residual == the rest
    np.testing.assert_allclose(np.asarray(red + res), np.asarray(g),
                               atol=1e-6)
    assert int(np.count_nonzero(np.asarray(red))) == 64


def test_wire_bytes_accounting(rng):
    idx = np.sort(rng.choice(1 << 20, 4096, replace=False))
    sparse = GC.wire_bytes_sparse(idx)
    dense = GC.wire_bytes_dense(1 << 20)
    assert sparse < dense / 50
    bm = GC.coordinate_bitmap(idx)
    assert bm.cardinality == 4096
