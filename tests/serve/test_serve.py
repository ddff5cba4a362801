"""Serving layer: paged KV allocator, constrained decoding, engine."""

import dataclasses

import jax
import numpy as np
import pytest

import repro.configs as C
from repro.core import RoaringBitmap
from repro.models import transformer as T
from repro.serve.constrained import VocabConstraint, lexicon_constraint
from repro.serve.engine import BlockPolicy, Engine
from repro.serve.kv_cache import PagedKVAllocator


# ---------------------------------------------------------------- kv cache
def test_alloc_release_cycle():
    a = PagedKVAllocator(n_pages=64)
    p1 = a.allocate(1, 10)
    p2 = a.allocate(2, 20)
    assert len(set(p1) & set(p2)) == 0
    assert a.n_free == 34
    a.release(1)
    assert a.n_free == 44
    assert a.owner_overlap(1, 2) == 0
    p3 = a.allocate(3, 44)
    assert a.n_free == 0
    with pytest.raises(MemoryError):
        a.allocate(4, 1)


def test_extend_by_tokens():
    a = PagedKVAllocator(n_pages=16, page_size=128)
    a.extend(0, 100)
    assert len(a.pages_of(0)) == 1
    a.extend(0, 129)
    assert len(a.pages_of(0)) == 2
    a.extend(0, 129)   # idempotent
    assert len(a.pages_of(0)) == 2


def test_fragmentation_metric():
    a = PagedKVAllocator(n_pages=64)
    assert a.fragmentation() == 0.0
    a.allocate(1, 8)
    a.allocate(2, 8)
    a.release(1)       # hole at the front -> still one run? no: [0..7]+[16..]
    assert 0.0 <= a.fragmentation() < 1.0


# ------------------------------------------------------------- constrained
def test_constraint_algebra():
    v = 1000
    a = VocabConstraint(v, RoaringBitmap.from_range(0, 500))
    b = VocabConstraint(v, RoaringBitmap.from_range(250, 750))
    assert a.intersect(b).n_allowed() == 250
    assert a.union(b).n_allowed() == 750
    banned = a.ban(range(0, 500, 2))
    assert banned.n_allowed() == 250
    assert banned.feasible()
    assert not a.intersect(VocabConstraint(
        v, RoaringBitmap.from_range(600, 700))).feasible()


def test_constraint_apply_masks_logits(rng):
    import jax.numpy as jnp
    v = 64
    c = VocabConstraint(v, RoaringBitmap.from_values([3, 7, 11]))
    logits = jnp.asarray(rng.standard_normal((2, v)), jnp.float32)
    out = np.asarray(c.apply(logits))
    allowed = {3, 7, 11}
    for t in range(v):
        if t in allowed:
            assert np.isfinite(out[:, t]).all()
        else:
            assert (out[:, t] == -np.inf).all()


def test_lexicon_union(rng):
    lex = {"digits": np.arange(10), "alpha": np.arange(20, 40)}
    c = lexicon_constraint(100, lex, ["digits", "alpha"])
    assert c.n_allowed() == 30


# ------------------------------------------------------------------ engine
@pytest.mark.slow
def test_engine_generates_and_respects_constraint(rng):
    cfg = C.get_config("gemma2_27b", reduced=True)
    params = T.init_params(cfg, jax.random.key(0))
    allowed = RoaringBitmap.from_values(np.arange(32, dtype=np.uint32))
    eng = Engine(cfg, params, max_seq=128,
                 policy=BlockPolicy(sink_blocks=1, local_blocks=4),
                 constraint=VocabConstraint(cfg.vocab, allowed))
    prompts = rng.integers(0, cfg.vocab, (2, 8)).astype(np.int32)
    out = eng.generate(prompts, max_new_tokens=6)
    assert out.shape == (2, 6)
    assert (out < 32).all(), "constrained decoding must honor the vocab set"
    eng.release_all()
    assert eng.allocator.n_free == eng.allocator.n_pages


def test_mask_words_cache():
    """_mask_words is cached on per-request block counts: decode steps
    inside one attention block reuse the rendered words."""
    cfg = dataclasses.make_dataclass("Cfg", ["attn_block_size"])(128)
    eng = Engine.__new__(Engine)            # skip weights/jit setup
    eng.cfg = cfg
    eng.policy = BlockPolicy(sink_blocks=1, local_blocks=2)
    eng.n_blocks = 16
    eng._mask_cache = {}
    m1 = eng._mask_words([100, 200])
    m2 = eng._mask_words([120, 250])        # same block counts -> cache hit
    assert m2 is m1
    assert len(eng._mask_cache) == 1
    m3 = eng._mask_words([200, 250])        # first request crossed a block
    assert m3 is not m1
    assert len(eng._mask_cache) == 2
    # cached words match a fresh render
    from repro.core.tensor import block_mask_words
    sets = [eng.policy.visible_set(kl, 128) for kl in (100, 200)]
    assert np.array_equal(np.asarray(m1),
                          np.asarray(block_mask_words(sets, 16)))


def test_block_policy_sets():
    pol = BlockPolicy(sink_blocks=2, local_blocks=3,
                      pinned=RoaringBitmap.from_values([10]))
    vis = pol.visible_set(kv_len=128 * 20, block_size=128)
    got = set(vis.to_array().tolist())
    assert got == {0, 1, 10, 17, 18, 19}
