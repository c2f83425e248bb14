"""Prefix-sharing paged KV invariants.

Two layers of evidence for the refcounted copy-on-write block design:

  * PROPERTY tests over BlockAllocator's refcounting -- arbitrary
    alloc/share/free interleavings never double-free, never leak
    (`in_use + free_blocks == num_blocks` is conserved at every step),
    and the peak-occupancy watermark is monotone;
  * GOLDEN tests over the serving engine -- on qwen2 under quant="none"
    (compute dtype == cache dtype, so the chunk program's
    store-then-attend roundtrip is the identity) sharing returns
    BIT-IDENTICAL greedy token ids to private whole-prompt prefill on
    both backends, while measurably allocating fewer fresh blocks; a
    warm index changes nothing but the hit counters; gemma2's local
    attention layers (dense ring KV, no page boundary) record a blocker
    and fall back to private prefill, still bit-identical to the
    no-sharing engine.

Runs with or without `hypothesis` installed: the offline container
replays each property over the _hypothesis_compat rotation.
"""
import numpy as np
import pytest
import jax

try:
    from hypothesis import given, settings, strategies as st
except ImportError:                       # offline container
    from _hypothesis_compat import given, settings, strategies as st

from repro import configs
from repro.core.config import EngineConfig
from repro.models import transformer as T
from repro.models.params import init_params
from repro.serve.engine import ServeEngine
from repro.serve.kv_alloc import BlockAllocator

PAGE = 8
PLEN = 24            # pinned prefill width: 2 full shared pages + tail
SHARED = 16          # page-aligned shared system-prompt length
NEW = 4


def _setup(name, seed=0):
    arch = configs.reduced(configs.get_arch(name))
    params = init_params(T.lm_schema(arch), jax.random.PRNGKey(seed))
    return arch, params


def _shared_prompts(arch, n, seed=0):
    """n full-width prompts agreeing on the first SHARED tokens."""
    rng = np.random.default_rng(seed)
    head = rng.integers(0, arch.vocab_size, size=SHARED)
    return [np.concatenate([
        head, rng.integers(0, arch.vocab_size, size=PLEN - SHARED)
    ]).astype(np.int32) for _ in range(n)]


def _engine(arch, params, backend="ref", sharing=True, **kw):
    eng = EngineConfig(quant="none", backend=backend)
    kw.setdefault("batch_size", 2)
    kw.setdefault("max_seq", 32)
    kw.setdefault("kv_blocks", 16)
    return ServeEngine(arch, params, eng, kv_layout="paged",
                       page_size=PAGE, prefill_len=PLEN,
                       prefix_sharing=sharing, **kw)


# ---------------------------------------------------------------------------
# BlockAllocator refcounting: conservation properties
# ---------------------------------------------------------------------------

class TestRefcountProperties:
    @settings(deadline=None)
    @given(num_blocks=st.integers(min_value=4, max_value=16),
           seed=st.integers(min_value=0, max_value=9))
    def test_interleavings_conserve_the_pool(self, num_blocks, seed):
        """Arbitrary alloc/share/free interleavings: the pool is conserved
        at every step (no leak, no double-count), the peak watermark is
        monotone, and releasing every live handle returns the allocator to
        pristine (all refcounts zero)."""
        rng = np.random.default_rng(seed)
        a = BlockAllocator(num_blocks)
        held = []                       # one entry per live owner handle
        peak_seen = 0
        for _ in range(200):
            op = int(rng.integers(0, 3))
            if op == 0:
                n = int(rng.integers(0, num_blocks + 1))
                if a.can_allocate(n):
                    held.append(a.alloc(n))
            elif op == 1 and held:      # a second table joins a prefix
                src = held[int(rng.integers(len(held)))]
                held.append(a.share(src))
            elif op == 2 and held:      # one owner releases
                a.free(held.pop(int(rng.integers(len(held)))))
            assert a.in_use + a.free_blocks == a.num_blocks
            assert a.stats.peak_in_use >= max(peak_seen, a.in_use)
            peak_seen = a.stats.peak_in_use
        for h in held:
            a.free(h)
        assert a.in_use == 0 and a.free_blocks == num_blocks
        assert all(a.refcount(b) == 0 for b in range(num_blocks))

    @settings(deadline=None)
    @given(owners=st.integers(min_value=2, max_value=6))
    def test_shared_block_frees_only_at_zero(self, owners):
        """A block with k owners survives k-1 frees and returns to the
        pool exactly on the k-th; the k+1-th is a detected double free."""
        a = BlockAllocator(4)
        blocks = a.alloc(2)
        for _ in range(owners - 1):
            assert a.share(blocks) == blocks
        for i in range(owners - 1):
            a.free(blocks)
            assert a.in_use == 2                  # still owned
            assert a.refcount(blocks[0]) == owners - 1 - i
        a.free(blocks)
        assert a.in_use == 0 and a.free_blocks == 4
        with pytest.raises(ValueError, match="double free"):
            a.free(blocks)

    def test_share_of_free_block_rejected(self):
        """Sharing a freed block means the caller's index held a stale
        pointer -- loud failure, not silent aliasing."""
        a = BlockAllocator(4)
        blocks = a.alloc(1)
        a.free(blocks)
        with pytest.raises(ValueError, match="free block"):
            a.share(blocks)
        with pytest.raises(ValueError, match="out of range"):
            a.share([7])

    @settings(deadline=None)
    @given(n1=st.integers(min_value=1, max_value=8),
           n2=st.integers(min_value=1, max_value=8))
    def test_peak_watermark_is_monotone(self, n1, n2):
        a = BlockAllocator(8)
        r1 = a.alloc(n1)
        assert a.stats.peak_in_use == n1
        a.free(r1)
        a.alloc(n2)
        assert a.stats.peak_in_use == max(n1, n2)

    def test_share_accounting(self):
        a = BlockAllocator(8)
        blocks = a.alloc(3)
        a.share(blocks)
        a.share(blocks[:1])
        assert a.stats.shares == 2
        assert a.stats.shared_blocks == 4
        assert a.share([]) == [] and a.stats.shares == 2   # no-op join


# ---------------------------------------------------------------------------
# Golden: shared serving is bit-identical and cheaper
# ---------------------------------------------------------------------------

class TestGoldenSharing:
    @pytest.mark.parametrize("backend", ["ref", "pallas"])
    def test_bit_identical_with_fewer_fresh_blocks(self, backend):
        """Sharing vs private serving of one shared-prefix trace under
        quant="none": token ids match bitwise on both backends, the index
        records hits, and strictly fewer fresh blocks are allocated."""
        arch, params = _setup("qwen2-1.5b")
        prompts = _shared_prompts(arch, 4)
        base = _engine(arch, params, backend, sharing=False)
        want = base.generate(prompts, max_new_tokens=NEW)
        eng = _engine(arch, params, backend, sharing=True)
        got = eng.generate(prompts, max_new_tokens=NEW)
        for a, b in zip(want, got):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        ps = eng.stats()["prefix_sharing"]
        assert ps["enabled"] and ps["hits"] >= 1
        assert ps["shared_blocks"] >= 1 and ps["index_nodes"] >= 1
        assert (eng.alloc.stats.blocks_served
                < base.alloc.stats.blocks_served)
        # pool conservation holds through serving too
        assert eng.alloc.in_use + eng.alloc.free_blocks == \
            eng.alloc.num_blocks

    def test_warm_index_is_invariant(self):
        """A warm index (prefix bits cached from an earlier run) changes
        hit counters, never token ids: cold-engine output == warm-engine
        output, request for request."""
        arch, params = _setup("qwen2-1.5b")
        prompts = _shared_prompts(arch, 3)
        cold = _engine(arch, params, sharing=True)
        want = cold.generate(prompts, max_new_tokens=NEW)
        warm = _engine(arch, params, sharing=True)
        warm.generate(prompts[:1], max_new_tokens=NEW)   # seeds the index
        warm_hits0 = warm.stats()["prefix_sharing"]["hits"]
        got = warm.generate(prompts, max_new_tokens=NEW)
        for a, b in zip(want, got):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # every warm-run prompt matched the seeded prefix
        assert (warm.stats()["prefix_sharing"]["hits"] - warm_hits0
                >= len(prompts))

    def test_gemma2_local_layers_record_blocker_and_fall_back(self):
        """Local-attention archs cannot share (dense ring KV has no page
        boundary): the engine disables sharing with a recorded blocker and
        serves bit-identically to an explicit no-sharing engine."""
        arch, params = _setup("gemma2-2b")
        eng = _engine(arch, params, sharing=True)
        assert not eng.prefix_sharing
        assert any("local" in b for b in eng.prefix_sharing_blockers)
        prompts = _shared_prompts(arch, 2)
        want = _engine(arch, params, sharing=False).generate(
            prompts, max_new_tokens=NEW)
        got = eng.generate(prompts, max_new_tokens=NEW)
        for a, b in zip(want, got):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        ps = eng.stats()["prefix_sharing"]
        assert ps["enabled"] is False and ps["blockers"]

    def test_config_validation(self):
        arch, params = _setup("qwen2-1.5b")
        eng = EngineConfig(quant="none", backend="ref")
        with pytest.raises(ValueError, match="paged"):
            ServeEngine(arch, params, eng, batch_size=2, max_seq=32,
                        prefix_sharing=True, prefill_len=PLEN)
        with pytest.raises(ValueError, match="prefill_len"):
            ServeEngine(arch, params, eng, batch_size=2, max_seq=32,
                        kv_layout="paged", page_size=PAGE,
                        prefix_sharing=True)
