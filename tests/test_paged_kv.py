"""Paged KV cache + speculative decode invariants.

The paged serving path replaces the dense per-slot `[B, max_seq]` KV
envelope with block tables over a shared pool (`T.paged_cache_schema`),
admission by free blocks (`serve.kv_alloc.BlockAllocator`), and an
optional self-speculative `[B, 1+k]` verify burst.  These tests pin:

  * the allocator's free-list semantics (interchangeable blocks, LIFO
    reuse, zero-free backpressure, double-free detection);
  * bit-identical greedy token ids between paged and dense serving on
    the golden archs x {ref, pallas} over variable-length prompts;
  * speculative decode (k >= 2) emitting token-for-token the same ids
    as plain greedy decode, with acceptance stats populated;
  * structured submit() rejections (over_length / over_capacity),
    block-constrained backpressure, and the zero-progress deadlock
    guard;
  * ProgramKey separation: dense / paged / draft-width decode programs
    are distinct cache lines.
"""
import numpy as np
import pytest
import jax

from repro import configs
from repro.core.config import EngineConfig
from repro.models import transformer as T
from repro.models.params import init_params
from repro.serve.engine import ServeEngine, SubmitRejection
from repro.serve.kv_alloc import BlockAllocator

ENG = EngineConfig(quant="none", backend="ref")
W8 = EngineConfig(quant="w8a8", backend="ref")

GOLDEN = ["qwen2-1.5b", "gemma2-2b"]


def _setup(name, seed=0):
    arch = configs.reduced(configs.get_arch(name))
    params = init_params(T.lm_schema(arch), jax.random.PRNGKey(seed))
    return arch, params


def _prompts(arch, n, seed=0, lens=(4, 5, 6, 7)):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, arch.vocab_size, size=int(lens[i % len(lens)]))
            for i in range(n)]


def _engine(arch, params, eng=ENG, **kw):
    kw.setdefault("batch_size", 2)
    kw.setdefault("max_seq", 32)
    return ServeEngine(arch, params, eng, **kw)


# ---------------------------------------------------------------------------
# BlockAllocator: free-list semantics
# ---------------------------------------------------------------------------

class TestBlockAllocator:
    def test_alloc_free_roundtrip(self):
        a = BlockAllocator(8)
        got = a.alloc(3)
        assert len(got) == 3 and len(set(got)) == 3
        assert all(0 <= b < 8 for b in got)
        assert a.in_use == 3 and a.free_blocks == 5
        a.free(got)
        assert a.in_use == 0 and a.free_blocks == 8
        assert a.stats.allocs == 1 and a.stats.frees == 1
        assert a.stats.blocks_served == 3

    def test_interleaved_frees_leave_no_fragmentation(self):
        """Blocks are interchangeable: after any alloc/free interleaving,
        every request up to the free count is satisfiable (no external
        fragmentation by construction)."""
        a = BlockAllocator(6)
        r1, r2, r3 = a.alloc(2), a.alloc(2), a.alloc(2)
        a.free(r2)                       # hole in the middle of the pool
        assert a.free_blocks == 2
        assert a.can_allocate(2)
        r4 = a.alloc(2)
        assert sorted(r4) == sorted(r2)  # LIFO reuse of the freed hole
        a.free(r1 + r3 + r4)
        assert a.can_allocate(6) and sorted(a.alloc(6)) == list(range(6))

    def test_zero_free_backpressure(self):
        a = BlockAllocator(4)
        a.alloc(4)
        assert not a.can_allocate(1)
        assert a.stats.denied == 1
        with pytest.raises(RuntimeError):
            a.alloc(1)
        # a zero-block probe still succeeds (empty request)
        assert a.can_allocate(0) and a.alloc(0) == []

    def test_double_free_and_range_checks(self):
        a = BlockAllocator(4)
        got = a.alloc(2)
        a.free(got)
        with pytest.raises(ValueError):
            a.free([got[0]])             # double free
        with pytest.raises(ValueError):
            a.free([4])                  # out of range
        with pytest.raises(ValueError):
            a.alloc(-1)
        with pytest.raises(ValueError):
            BlockAllocator(0)

    def test_peak_and_describe(self):
        a = BlockAllocator(8)
        r = a.alloc(5)
        a.free(r)
        a.alloc(2)
        d = a.describe()
        assert d["peak_in_use"] == 5 and d["in_use"] == 2
        assert d["utilization"] == pytest.approx(0.25)
        assert d["num_blocks"] == 8 and d["free_blocks"] == 6


# ---------------------------------------------------------------------------
# Paged vs dense: bit-identical greedy ids, golden archs x backends
# ---------------------------------------------------------------------------

class TestPagedParity:
    @pytest.mark.parametrize("backend", ["ref", "pallas"])
    @pytest.mark.parametrize("name", GOLDEN)
    def test_paged_ids_match_dense(self, name, backend):
        """Variable-length prompts through a paged engine produce token
        ids bit-identical to the dense engine, on both kernel backends
        (gemma2 exercises the local ring layers that stay dense)."""
        arch, params = _setup(name)
        eng = EngineConfig(quant="none", backend=backend)
        prompts = _prompts(arch, 5, seed=1)
        dense = _engine(arch, params, eng).generate(prompts,
                                                    max_new_tokens=3)
        paged = _engine(arch, params, eng, kv_layout="paged",
                        page_size=8).generate(prompts, max_new_tokens=3)
        for d, p in zip(dense, paged):
            np.testing.assert_array_equal(p, d)

    def test_paged_int8_kv_matches_dense(self):
        """The int8 KV pools (per-page scales) keep bit-identical ids."""
        arch, params = _setup("qwen2-1.5b")
        eng = EngineConfig(quant="none", backend="ref",
                           kv_cache_dtype="int8")
        prompts = _prompts(arch, 3, seed=2)
        dense = _engine(arch, params, eng).generate(prompts,
                                                    max_new_tokens=3)
        paged = _engine(arch, params, eng, kv_layout="paged",
                        page_size=8).generate(prompts, max_new_tokens=3)
        for d, p in zip(dense, paged):
            np.testing.assert_array_equal(p, d)

    def test_paged_schema_requires_page_multiple(self):
        arch, _ = _setup("qwen2-1.5b")
        with pytest.raises(ValueError):
            T.paged_cache_schema(arch, 2, 30, ENG, 8)

    def test_paged_slot_footprint_beats_dense_envelope(self):
        """The headline claim: at fixed memory, measured KV bytes/slot of
        the paged engine is strictly below the dense max_seq envelope for
        short requests, so sustainable concurrency is strictly higher."""
        arch, params = _setup("qwen2-1.5b")
        de = _engine(arch, params)
        pe = _engine(arch, params, kv_layout="paged", page_size=8)
        prompts = _prompts(arch, 4, seed=3)
        de.generate(prompts, max_new_tokens=3)
        pe.generate(prompts, max_new_tokens=3)
        ds, ps = de.stats(), pe.stats()
        assert ps["kv_bytes_per_slot"] < ds["kv_bytes_per_slot"]
        assert ps["kv_blocks"]["peak_in_use"] <= ps["kv_blocks"]["num_blocks"]
        assert ps["page_size"] == 8 and ps["kv_layout"] == "paged"


# ---------------------------------------------------------------------------
# Speculative decode: greedy-exact acceptance
# ---------------------------------------------------------------------------

class TestSpeculativeDecode:
    @pytest.mark.parametrize("k", [2, 3])
    def test_spec_matches_greedy_token_for_token(self, k):
        """Self-speculative verify bursts (draft width k) emit exactly the
        plain greedy ids: acceptance only ever commits tokens the verify
        logits agree with, and rejected tails are never observable."""
        arch, params = _setup("gemma2-2b")
        prompts = _prompts(arch, 5, seed=4)
        want = _engine(arch, params).generate(prompts, max_new_tokens=4)
        se = _engine(arch, params, kv_layout="paged", page_size=8,
                     draft_len=k)
        got = se.generate(prompts, max_new_tokens=4)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(g, w)
        st = se.stats()
        assert st["spec_steps"] > 0
        assert 0.0 <= st["accepted_draft_rate"] <= 1.0
        assert 1.0 <= st["tokens_per_burst"] <= 1 + k

    def test_spec_on_dense_layout(self):
        """draft_len composes with the dense cache too (layout and
        speculation are independent axes)."""
        arch, params = _setup("qwen2-1.5b")
        prompts = _prompts(arch, 3, seed=5)
        want = _engine(arch, params).generate(prompts, max_new_tokens=3)
        got = _engine(arch, params, draft_len=2).generate(prompts,
                                                          max_new_tokens=3)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(g, w)

    def test_spec_requires_compiled_decode(self):
        """Speculation and paging ride the compiled DecodeStep; a
        non-lowerable arch must fail loudly at construction, not fall
        back to an eager path that silently ignores them."""
        arch = configs.reduced(configs.get_arch("falcon-mamba-7b"))
        params = init_params(T.lm_schema(arch), jax.random.PRNGKey(0))
        with pytest.raises(ValueError):
            _engine(arch, params, draft_len=2)
        with pytest.raises(ValueError):
            _engine(arch, params, kv_layout="paged", page_size=8)


# ---------------------------------------------------------------------------
# Admission, backpressure, rejection
# ---------------------------------------------------------------------------

class TestPagedAdmission:
    def test_structured_rejections(self):
        arch, params = _setup("qwen2-1.5b")
        se = _engine(arch, params, kv_layout="paged", page_size=8,
                     kv_blocks=2)
        long = np.zeros(30, np.int32)
        r = se.submit(long, max_new_tokens=8)
        assert isinstance(r, SubmitRejection) and not r
        assert r.reason == "over_length"
        # fits max_seq but needs 4 blocks of a 2-block pool
        r2 = se.submit(np.zeros(20, np.int32), max_new_tokens=8)
        assert isinstance(r2, SubmitRejection) and not r2
        assert r2.reason == "over_capacity"
        assert se.stats()["rejected_requests"] == 2
        assert se.pending() == 0

    def test_generate_surfaces_rejections(self):
        arch, params = _setup("qwen2-1.5b")
        se = _engine(arch, params, kv_layout="paged", page_size=8,
                     kv_blocks=2)
        with pytest.raises(ValueError, match="rejected"):
            se.generate([np.zeros(20, np.int32)], max_new_tokens=8)

    def test_block_constrained_pool_still_exact(self):
        """With only enough blocks for one request at a time the engine
        serializes admissions (denied probes counted) but the ids are
        unchanged from an unconstrained pool."""
        arch, params = _setup("qwen2-1.5b")
        prompts = _prompts(arch, 4, seed=6)
        free = _engine(arch, params, kv_layout="paged", page_size=8,
                       prefill_len=8)
        want = free.generate(prompts, max_new_tokens=3)
        tight = _engine(arch, params, kv_layout="paged", page_size=8,
                        prefill_len=8, kv_blocks=2)
        got = tight.generate(prompts, max_new_tokens=3)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(g, w)
        d = tight.stats()["kv_blocks"]
        assert d["denied"] > 0                  # backpressure happened
        assert d["peak_in_use"] <= 2
        assert d["in_use"] == 0                 # all released at the end
        assert d["allocs"] == d["frees"] == len(prompts)

    def test_padded_prompt_overflow_deadlock_guard(self):
        """A request that fits the pool by raw length but not once padded
        to the prefill bucket can never be admitted; run() must raise
        instead of spinning."""
        arch, params = _setup("qwen2-1.5b")
        se = _engine(arch, params, kv_layout="paged", page_size=8,
                     kv_blocks=1, prefill_len=16)
        t = se.submit(np.zeros(4, np.int32), max_new_tokens=2)
        assert not isinstance(t, SubmitRejection)
        with pytest.raises(RuntimeError, match="KV blocks"):
            se.run()


# ---------------------------------------------------------------------------
# ProgramKey separation across layout / draft width
# ---------------------------------------------------------------------------

class TestDecodeKeyVariants:
    def test_layout_and_draft_produce_distinct_keys(self):
        arch, params = _setup("qwen2-1.5b")
        dense = _engine(arch, params)
        paged = _engine(arch, params, kv_layout="paged", page_size=8)
        spec = _engine(arch, params, kv_layout="paged", page_size=8,
                       draft_len=3)
        keys = {dense._decode_key(), paged._decode_key(),
                spec._decode_key()}
        assert len(keys) == 3
        assert ":p8" in paged._decode_key().variant
        assert ":k3" in spec._decode_key().variant
