"""Decode-as-program invariants: the LM decode step lowers through the
engine IR (AttnOp `update` mode, DecodeStep program kind), compiles to a
static-int8 program from the same calibration run as prefill, executes from
the ProgramCache inside ServeEngine's decode burst, and the continuous-
batching slot scheduler serves any arrival order with per-request outputs
identical to serial serving."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp
import jax.tree_util as jtu

from repro import compiler, configs
from repro.compiler import executor as ex
from repro.compiler import passes
from repro.compiler.graph import AttnOp, HeadOp, LinearOp
from repro.core import engine as eng_lib
from repro.core.config import EngineConfig
from repro.models import transformer as T
from repro.models.params import init_params, is_spec
from repro.serve.engine import ServeEngine, SubmitRejection

ENG = EngineConfig(quant="none", backend="ref")
W8 = EngineConfig(quant="w8a8", backend="ref")

GOLDEN = ["qwen2-1.5b", "gemma2-2b"]

B, L, STEPS = 2, 8, 4


def _setup(name, seed=0):
    arch = configs.reduced(configs.get_arch(name))
    params = init_params(T.lm_schema(arch), jax.random.PRNGKey(seed))
    toks = jnp.array(np.random.default_rng(seed).integers(
        0, arch.vocab_size, (B, L)).astype(np.int32))
    return arch, params, toks


def _cache(arch, batch, seq, eng):
    return jtu.tree_map(lambda s: jnp.zeros(s.shape, s.dtype),
                        T.cache_schema(arch, batch, seq, eng),
                        is_leaf=is_spec)


def _greedy_ids(arch, params, prompts, eng, steps, compute=jnp.float32,
                max_seq=None):
    """Reference greedy loop: eager prefill + eager decode, one prompt per
    batch row (batch-size len(prompts), equal-length prompts)."""
    max_seq = max_seq or (len(prompts[0]) + steps + 2)
    toks = jnp.asarray(np.stack(prompts).astype(np.int32))
    cache = _cache(arch, len(prompts), max_seq, eng)
    logits, cache = T.prefill(params, cache, {"tokens": toks}, arch, eng,
                              compute_dtype=compute)
    cur = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
    out = []
    for _ in range(steps):
        out.append(np.asarray(cur[:, 0]))
        logits, cache = T.decode(params, cache, cur, arch, eng,
                                 compute_dtype=compute)
        cur = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
    return np.stack(out, axis=1)          # [B, steps]


# ---------------------------------------------------------------------------
# Lowering: the DecodeStep graph
# ---------------------------------------------------------------------------

class TestDecodeLowering:
    @pytest.mark.parametrize("name", GOLDEN)
    def test_decode_graph_mirrors_full_graph(self, name):
        """Same node sequence as the full graph (so calibration scales
        transfer by node id), with every AttnOp in update mode."""
        arch, _, _ = _setup(name)
        full = compiler.lower_transformer(arch)
        dec = compiler.lower_transformer(arch, mode="decode")
        assert len(full.nodes) == len(dec.nodes)
        for f, d in zip(full.nodes, dec.nodes):
            assert type(f) is type(d)
            assert f.inputs == d.inputs
            if isinstance(d, AttnOp):
                assert d.mode == "update" and f.mode == "full"
        assert dec.count(AttnOp) == arch.n_layers
        assert not dec.nodes[dec.output].last_only

    def test_unknown_mode_rejected(self):
        arch, _, _ = _setup("qwen2-1.5b")
        with pytest.raises(ValueError):
            compiler.lower_transformer(arch, mode="chunked")
        with pytest.raises(ValueError):
            compiler.compile_lm(arch, mode="chunked")

    def test_decode_program_kind_and_memoization(self):
        arch, _, _ = _setup("qwen2-1.5b")
        d = compiler.compile_lm(arch, mode="decode")
        assert d.kind == "decode"
        assert compiler.compile_lm(arch, mode="decode") is d
        # prefill / full / decode memoize as three distinct programs
        assert compiler.compile_lm(arch, prefill=True) is not d
        assert compiler.compile_lm(arch) is not d

    def test_execute_guards_program_kind(self):
        arch, params, toks = _setup("qwen2-1.5b")
        d = compiler.compile_lm(arch, mode="decode")
        p = compiler.compile_lm(arch, prefill=True)
        with pytest.raises(ValueError):
            compiler.execute(d, params, toks, ENG)
        cache = _cache(arch, B, L, ENG)
        with pytest.raises(ValueError):
            compiler.execute_decode(p, params, cache, toks[:, :1], ENG)


# ---------------------------------------------------------------------------
# Static plan: every decode GEMM input carries a compile-time scale
# ---------------------------------------------------------------------------

class TestStaticDecodePlan:
    def test_decode_gemms_all_static(self):
        arch, params, toks = _setup("qwen2-1.5b")
        prog = compiler.compile_lm_calibrated(arch, params, [toks],
                                              mode="decode")
        assert prog.static and prog.kind == "decode"
        g, plan = prog.graph, prog.plan
        assert passes.f32_roundtrip_edges(g, plan) == []
        assert prog.f32_roundtrips() == 0
        for n in g.nodes:
            if isinstance(n, LinearOp):
                # a fused residual tail rides the epilogue in f32 (the PE
                # adds it post-GEMM); only the GEMM inputs must be int8
                ins = (n.inputs[:-1] if n.epilogue is not None
                       and n.epilogue.add else n.inputs)
                assert all(plan.emit_int8[i] for i in ins), n

    def test_one_calibration_run_covers_both_programs(self):
        """calibrate_lm scales compile prefill AND decode; the two plans
        agree edge-for-edge on every shared (non-head) node."""
        arch, params, toks = _setup("gemma2-2b")
        scales = compiler.calibrate_lm(arch, params, [toks])
        pp = compiler.compile_lm(arch, scales=scales, mode="prefill")
        dp = compiler.compile_lm(arch, scales=scales, mode="decode")
        for n in dp.graph.nodes:
            if isinstance(n, HeadOp):
                continue
            assert dp.plan.out_scale[n.id] == pp.plan.out_scale[n.id]
            assert dp.plan.emit_int8[n.id] == pp.plan.emit_int8[n.id]

    def test_static_decode_tracks_static_full_program(self):
        """Teacher-forced static decode continues the static prefill within
        a small quantization drift of the static full-sequence program."""
        arch, params, _ = _setup("qwen2-1.5b")
        EXTRA = 3
        rng = np.random.default_rng(3)
        toks = jnp.array(rng.integers(0, arch.vocab_size,
                                      (B, L + EXTRA)).astype(np.int32))
        scales = compiler.calibrate_lm(arch, params, [toks])
        fprog = compiler.compile_lm(arch, scales=scales)
        pprog = compiler.compile_lm(arch, scales=scales, mode="prefill")
        dprog = compiler.compile_lm(arch, scales=scales, mode="decode")
        qparams = eng_lib.quantize_params(params, W8)
        full = np.asarray(compiler.execute(fprog, qparams, toks, W8))
        kvs = {}
        lp = compiler.execute(pprog, qparams, toks[:, :L], W8, collect=kvs)
        np.testing.assert_allclose(np.asarray(lp[:, 0]), full[:, L - 1],
                                   rtol=1e-5, atol=1e-5)
        cache = _cache(arch, B, L + EXTRA, W8)
        layers = [T._kv_store(cache["layers"][i], *kvs[i], 0, W8)
                  for i in range(arch.n_layers)]
        cache = {"layers": layers, "pos": jnp.asarray(L, jnp.int32)}
        bound = 0.15 * np.max(np.abs(full))
        for t in range(EXTRA):
            ld, cache = compiler.execute_decode(
                dprog, qparams, cache, toks[:, L + t:L + t + 1], W8)
            gap = float(np.max(np.abs(np.asarray(ld[:, 0]) - full[:, L + t])))
            assert np.isfinite(np.asarray(ld)).all()
            assert gap <= bound, (t, gap, bound)


# ---------------------------------------------------------------------------
# Golden decode parity x {ref, pallas}: bit-identical greedy token ids
# ---------------------------------------------------------------------------

class TestGoldenDecodeParity:
    @pytest.mark.parametrize("backend", ["ref", "pallas"])
    @pytest.mark.parametrize("name", GOLDEN)
    def test_compiled_decode_greedy_ids_match_eager(self, name, backend):
        """Full prefill + N-token greedy decode through the compiled
        (dynamic) prefill + DecodeStep programs produces bit-identical
        token ids to the eager T.prefill/T.decode loop, on both kernel
        backends."""
        arch, params, toks = _setup(name)
        eng = EngineConfig(quant="none", backend=backend)
        max_seq = L + STEPS + 2
        want = _greedy_ids(arch, params, np.asarray(toks), eng, STEPS,
                           max_seq=max_seq)

        pprog = compiler.compile_lm(arch, prefill=True)
        dprog = compiler.compile_lm(arch, mode="decode")
        kvs = {}
        logits = compiler.execute(pprog, params, toks, eng, collect=kvs)
        cache = _cache(arch, B, max_seq, eng)
        layers = []
        for i in range(arch.n_layers):
            k, v = kvs[i]
            entry = cache["layers"][i]
            if arch.layer_kind(i) == "local":
                w = entry["k"].shape[1]
                entry = T._kv_store(entry, k[:, -w:], v[:, -w:], 0, eng)
            else:
                entry = T._kv_store(entry, k, v, 0, eng)
            layers.append(entry)
        cache = {"layers": layers, "pos": jnp.asarray(L, jnp.int32)}
        cur = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
        got = []
        for _ in range(STEPS):
            got.append(np.asarray(cur[:, 0]))
            ld, cache = compiler.execute_decode(dprog, params, cache, cur,
                                                eng)
            cur = jnp.argmax(ld[:, -1], -1)[:, None].astype(jnp.int32)
        np.testing.assert_array_equal(np.stack(got, axis=1), want)

    def test_dynamic_decode_logits_bitwise_vs_eager(self):
        """Stronger than id parity on the float path: the compiled decode
        step's logits equal eager T.decode's bit for bit."""
        arch, params, toks = _setup("gemma2-2b")
        max_seq = L + 3
        dprog = compiler.compile_lm(arch, mode="decode")
        cache = _cache(arch, B, max_seq, ENG)
        _, cache = T.prefill(params, cache, {"tokens": toks}, arch, ENG,
                             compute_dtype=jnp.float32)
        cache2 = jtu.tree_map(lambda x: x, cache)
        tok = toks[:, -1:]
        for _ in range(3):
            le, cache = T.decode(params, cache, tok, arch, ENG,
                                 compute_dtype=jnp.float32)
            lp, cache2 = compiler.execute_decode(dprog, params, cache2, tok,
                                                 ENG)
            np.testing.assert_array_equal(np.asarray(le), np.asarray(lp))
            tok = jnp.argmax(le[:, -1], -1)[:, None].astype(jnp.int32)


# ---------------------------------------------------------------------------
# ServeEngine: compiled decode burst + continuous batching
# ---------------------------------------------------------------------------

class TestServeEngineDecode:
    def test_decode_burst_executes_cached_program(self):
        arch, params, _ = _setup("qwen2-1.5b")
        rng = np.random.default_rng(0)
        calib = [jnp.array(rng.integers(0, arch.vocab_size,
                                        (2, 8)).astype(np.int32))]
        se = ServeEngine(arch, params, W8, batch_size=2, max_seq=32,
                         calib_batches=calib)
        prompts = [rng.integers(0, arch.vocab_size, size=6)
                   for _ in range(2)]
        se.generate(prompts, max_new_tokens=2)
        # two compiles: prefill + decode, no more on re-serve
        assert se.cache.stats.misses == 2
        d = se.decode_program()
        assert d.static and d.kind == "decode" and d.f32_roundtrips() == 0
        se.generate(prompts, max_new_tokens=2)
        assert se.cache.stats.misses == 2
        st = se.stats()
        assert st["compiled_decode"] and st["decode_levels"] > 0
        assert st["decode_steps"] > 0

    def test_prefill_and_decode_cache_keys_distinct(self):
        arch, params, _ = _setup("qwen2-1.5b")
        se = ServeEngine(arch, params, ENG, batch_size=2, max_seq=32)
        assert se._prefill_key() != se._decode_key()
        se.prefill_program()
        se.decode_program()
        variants = {k.variant for k in se.cache.keys()}
        assert len(variants) == 2

    def test_compiled_matches_eager_engine_ids(self):
        """Engine-level golden: compiled prefill+decode serving produces
        the same greedy ids as a ServeEngine with both programs disabled
        (the all-eager path), float fabric."""
        arch, params, _ = _setup("gemma2-2b")
        rng = np.random.default_rng(1)
        prompts = [rng.integers(0, arch.vocab_size, size=6)
                   for _ in range(3)]
        a = ServeEngine(arch, params, ENG, batch_size=2, max_seq=32,
                        prefill_len=6).generate(prompts, max_new_tokens=3)
        # eager engine decodes in bf16; compare against the f32 reference
        # loop instead, which the compiled path must match bitwise
        want = _greedy_ids(arch, params, prompts[:1] + prompts[1:],
                           ENG, 3, max_seq=32)
        for got, ref in zip(a, want):
            np.testing.assert_array_equal(got, ref)

    def test_continuous_refill_serves_deep_queue(self):
        arch, params, _ = _setup("qwen2-1.5b")
        rng = np.random.default_rng(2)
        se = ServeEngine(arch, params, ENG, batch_size=2, max_seq=32,
                         decode_burst=2)
        prompts = [rng.integers(0, arch.vocab_size, size=5)
                   for _ in range(7)]
        outs = se.generate(prompts, max_new_tokens=3)
        assert len(outs) == 7
        assert all(len(o) == 3 for o in outs)
        st = se.stats()
        assert st["slot_refills"] >= 5          # 7 requests, 2 slots
        assert st["slot_refill_rate"] > 0.5
        assert 0 < st["slot_occupancy"] <= 1

    def test_arrival_order_invariance(self):
        """The continuous-batching property: any submission order yields
        the same per-request token ids as serial serving (slot placement
        and batch composition cannot leak between rows)."""
        arch, params, _ = _setup("qwen2-1.5b")
        rng = np.random.default_rng(3)
        prompts = [rng.integers(0, arch.vocab_size, size=6)
                   for _ in range(6)]
        serial = {}
        for i, p in enumerate(prompts):
            se = ServeEngine(arch, params, ENG, batch_size=2, max_seq=32,
                             prefill_len=6)
            serial[i] = se.generate([p], max_new_tokens=3)[0]
        for seed in range(3):
            order = list(range(len(prompts)))
            np.random.default_rng(seed).shuffle(order)
            se = ServeEngine(arch, params, ENG, batch_size=2, max_seq=32,
                             prefill_len=6, decode_burst=1 + seed)
            tickets = {i: se.submit(prompts[i], 3) for i in order}
            res = se.run()
            for i in order:
                np.testing.assert_array_equal(res[tickets[i]], serial[i],
                                              err_msg=f"req {i} seed {seed}")

    def test_eager_fallback_reports_blockers(self):
        """A non-lowerable arch serves through the same continuous
        scheduler on the eager path, and stats() says WHY it fell back."""
        arch = configs.reduced(configs.get_arch("falcon-mamba-7b"))
        params = init_params(T.lm_schema(arch), jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        se = ServeEngine(arch, params, ENG, batch_size=2, max_seq=32)
        assert not se.compiled and not se.compiled_decode
        prompts = [rng.integers(0, arch.vocab_size, size=5)
                   for _ in range(3)]
        outs = se.generate(prompts, max_new_tokens=2)
        assert len(outs) == 3 and all(len(o) == 2 for o in outs)
        st = se.stats()
        assert st["lowering_blockers"], "fallback must not be silent"
        assert any("mamba" in b for b in st["lowering_blockers"])
        assert st["slot_refills"] >= 1

    def test_oversized_prompt_rejected(self):
        arch, params, _ = _setup("qwen2-1.5b")
        se = ServeEngine(arch, params, ENG, batch_size=2, max_seq=16)
        # queue-level backpressure: a falsy SubmitRejection, not a raise
        rej = se.submit(np.zeros(12, np.int32), max_new_tokens=8)
        assert isinstance(rej, SubmitRejection) and not rej
        assert rej.reason == "over_length"
        assert se.stats()["rejected_requests"] == 1
        assert se.pending() == 0
        # a 0-token request would never own its slot; reject at submit
        with pytest.raises(ValueError):
            se.submit(np.zeros(4, np.int32), max_new_tokens=0)
        se2 = ServeEngine(arch, params, ENG, batch_size=2, max_seq=32,
                          prefill_len=4)
        se2.submit(np.zeros(8, np.int32), max_new_tokens=2)
        with pytest.raises(ValueError):
            se2.run()
