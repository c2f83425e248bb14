"""Model-agnostic engine IR, LM side: transformer prefill lowers through the
compiler, calibrates to a static-int8 program whose GEMM inputs all carry
compile-time scales, matches the eager T.forward/T.prefill paths on both
backends, and serves from the keyed ProgramCache."""
import dataclasses

import numpy as np
import pytest
import jax
import jax.numpy as jnp
import jax.tree_util as jtu

from repro import compiler, configs
from repro.compiler import passes
from repro.compiler.graph import (AddOp, AttnOp, EmbedOp, HeadOp, InputOp,
                                  LinearOp, MulOp, NormOp)
from repro.core import engine as eng_lib
from repro.core.config import EngineConfig
from repro.models import transformer as T
from repro.models.params import init_params, is_spec

ENG = EngineConfig(quant="none", backend="ref")
W8 = EngineConfig(quant="w8a8", backend="ref")

# archs the IR lowers (attention-only mixers); the rest stay eager
LOWERABLE = ["qwen2-1.5b", "gemma2-2b", "minitron-4b", "granite-8b"]
EAGER_ONLY = ["falcon-mamba-7b", "recurrentgemma-2b", "grok-1-314b",
              "whisper-tiny"]

B, L = 2, 12


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


# ---------------------------------------------------------------------------
# Lowering
# ---------------------------------------------------------------------------

class TestLowerTransformer:
    @pytest.mark.parametrize("name", LOWERABLE)
    def test_structure_and_param_paths(self, name):
        arch, params, _ = _setup(name)
        g = compiler.lower_transformer(arch)
        assert g.count(InputOp) == 1 and g.count(EmbedOp) == 1
        assert g.count(AttnOp) == arch.n_layers
        assert g.count(AddOp) == 2 * arch.n_layers
        # qkv + wo + mlp per layer
        per_layer = 4 + (3 if arch.mlp_gated else 2)
        assert g.count(LinearOp) == per_layer * arch.n_layers
        assert g.count(MulOp) == (arch.n_layers if arch.mlp_gated else 0)
        assert g.count(HeadOp) == 1
        assert isinstance(g.nodes[g.output], HeadOp)
        assert g.nodes[g.output].tied == arch.tie_embeddings
        for n in g.nodes:                    # topological, paths resolve
            assert all(i < n.id for i in n.inputs)
            for path in (getattr(n, "w", None), getattr(n, "b", None)):
                if path:
                    leaf = compiler.get_param(params, path)
                    assert hasattr(leaf, "shape"), (name, path)

    @pytest.mark.parametrize("name", EAGER_ONLY)
    def test_unsupported_archs_refuse(self, name):
        arch = configs.reduced(configs.get_arch(name))
        assert not compiler.can_lower(arch)
        assert compiler.lowering_blockers(arch)
        with pytest.raises(NotImplementedError):
            compiler.lower_transformer(arch)

    def test_qkv_colevel_on_conv_pe(self):
        """The concurrency the IR exposes: a block's three QKV projections
        dispatch in one Conv PE wave, and the SwiGLU gate/up pair does too."""
        arch, _, _ = _setup("qwen2-1.5b")
        g = compiler.lower_transformer(arch)
        s = compiler.level_schedule(g)
        level_of = {i: k for k, lv in enumerate(s.levels) for i in lv}
        for n in g.nodes:
            if isinstance(n, AttnOp):
                assert len({level_of[i] for i in n.inputs}) == 1
        assert s.stats["max_width"] >= 3


# ---------------------------------------------------------------------------
# Compiled dynamic program == eager forward (float path, bit-level)
# ---------------------------------------------------------------------------

class TestDynamicParity:
    @pytest.mark.parametrize("name", LOWERABLE)
    def test_float_forward_exact(self, name):
        arch, params, toks = _setup(name)
        prog = compiler.compile_lm(arch)
        out = compiler.execute(prog, params, toks, ENG)
        want, _ = T.forward(params, {"tokens": toks}, arch, ENG,
                            compute_dtype=jnp.float32)
        np.testing.assert_allclose(np.array(out), np.array(want),
                                   rtol=1e-5, atol=1e-5)

    def test_dynamic_program_memoized(self):
        arch, _, _ = _setup("qwen2-1.5b")
        assert compiler.compile_lm(arch) is compiler.compile_lm(arch)
        # the prefill variant is a distinct cached program
        p = compiler.compile_lm(arch, prefill=True)
        assert p is not compiler.compile_lm(arch)
        assert compiler.compile_lm(arch, prefill=True) is p


# ---------------------------------------------------------------------------
# Static int8 plan: every GEMM input carries a compile-time scale
# ---------------------------------------------------------------------------

class TestStaticPlan:
    def test_linear_inputs_int8_rest_float(self):
        arch, params, toks = _setup("qwen2-1.5b")
        prog = compiler.compile_lm_calibrated(arch, params, [toks])
        g, plan = prog.graph, prog.plan
        # zero f32 edges into GEMM engines
        assert passes.f32_roundtrip_edges(g, plan) == []
        assert prog.f32_roundtrips() == 0
        for n in g.nodes:
            if isinstance(n, LinearOp):      # every ops.linear input static
                # a fused residual-add epilogue appends the residual edge,
                # which rides the f32 MISC stream by design
                ins = (n.inputs[:-1] if n.epilogue is not None
                       and n.epilogue.add else n.inputs)
                assert all(plan.emit_int8[i] for i in ins), n
            if isinstance(n, (EmbedOp, HeadOp)):
                assert not plan.emit_int8[n.id]
        # the residual stream stays f32 on the MISC core
        for n in g.nodes:
            if isinstance(n, AddOp):
                assert not plan.emit_int8[n.id]

    def test_calibration_covers_every_edge(self):
        arch, params, toks = _setup("gemma2-2b")
        g = compiler.lower_transformer(arch)
        scales = compiler.calibrate(g, params, [toks], arch)
        assert set(scales) == {n.id for n in g.nodes}
        assert all(s > 0 for s in scales.values())


# ---------------------------------------------------------------------------
# Golden compiled-vs-eager parity, >=2 zoo configs x {ref, pallas}
# ---------------------------------------------------------------------------

# Max |static - dynamic| logit gap as a fraction of max |dynamic logit|
# (the CNN golden-test criterion, test_compiler.GOLDEN_GAP_FRAC): the
# requant-rounding drift of per-tensor static scales vs per-token dynamic
# quantization at reduced scale, ~2.5x the measured gap at seed 0.
GOLDEN_GAP_FRAC = {
    "qwen2-1.5b": 0.25,
    "gemma2-2b": 0.25,
    "minitron-4b": 0.30,
}


@pytest.fixture(scope="module")
def lm_golden():
    """One calibration + compile per arch, shared by both backends."""
    cache = {}

    def get(name):
        if name not in cache:
            arch, params, toks = _setup(name)
            prog = compiler.compile_lm_calibrated(arch, params, [toks])
            f, _ = T.forward(params, {"tokens": toks}, arch, ENG,
                             compute_dtype=jnp.float32)
            cache[name] = (arch, params, toks, prog, np.array(f))
        return cache[name]

    return get


class TestGoldenPrefillParity:
    @pytest.mark.parametrize("backend", ["ref", "pallas"])
    @pytest.mark.parametrize("name", sorted(GOLDEN_GAP_FRAC))
    def test_static_vs_eager_gap_bounded(self, name, backend, lm_golden):
        """The compiled static-int8 prefill program tracks the eager dynamic
        w8a8 forward within the golden bound and correlates with the float
        reference, on both kernel backends."""
        arch, params, toks, prog, f = lm_golden(name)
        eng = EngineConfig(quant="w8a8", backend=backend)
        qparams = eng_lib.quantize_params(params, eng)
        dyn = np.array(T.forward(qparams, {"tokens": toks}, arch, eng,
                                 compute_dtype=jnp.float32)[0])
        stat = np.array(compiler.execute(prog, qparams, toks, eng))
        assert np.isfinite(stat).all() and np.isfinite(dyn).all()
        gap = np.max(np.abs(stat - dyn))
        bound = GOLDEN_GAP_FRAC[name] * np.max(np.abs(dyn))
        assert gap <= bound, (name, backend, gap, bound)
        assert np.corrcoef(f.ravel(), stat.ravel())[0, 1] > 0.9


# ---------------------------------------------------------------------------
# Prefill program: last-token logits + collected KV == eager T.prefill
# ---------------------------------------------------------------------------

class TestPrefillProgram:
    @pytest.mark.parametrize("name", ["qwen2-1.5b", "gemma2-2b"])
    def test_logits_and_kv_match_eager_prefill(self, name):
        arch, params, toks = _setup(name)
        prog = compiler.compile_lm(arch, prefill=True)
        kvs = {}
        lp = compiler.execute(prog, params, toks, ENG, collect=kvs)
        cache = _cache(arch, B, L, ENG)
        elp, ecache = T.prefill(params, cache, {"tokens": toks}, arch, ENG,
                                compute_dtype=jnp.float32)
        np.testing.assert_allclose(np.array(lp), np.array(elp),
                                   rtol=1e-5, atol=1e-5)
        assert sorted(kvs) == list(range(arch.n_layers))
        for i in range(arch.n_layers):
            k, v = kvs[i]
            entry = ecache["layers"][i]
            w = entry["k"].shape[1]
            np.testing.assert_allclose(
                np.array(k[:, -w:].astype(entry["k"].dtype)),
                np.array(entry["k"][:, :min(w, L)]), rtol=1e-2, atol=1e-2)
            np.testing.assert_allclose(
                np.array(v[:, -w:].astype(entry["v"].dtype)),
                np.array(entry["v"][:, :min(w, L)]), rtol=1e-2, atol=1e-2)

    def test_decode_continues_from_compiled_prefill(self):
        """Compiled prefill -> eager decode == full forward teacher forcing
        (the serving invariant, through the program path)."""
        arch, params, _ = _setup("qwen2-1.5b")
        rng = np.random.default_rng(3)
        EXTRA = 3
        toks = jnp.array(rng.integers(0, arch.vocab_size,
                                      (B, L + EXTRA)).astype(np.int32))
        full, _ = T.forward(params, {"tokens": toks}, arch, ENG,
                            compute_dtype=jnp.float32)
        prog = compiler.compile_lm(arch, prefill=True)
        kvs = {}
        lp = compiler.execute(prog, params, toks[:, :L], ENG, collect=kvs)
        cache = _cache(arch, B, L + EXTRA, ENG)
        layers = []
        for i in range(arch.n_layers):
            k, v = kvs[i]
            layers.append(T._kv_store(cache["layers"][i], k, v, 0, ENG))
        cache = {"layers": layers, "pos": jnp.asarray(L, jnp.int32)}
        np.testing.assert_allclose(np.array(lp[:, 0]),
                                   np.array(full[:, L - 1]),
                                   rtol=2e-2, atol=2e-2)
        for t in range(EXTRA):
            ld, cache = T.decode(params, cache, toks[:, L + t:L + t + 1],
                                 arch, ENG, compute_dtype=jnp.float32)
            np.testing.assert_allclose(np.array(ld[:, 0]),
                                       np.array(full[:, L + t]),
                                       rtol=2e-2, atol=2e-2)


# ---------------------------------------------------------------------------
# Serving: ServeEngine prefill through the ProgramCache
# ---------------------------------------------------------------------------

class TestServeEnginePrograms:
    def test_compiled_prefill_matches_eager_prefill(self):
        from repro.serve.engine import ServeEngine
        arch, params, toks = _setup("qwen2-1.5b")
        se = ServeEngine(arch, params, ENG, batch_size=B, max_seq=L + 8)
        assert se.compiled
        cache = se._empty_cache()
        lp, c2 = se._prefill_exec()(se.params, cache, {"tokens": toks})
        elp, ec = T.prefill(params, _cache(arch, B, L + 8, ENG),
                            {"tokens": toks}, arch, ENG,
                            compute_dtype=jnp.float32)
        np.testing.assert_allclose(np.array(lp), np.array(elp),
                                   rtol=1e-4, atol=1e-4)
        for i in range(arch.n_layers):
            np.testing.assert_allclose(np.array(c2["layers"][i]["k"]),
                                       np.array(ec["layers"][i]["k"]),
                                       rtol=1e-2, atol=1e-2)
        assert int(c2["pos"]) == L

    def test_program_cache_hits_on_reserve(self):
        """The acceptance invariant: re-serving an arch hits the
        ProgramCache, including across engines sharing one cache."""
        from repro.serve.engine import ServeEngine
        arch, params, _ = _setup("qwen2-1.5b")
        rng = np.random.default_rng(0)
        calib = [jnp.array(rng.integers(0, arch.vocab_size,
                                        (2, 8)).astype(np.int32))]
        se = ServeEngine(arch, params, W8, batch_size=2, max_seq=32,
                         calib_batches=calib)
        prompts = [rng.integers(0, arch.vocab_size, size=6)
                   for _ in range(2)]
        se.generate(prompts, max_new_tokens=2)
        # one compile per program: prefill + decode, from ONE calibration
        assert se.cache.stats.misses == 2
        p1 = se.prefill_program()
        d1 = se.decode_program()
        assert p1.static and d1.static        # calibrated static programs
        assert d1.kind == "decode" and p1.kind == "forward"
        se.generate(prompts, max_new_tokens=2)
        assert se.cache.stats.misses == 2     # no recompile on re-serve
        assert se.cache.stats.hits >= 2
        assert se.prefill_program() is p1
        # a second engine on the same fabric shares the compiled programs
        se2 = ServeEngine(arch, params, W8, batch_size=2, max_seq=32,
                          calib_batches=calib, cache=se.cache)
        assert se2.prefill_program() is p1
        assert se2.decode_program() is d1
        assert se.cache.stats.misses == 2
        st = se.stats()
        assert st["compiled_prefill"] and st["prefill_levels"] > 0
        assert 0 < st["prefill_occupancy"] <= 1
        assert st["compiled_decode"] and st["decode_levels"] > 0
        assert st["lowering_blockers"] == []

    def test_calibrator_method_keys_distinct_programs(self):
        """absmax and percentile calibrations never share a cache entry."""
        from repro.serve.engine import ServeEngine
        arch, params, _ = _setup("qwen2-1.5b")
        rng = np.random.default_rng(0)
        calib = [jnp.array(rng.integers(0, arch.vocab_size,
                                        (2, 8)).astype(np.int32))]
        from repro.serve.program_cache import ProgramCache
        shared = ProgramCache(capacity=4)
        sa = ServeEngine(arch, params, W8, batch_size=2, max_seq=32,
                         calib_batches=calib, cache=shared)
        sp = ServeEngine(arch, params, W8, batch_size=2, max_seq=32,
                         calib_batches=calib, calibrator="p99.9",
                         cache=shared)
        pa, pp = sa.prefill_program(), sp.prefill_program()
        assert pa is not pp
        assert shared.stats.misses == 2
        assert sa.calib_id != sp.calib_id

    def test_greedy_generation_deterministic(self):
        from repro.serve.engine import ServeEngine
        arch, params, _ = _setup("gemma2-2b")
        rng = np.random.default_rng(1)
        se = ServeEngine(arch, params, ENG, batch_size=2, max_seq=48)
        prompts = [rng.integers(0, arch.vocab_size, size=5)
                   for _ in range(3)]
        a = se.generate(prompts, max_new_tokens=3)
        b = se.generate(prompts, max_new_tokens=3)
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x, y)


# ---------------------------------------------------------------------------
# Percentile calibrator
# ---------------------------------------------------------------------------

class TestPercentileCalibrator:
    def test_outlier_robustness(self):
        """One huge outlier wastes the absmax range but barely moves p99.9."""
        from repro.compiler.calibrate import PercentileCalibrator
        from repro.core.quant import Calibrator
        rng = np.random.default_rng(0)
        x = rng.normal(size=100_000).astype(np.float32)
        x[0] = 1e4
        ab, pc = Calibrator(), PercentileCalibrator(q=99.9)
        ab.observe("e", jnp.asarray(x))
        pc.observe("e", jnp.asarray(x))
        s_ab, s_pc = ab.scales()["e"], pc.scales()["e"]
        assert s_pc < s_ab / 100          # outlier ignored
        assert s_pc > 0

    def test_tracks_absmax_without_outliers(self):
        from repro.compiler.calibrate import PercentileCalibrator
        from repro.core.quant import Calibrator
        rng = np.random.default_rng(1)
        ab, pc = Calibrator(), PercentileCalibrator(q=100.0)
        for _ in range(3):                # streaming, with range growth
            x = jnp.asarray(rng.normal(size=4096).astype(np.float32)
                            * rng.uniform(0.5, 4.0))
            ab.observe("e", x)
            pc.observe("e", x)
        s_ab, s_pc = ab.scales()["e"], pc.scales()["e"]
        assert abs(s_pc - s_ab) / s_ab < 0.05   # p100 ~ absmax (bin width)

    def test_method_string_parsing(self):
        from repro.compiler.calibrate import make_calibrator
        assert make_calibrator("p99.9").q == 99.9
        with pytest.raises(ValueError):
            make_calibrator("median")

    def test_percentile_calibrated_program_still_accurate(self):
        arch, params, toks = _setup("qwen2-1.5b")
        prog = compiler.compile_lm_calibrated(arch, params, [toks],
                                              method="p99.9")
        qparams = eng_lib.quantize_params(params, W8)
        stat = np.array(compiler.execute(prog, qparams, toks, W8))
        f = np.array(T.forward(params, {"tokens": toks}, arch, ENG,
                               compute_dtype=jnp.float32)[0])
        assert np.isfinite(stat).all()
        assert np.corrcoef(f.ravel(), stat.ravel())[0, 1] > 0.9
