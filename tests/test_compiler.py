"""Compiler layer: op-graph lowering, calibration, requant folding, and the
compiled static-int8 engine program vs the eager dynamic path."""
import dataclasses

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro import compiler
from repro.compiler import passes
from repro.compiler.graph import (AddOp, ConcatOp, ConvOp, DwcOp, InputOp,
                                  LinearOp, PoolOp)
from repro.configs.cnn_zoo import CNN_ZOO
from repro.core import engine as eng_lib
from repro.core.config import CNNConfig, ConvSpec as C, EngineConfig
from repro.core.quant import QTensor
from repro.models import cnn
from repro.models.params import init_params

SMALL_HW = 32


def _small(cfg):
    return dataclasses.replace(cfg, input_hw=SMALL_HW)


def _setup(name, seed=0, batch=2):
    cfg = _small(CNN_ZOO[name])
    params = init_params(cnn.cnn_schema(cfg), jax.random.PRNGKey(seed))
    x = jnp.asarray(np.random.default_rng(0).normal(
        size=(batch, cfg.input_hw, cfg.input_hw, cfg.input_ch)
    ).astype(np.float32) * 0.5)
    return cfg, params, x


# ---------------------------------------------------------------------------
# Graph construction
# ---------------------------------------------------------------------------

class TestGraph:
    def test_all_six_stage_kinds_lower(self):
        """One synthetic config exercising every stage kind."""
        cfg = CNNConfig(
            name="allkinds", input_hw=32, input_ch=3,
            stem_kernel=3, stem_stride=2, stem_ch=16,
            stages=(
                C("conv", out_ch=32, kernel=3, stride=1, repeat=1),
                C("pool", kernel=2, stride=2),
                C("bottleneck", out_ch=32, kernel=3, stride=1, repeat=1),
                C("inverted", out_ch=32, kernel=3, stride=1, repeat=1,
                  expand=2),
                C("dwsep", out_ch=64, kernel=3, stride=1, repeat=1),
                C("fire", out_ch=64, kernel=3, stride=1, repeat=1),
            ), num_classes=10)
        g = compiler.build_graph(cfg)
        assert g.count(InputOp) == 1
        assert g.count(ConvOp) >= 9          # stem + stage convs
        assert g.count(DwcOp) == 2           # inverted + dwsep
        assert g.count(AddOp) == 2           # bottleneck + inverted residual
        assert g.count(PoolOp) == 2          # max pool + global avgpool
        assert g.count(ConcatOp) == 1        # fire expand concat
        assert g.count(LinearOp) == 1        # head
        # topological: every input id precedes its consumer
        for n in g.nodes:
            assert all(i < n.id for i in n.inputs)
        assert isinstance(g.nodes[g.output], LinearOp)

    def test_graph_matches_schema_for_zoo(self):
        """Every zoo model builds, and every param path resolves against the
        schema-shaped param tree."""
        for name, cfg0 in CNN_ZOO.items():
            cfg = _small(cfg0)
            params = init_params(cnn.cnn_schema(cfg), jax.random.PRNGKey(0))
            g = compiler.build_graph(cfg)
            for n in g.nodes:
                for path in (getattr(n, "w", None), getattr(n, "b", None)):
                    if path:
                        leaf = compiler.get_param(params, path)
                        assert hasattr(leaf, "shape"), (name, path)

    def test_bottleneck_residual_shapes(self):
        g = compiler.build_graph(_small(CNN_ZOO["resnet50"]))
        chains = passes.residual_chains(g)
        assert len(chains) >= 16             # 3+4+6+3 blocks, some 2-input
        for conv_id, add_id in chains:
            assert isinstance(g.nodes[add_id], AddOp)


# ---------------------------------------------------------------------------
# Dynamic program == eager path (cnn_forward is the thin wrapper)
# ---------------------------------------------------------------------------

class TestDynamicProgram:
    @pytest.mark.parametrize("name", ["resnet50", "mobilenetv2",
                                      "squeezenet"])
    def test_float_forward_finite(self, name):
        cfg, params, x = _setup(name)
        eng = EngineConfig(quant="none", backend="ref")
        prog = compiler.compile_cnn(cfg)
        out = compiler.execute(prog, params, x, eng)
        assert out.shape == (2, cfg.num_classes)
        assert np.isfinite(np.array(out)).all()
        # and cnn_forward is exactly this program
        np.testing.assert_array_equal(
            np.array(out), np.array(cnn.cnn_forward(params, x, cfg, eng)))


# ---------------------------------------------------------------------------
# Calibration
# ---------------------------------------------------------------------------

class TestCalibration:
    def test_records_scale_for_every_edge(self):
        cfg, params, x = _setup("mobilenetv2")
        g = compiler.build_graph(cfg)
        scales = compiler.calibrate(g, params, [x], cfg)
        assert set(scales) == {n.id for n in g.nodes}
        assert all(s > 0 for s in scales.values())

    def test_running_absmax_over_batches(self):
        """The recorded scale is the max over all batches (running absmax)."""
        cfg, params, x = _setup("squeezenet")
        g = compiler.build_graph(cfg)
        s1 = compiler.calibrate(g, params, [x], cfg)
        s2 = compiler.calibrate(g, params, [x, 3.0 * x], cfg)
        assert s2[0] > s1[0]                 # input edge saw a larger range
        assert all(s2[i] >= s1[i] - 1e-12 for i in s1)

    def test_rejects_quantized_engine(self):
        cfg, params, x = _setup("squeezenet")
        g = compiler.build_graph(cfg)
        with pytest.raises(ValueError):
            compiler.calibrate(g, params, [x], cfg,
                               eng=EngineConfig(quant="w8a8"))


# ---------------------------------------------------------------------------
# Requant folding / fusion
# ---------------------------------------------------------------------------

class TestPasses:
    def test_no_f32_roundtrips_on_conv_add_relu_chains(self):
        """The fusion criterion: in the compiled static program every
        conv->add->relu chain stays int8 -- the conv epilogue requants into
        the MISC add's input scale and the add requants its output, with no
        f32 tensor materialized between engines."""
        cfg, params, x = _setup("resnet50")
        prog = compiler.compile_calibrated(cfg, params, [x])
        assert passes.f32_roundtrip_edges(prog.graph, prog.plan) == []
        assert prog.f32_roundtrips() == 0
        for conv_id, add_id in passes.residual_chains(prog.graph):
            assert prog.plan.emit_int8[conv_id]
            assert prog.plan.emit_int8[add_id]
        # while the dynamic program round-trips every internal edge
        assert compiler.compile_cnn(cfg).f32_roundtrips() > 50

    def test_maxpool_scale_preserving(self):
        cfg, params, x = _setup("resnet50")
        g = compiler.build_graph(cfg)
        scales = compiler.calibrate(g, params, [x], cfg)
        plan = compiler.fold_requant(g, scales)
        for n in g.nodes:
            if isinstance(n, PoolOp) and n.pool == "max":
                assert plan.out_scale[n.id] == plan.out_scale[n.inputs[0]]

    def test_concat_branches_folded_to_one_scale(self):
        cfg, params, x = _setup("squeezenet")
        prog = compiler.compile_calibrated(cfg, params, [x])
        g, plan = prog.graph, prog.plan
        folded = dict((p, c) for p, c in plan.folded)
        for n in g.nodes:
            if isinstance(n, ConcatOp):
                for p in n.inputs:
                    assert plan.out_scale[p] == plan.out_scale[n.id]
                    assert folded.get(p) == n.id
        assert plan.stats["folded_requants"] >= 16   # 8 fire modules x 2

    def test_missing_scales_rejected(self):
        g = compiler.build_graph(_small(CNN_ZOO["squeezenet"]))
        with pytest.raises(ValueError):
            compiler.fold_requant(g, {0: 1.0})


# ---------------------------------------------------------------------------
# End-to-end: compiled static int8 vs eager reference
# ---------------------------------------------------------------------------

class TestStaticProgram:
    @pytest.mark.parametrize("name", ["resnet50", "mobilenetv2"])
    def test_matches_eager_within_quant_tolerance(self, name):
        """ResNet-style and MobileNet-style: the compiled static-int8
        program agrees with both the float path and the eager dynamic w8a8
        path within quantization tolerance (rank correlation, as in
        test_cnn.test_quantized_close_to_float)."""
        cfg, params, x = _setup(name)
        eng = EngineConfig(quant="w8a8", backend="ref")
        qparams = eng_lib.quantize_params(params, eng)
        f = np.array(cnn.cnn_forward(
            params, x, cfg, EngineConfig(quant="none", backend="ref")))
        dyn = np.array(cnn.cnn_forward(qparams, x, cfg, eng))
        prog = compiler.compile_calibrated(cfg, params, [x])
        stat = np.array(compiler.execute(prog, qparams, x, eng))
        assert np.isfinite(stat).all()
        assert np.corrcoef(f.ravel(), stat.ravel())[0, 1] > 0.7
        assert np.corrcoef(dyn.ravel(), stat.ravel())[0, 1] > 0.7

    def test_all_intermediates_int8(self):
        """Structural check on the executed values: every internal edge of
        the static program carries int8."""
        cfg, params, x = _setup("mobilenetv2")
        eng = EngineConfig(quant="w8a8", backend="ref")
        qparams = eng_lib.quantize_params(params, eng)
        prog = compiler.compile_calibrated(cfg, params, [x])
        out = compiler.execute(prog, qparams, x, eng)
        assert out.dtype == jnp.float32      # only the logits are f32
        assert all(prog.plan.emit_int8[n.id] for n in prog.graph.nodes
                   if n.id != prog.graph.output)

    def test_static_program_on_pallas_backend(self):
        """The same compiled program runs on the Pallas kernels and matches
        the ref backend (the engines' out_scale epilogues agree)."""
        cfg, params, x = _setup("mobilenetv2")
        engr = EngineConfig(quant="w8a8", backend="ref")
        engp = EngineConfig(quant="w8a8", backend="pallas")
        qparams = eng_lib.quantize_params(params, engr)
        prog = compiler.compile_calibrated(cfg, params, [x])
        r = np.array(compiler.execute(prog, qparams, x, engr))
        p = np.array(compiler.execute(prog, qparams, x, engp))
        assert np.corrcoef(r.ravel(), p.ravel())[0, 1] > 0.99

    def test_static_program_jits(self):
        cfg, params, x = _setup("squeezenet")
        eng = EngineConfig(quant="w8a8", backend="ref")
        qparams = eng_lib.quantize_params(params, eng)
        prog = compiler.compile_calibrated(cfg, params, [x])
        eager = np.array(compiler.execute(prog, qparams, x, eng))
        jitted = np.array(jax.jit(
            lambda p, im: compiler.execute(prog, p, im, eng))(qparams, x))
        np.testing.assert_allclose(jitted, eager, rtol=1e-5, atol=1e-5)

    def test_requires_quantized_params(self):
        cfg, params, x = _setup("squeezenet")
        prog = compiler.compile_calibrated(cfg, params, [x])
        with pytest.raises(ValueError, match="QTensor"):
            compiler.execute(prog, params, x,
                             EngineConfig(quant="w8a8", backend="ref"))


# ---------------------------------------------------------------------------
# Compile-time weight-layout folding (im2col reshape, DWC lane padding)
# ---------------------------------------------------------------------------

class TestWeightLayoutFolding:
    def test_conv_weights_prelaid_and_dwc_padded(self):
        cfg, params, x = _setup("mobilenetv2")
        eng = EngineConfig(quant="w8a8", backend="ref")
        qparams = eng_lib.quantize_params(params, eng)
        g = compiler.build_graph(cfg)
        folded = passes.fold_weight_layouts(g, qparams)
        for n in g.nodes:
            if isinstance(n, ConvOp) and not n.first_layer:
                w = compiler.get_param(folded, n.w)
                assert w.q.ndim == 2              # im2col GEMM layout
                assert w.scale.shape[0] == 1
            elif isinstance(n, ConvOp):
                assert compiler.get_param(folded, n.w).q.ndim == 4  # stem
            elif isinstance(n, DwcOp):
                w = compiler.get_param(folded, n.w)
                assert w.q.shape[2] % 128 == 0    # lane-aligned
                b = compiler.get_param(folded, n.b)
                assert b.shape[0] == w.q.shape[2]
        # untouched leaves are shared, not copied
        assert compiler.get_param(folded, ("stem_w",)) is \
            compiler.get_param(qparams, ("stem_w",))

    @pytest.mark.parametrize("name", ["mobilenetv2", "resnet50"])
    def test_folded_execution_bit_identical(self, name):
        """Reshape and zero-padding do not touch values: the folded tree
        executes bit-identically, static and dynamic, ref and pallas."""
        cfg, params, x = _setup(name)
        eng = EngineConfig(quant="w8a8", backend="ref")
        qparams = eng_lib.quantize_params(params, eng)
        prog = compiler.compile_calibrated(cfg, params, [x])
        folded = passes.fold_weight_layouts(prog.graph, qparams)
        a = np.array(compiler.execute(prog, qparams, x, eng))
        b = np.array(compiler.execute(prog, folded, x, eng))
        np.testing.assert_array_equal(a, b)
        engp = EngineConfig(quant="w8a8", backend="pallas")
        ap = np.array(compiler.execute(prog, qparams, x, engp))
        bp = np.array(compiler.execute(prog, folded, x, engp))
        np.testing.assert_array_equal(ap, bp)

    def test_folded_float_and_dynamic_paths(self):
        cfg, params, x = _setup("mobilenetv1")
        eng = EngineConfig(quant="none", backend="ref")
        prog = compiler.compile_cnn(cfg)
        folded = passes.fold_weight_layouts(prog.graph, params)
        a = np.array(compiler.execute(prog, params, x, eng))
        b = np.array(compiler.execute(prog, folded, x, eng))
        np.testing.assert_array_equal(a, b)

    def test_folding_idempotent(self):
        cfg, params, x = _setup("mobilenetv2")
        g = compiler.build_graph(cfg)
        once = passes.fold_weight_layouts(g, params)
        twice = passes.fold_weight_layouts(g, once)
        for a, b in zip(jax.tree_util.tree_leaves(once),
                        jax.tree_util.tree_leaves(twice)):
            assert a is b

    def test_baseline_engine_unfolds_dwc(self):
        """The dense-diagonal DWC baseline works on true channels: folded
        (pre-padded) weights still execute correctly there."""
        cfg, params, x = _setup("mobilenetv1")
        eng = eng_lib.baseline_engine()
        qparams = eng_lib.quantize_params(params, eng)
        prog = compiler.compile_calibrated(cfg, params, [x])
        folded = passes.fold_weight_layouts(prog.graph, qparams)
        a = np.array(compiler.execute(prog, qparams, x, eng))
        b = np.array(compiler.execute(prog, folded, x, eng))
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)

    def test_serving_engine_folds_transparently(self):
        """CNNServeEngine binds folded params: results match the jitted
        unfolded program execution bitwise (jit-vs-jit, since XLA's fusion
        can flip requant-boundary rounding against the eager run)."""
        from repro.serve.cnn_engine import CNNServeEngine
        cfg, params, x = _setup("mobilenetv2")
        eng = EngineConfig(quant="w8a8", backend="ref")
        engine = CNNServeEngine(eng, wave_size=2)
        engine.register(cfg, params, calib_batches=[x])
        imgs = np.asarray(x)
        got = engine.infer(cfg.name, imgs)
        prog = engine.program_for(cfg.name)
        qparams = eng_lib.quantize_params(params, eng)
        want = np.array(jax.jit(
            lambda p, im: compiler.execute(prog, p, im, eng))(qparams, x))
        np.testing.assert_array_equal(got, want)
        m = engine._models[cfg.name]
        assert m.folded is not None and m.folded[0] is prog


# ---------------------------------------------------------------------------
# Golden dynamic-vs-static parity across the whole zoo, on both backends
# ---------------------------------------------------------------------------

# Max |static - dynamic| logit gap, as a fraction of max |dynamic logit|
# (absolute logit magnitudes at random init vary by orders of magnitude
# across the zoo, so the bound is relative).  Values are ~2.5x the measured
# gap at seed 0, hw=32: the requant-rounding drift of each model's depth /
# branch structure.  A regression that breaks the static plan (wrong scale,
# dropped fold, misrouted epilogue) blows far past these.
GOLDEN_GAP_FRAC = {
    "resnet50": 0.10,
    "resnet152": 0.13,
    "mobilenetv1": 0.20,
    "mobilenetv2": 0.35,
    "efficientnet": 0.18,
    "squeezenet": 0.12,
    "yolov3": 0.12,
    "yolov5n": 0.10,
}


@pytest.fixture(scope="module")
def zoo_golden():
    """Shared per-config setup: one calibration + compile per model, reused
    by both backend parametrizations."""
    cache = {}

    def get(name):
        if name not in cache:
            cfg, params, x = _setup(name)
            prog = compiler.compile_calibrated(cfg, params, [x])
            f = np.array(cnn.cnn_forward(
                params, x, cfg, EngineConfig(quant="none", backend="ref")))
            cache[name] = (cfg, params, x, prog, f)
        return cache[name]

    return get


class TestGoldenZooParity:
    @pytest.mark.parametrize("backend", ["ref", "pallas"])
    @pytest.mark.parametrize("name", sorted(CNN_ZOO))
    def test_dynamic_vs_static_gap_bounded(self, name, backend, zoo_golden):
        """Every zoo config: the compiled static-int8 program tracks the
        eager dynamic w8a8 path within the golden max-logit-gap bound, and
        both correlate with the float reference."""
        cfg, params, x, prog, f = zoo_golden(name)
        eng = EngineConfig(quant="w8a8", backend=backend)
        qparams = eng_lib.quantize_params(params, eng)
        dyn = np.array(cnn.cnn_forward(qparams, x, cfg, eng))
        stat = np.array(compiler.execute(prog, qparams, x, eng))
        assert np.isfinite(stat).all() and np.isfinite(dyn).all()
        gap = np.max(np.abs(stat - dyn))
        bound = GOLDEN_GAP_FRAC[name] * np.max(np.abs(dyn))
        assert gap <= bound, (name, backend, gap, bound)
        assert np.corrcoef(f.ravel(), stat.ravel())[0, 1] > 0.9
