"""Epilogue fusion: the graph rewrite, fused-kernel parity, and the
contention-aware slack leveling.

The contract under test:

  * `passes.fuse_epilogues` collapses every single-consumer Conv/DWC ->
    {residual Add, avg/global/max pool} chain into ONE fused node, and the
    rewritten graph is a valid renumbered topological op list;
  * fused execution is BIT-IDENTICAL to the unfused program on the static
    int8 path (the kernels quantize-dequantize in-register at the absorbed
    edges' scales) and within golden tolerance on the dynamic f32 path,
    across the CNN zoo x {ref, pallas} and under random property configs;
  * `level_schedule(policy="slack")` produces valid levelings that never
    raise the worst per-level same-unit op count above ASAP's and never
    lower per-level engine occupancy below ASAP's;
  * the launch accounting behind the serving benchmark: kernel dispatches
    per ResNet-style image drop >= 25% after fusion.
"""
import dataclasses

import numpy as np
import pytest
import jax
import jax.numpy as jnp

try:
    from hypothesis import given, settings, strategies as st
except ImportError:                       # offline container
    from _hypothesis_compat import given, settings, strategies as st

from repro import compiler
from repro.compiler import passes
from repro.compiler.graph import (AddOp, ConcatOp, ConvOp, DwcOp, Epilogue,
                                  Graph, InputOp, LinearOp, PoolOp)
from repro.configs.cnn_zoo import CNN_ZOO
from repro.core import engine as eng_lib
from repro.core.config import CNNConfig, ConvSpec as C, EngineConfig
from repro.models import cnn
from repro.models.params import init_params

# fused-chain-bearing kinds first so the shim's prefix sampling hits them
KINDS = ("bottleneck", "inverted", "conv", "pool", "dwsep", "fire")


def _stage(kind: str, out_ch: int, stride: int) -> C:
    if kind == "pool":
        return C("pool", kernel=2, stride=2)
    if kind == "inverted":
        return C(kind, out_ch=out_ch, kernel=3, stride=stride, repeat=1,
                 expand=2)
    return C(kind, out_ch=out_ch, kernel=3, stride=stride, repeat=1)


def _random_cfg(kinds, stem_ch: int, out_ch: int, stride: int) -> CNNConfig:
    stages = tuple(_stage(k, out_ch, stride) for k in kinds)
    name = f"fuse_{'-'.join(kinds)}_{stem_ch}_{out_ch}_{stride}"
    return CNNConfig(name=name, input_hw=32, input_ch=3, stem_kernel=3,
                     stem_stride=2, stem_ch=stem_ch, stages=stages,
                     num_classes=8)


def _setup(cfg: CNNConfig, batch: int = 2, seed: int = 0):
    params = init_params(cnn.cnn_schema(cfg), jax.random.PRNGKey(seed))
    x = jnp.asarray(np.random.default_rng(0).normal(
        size=(batch, cfg.input_hw, cfg.input_hw, cfg.input_ch)
    ).astype(np.float32) * 0.5)
    return params, x


# ---------------------------------------------------------------------------
# The rewrite itself
# ---------------------------------------------------------------------------

class TestFuseEpilogues:
    def test_resnet_chains_collapse(self):
        """Every bottleneck add, the stem max-pool tail and the GAP tail
        fuse; the rewritten graph has no standalone adds left."""
        g = compiler.build_graph(CNN_ZOO["resnet50"])
        fg, _ = compiler.fuse_epilogues(g)
        s = compiler.fusion_stats(fg)
        assert s["misc_adds"] == 0
        assert s["fused_adds"] == 16              # 3+4+6+3 bottlenecks
        assert s["fused_pools"] == 2              # stem->maxpool + add->GAP
        assert fg.count(PoolOp) == 0
        # valid renumbered topological graph
        assert all(n.id == i for i, n in enumerate(fg.nodes))
        for n in fg.nodes:
            assert all(i < n.id for i in n.inputs)
        compiler.validate_schedule(fg, compiler.level_schedule(fg))

    def test_resnet_launch_drop_at_least_25_percent(self):
        """The acceptance gate: kernel dispatches per ResNet-style image
        drop >= 25% (fused chains execute as single launches)."""
        for name in ("resnet50", "resnet152"):
            g = compiler.build_graph(CNN_ZOO[name])
            fg, _ = compiler.fuse_epilogues(g)
            unf = compiler.launch_count(g)
            fus = compiler.launch_count(fg)
            assert 1.0 - fus / unf >= 0.25, (name, fus, unf)
            st = compiler.fusion_stats(fg)
            assert st["materialized_edges"] < \
                compiler.fusion_stats(g)["materialized_edges"]

    def test_residual_operand_is_last_input(self):
        g = compiler.build_graph(CNN_ZOO["resnet50"])
        fg, _ = compiler.fuse_epilogues(g)
        for n in fg.nodes:
            if getattr(n, "epilogue", None) is not None and n.epilogue.add:
                assert len(n.inputs) == 2
                assert isinstance(n, (ConvOp, DwcOp))

    def test_multi_consumer_edges_do_not_fuse(self):
        """A conv whose output feeds two consumers keeps its launch: the
        fire squeeze conv (feeding both expand convs) never fuses."""
        g = compiler.build_graph(CNN_ZOO["squeezenet"])
        fg, _ = compiler.fuse_epilogues(g)
        # all fire-module convs survive; only the stem->maxpool chain fuses
        assert compiler.fusion_stats(fg)["fused_ops"] == 1
        stem = fg.nodes[1]
        assert isinstance(stem, ConvOp) and stem.first_layer
        assert stem.epilogue is not None and stem.epilogue.pool == "max"

    def test_scales_remap_and_interiors_baked(self):
        cfg = dataclasses.replace(CNN_ZOO["resnet50"], input_hw=32)
        params, x = _setup(cfg)
        g = compiler.build_graph(cfg)
        scales = compiler.calibrate(g, params, [x], cfg)
        fg, fscales = compiler.fuse_epilogues(g, scales)
        assert set(fscales) == {n.id for n in fg.nodes}
        for n in fg.nodes:
            ep = getattr(n, "epilogue", None)
            if ep is None:
                continue
            assert ep.mid_scale > 0.0
            if ep.add and ep.pool != "none":
                assert ep.add_scale > 0.0
            if ep.pool == "max":
                # scale-preserving tail: output edge inherits pre-pool scale
                pre = ep.add_scale if ep.add else ep.mid_scale
                assert fscales[n.id] == pre

    def test_dynamic_program_cache_distinguishes_fuse_flag(self):
        cfg = dataclasses.replace(CNN_ZOO["squeezenet"], input_hw=32)
        fused = compiler.compile_cnn(cfg)
        unfused = compiler.compile_cnn(cfg, fuse=False)
        assert fused.graph is not unfused.graph
        assert compiler.fusion_stats(fused.graph)["fused_ops"] > 0
        assert compiler.fusion_stats(unfused.graph)["fused_ops"] == 0
        # and the cache returns the right one on re-request
        assert compiler.compile_cnn(cfg).graph is fused.graph
        assert compiler.compile_cnn(cfg, fuse=False).graph is unfused.graph

    def test_dwc_chain_fuses(self):
        """A hand-built dwc -> add -> global pool chain fuses into the DWC
        node (the engine the paper extends for depthwise models)."""
        g = Graph(nodes=(
            InputOp(0, ()),
            DwcOp(1, (0,), w=("wd",), b=("bd",), act="relu"),
            ConvOp(2, (0,), w=("wp",), b=("bp",)),
            AddOp(3, (1, 2), act="relu"),
            PoolOp(4, (3,), pool="global"),
            LinearOp(5, (4,), w=("head_w",), b=("head_b",)),
        ), output=5)
        fg, _ = compiler.fuse_epilogues(g)
        fused = [n for n in fg.nodes
                 if getattr(n, "epilogue", None) is not None]
        assert len(fused) == 1 and isinstance(fused[0], DwcOp)
        ep = fused[0].epilogue
        assert ep.add and ep.add_act == "relu" and ep.pool == "global"
        assert len(fg.nodes) == 4                # input, conv, fused, head
        compiler.validate_schedule(fg, compiler.level_schedule(fg))

    def test_per_channel_residual_edge_collapses(self):
        """An edge a fused DwcOp consumes as its RESIDUAL operand is not a
        channelwise-consumed edge: under per-channel calibration its scale
        must collapse to the per-tensor max (the epilogue's residual add
        carries a scalar scale), and the fused program must execute."""
        from repro.compiler.executor import _finish_program

        g = Graph(nodes=(
            InputOp(0, ()),
            ConvOp(1, (0,), w=("wp",), b=("bp",)),
            DwcOp(2, (1,), w=("wd",), b=("bd",), act="relu"),
            AddOp(3, (2, 1), act="relu"),         # conv1 consumed twice:
            LinearOp(4, (3,), w=("head_w",)),     # dwc input AND residual
        ), output=4)
        c = 8
        rng = np.random.default_rng(0)
        params = {
            "wp": jnp.asarray(rng.normal(size=(1, 1, c, c)),
                              jnp.float32) * 0.3,
            "bp": jnp.zeros((c,), jnp.float32),
            "wd": jnp.asarray(rng.normal(size=(3, 3, c)), jnp.float32) * 0.3,
            "bd": jnp.zeros((c,), jnp.float32),
            "head_w": jnp.asarray(rng.normal(size=(c, 4)), jnp.float32) * 0.3,
        }
        x = jnp.asarray(rng.normal(size=(2, 8, 8, c)), jnp.float32) * 0.5
        scales = compiler.calibrate(g, params, [x], None,
                                    granularity="per_channel")
        fg, fscales = compiler.fuse_epilogues(g, scales)
        fused = [n for n in fg.nodes
                 if getattr(n, "epilogue", None) is not None]
        assert len(fused) == 1 and fused[0].epilogue.add
        prog = _finish_program(fg, None, fscales, True,
                               granularity="per_channel")
        # conv1's edge feeds the fused DwcOp as data AND residual: scalar
        conv_id = next(n.id for n in fg.nodes
                       if isinstance(n, ConvOp))
        assert isinstance(prog.plan.out_scale[conv_id], float)
        eng = EngineConfig(quant="w8a8", backend="ref")
        qp = eng_lib.quantize_params(params, eng)
        out = compiler.execute(prog, qp, x, eng)
        assert np.isfinite(np.array(out)).all()
        # and matches the unfused per-channel program bitwise
        pu = _finish_program(g, None, scales, True,
                             granularity="per_channel")
        np.testing.assert_array_equal(
            np.array(out), np.array(compiler.execute(pu, qp, x, eng)))

    def test_idempotent_on_fused_graphs(self):
        g = compiler.build_graph(CNN_ZOO["resnet50"])
        fg, _ = compiler.fuse_epilogues(g)
        fg2, _ = compiler.fuse_epilogues(fg)
        assert fg2 is fg or len(fg2.nodes) == len(fg.nodes)


# ---------------------------------------------------------------------------
# Execution parity: fused == unfused (bitwise int8 / golden-tolerance f32)
# ---------------------------------------------------------------------------

class TestFusedExecutionParity:
    @settings(deadline=None)
    @given(kinds=st.lists(st.sampled_from(KINDS), min_size=1, max_size=3),
           out_ch=st.sampled_from([8, 16]),
           stride=st.sampled_from([1, 2]))
    def test_static_int8_bit_identical_property(self, kinds, out_ch, stride):
        """Random configs: the fused static program's logits match the
        unfused program's bit for bit on the ref backend."""
        cfg = _random_cfg(kinds, 4, out_ch, stride)
        params, x = _setup(cfg)
        eng = EngineConfig(quant="w8a8", backend="ref")
        qparams = eng_lib.quantize_params(params, eng)
        fused = compiler.compile_calibrated(cfg, params, [x])
        unfused = compiler.compile_calibrated(cfg, params, [x], fuse=False)
        a = np.array(compiler.execute(fused, qparams, x, eng))
        b = np.array(compiler.execute(unfused, qparams, x, eng))
        np.testing.assert_array_equal(a, b)

    @settings(deadline=None)
    @given(kinds=st.lists(st.sampled_from(KINDS), min_size=1, max_size=3),
           out_ch=st.sampled_from([8, 16]))
    def test_dynamic_f32_parity_property(self, kinds, out_ch):
        cfg = _random_cfg(kinds, 4, out_ch, 1)
        params, x = _setup(cfg)
        eng = EngineConfig(quant="none", backend="ref")
        a = np.array(compiler.execute(compiler.compile_cnn(cfg),
                                      params, x, eng))
        b = np.array(compiler.execute(compiler.compile_cnn(cfg, fuse=False),
                                      params, x, eng))
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("backend", ["ref", "pallas"])
    @pytest.mark.parametrize("name", sorted(CNN_ZOO))
    def test_zoo_static_bit_identical(self, name, backend, fusion_golden):
        """Whole zoo x both backends: fused static int8 execution is
        bit-identical to the unfused program (the in-register qdq points
        reproduce the unfused dataflow exactly)."""
        cfg, params, x, fused, unfused = fusion_golden(name)
        eng = EngineConfig(quant="w8a8", backend=backend)
        qparams = eng_lib.quantize_params(params, eng)
        a = np.array(compiler.execute(fused, qparams, x, eng))
        b = np.array(compiler.execute(unfused, qparams, x, eng))
        assert np.isfinite(a).all()
        np.testing.assert_array_equal(a, b)

    @pytest.mark.parametrize("name", ["resnet50", "mobilenetv2"])
    def test_zoo_dynamic_pallas_tolerance(self, name):
        """Dynamic (per-call quant) path on the fused pallas kernels stays
        within golden tolerance of the unfused dynamic program."""
        cfg = dataclasses.replace(CNN_ZOO[name], input_hw=32)
        params, x = _setup(cfg)
        eng = EngineConfig(quant="w8a8", backend="pallas")
        qparams = eng_lib.quantize_params(params, eng)
        a = np.array(compiler.execute(compiler.compile_cnn(cfg),
                                      qparams, x, eng))
        b = np.array(compiler.execute(compiler.compile_cnn(cfg, fuse=False),
                                      qparams, x, eng))
        gap = np.max(np.abs(a - b))
        assert gap <= 0.05 * np.max(np.abs(b)) + 1e-6, gap

    def test_fused_static_jits_and_schedules(self):
        """Fused programs jit and execute bit-identically scheduled vs
        sequential (the executor parity harness covers fused nodes too)."""
        cfg = dataclasses.replace(CNN_ZOO["resnet50"], input_hw=32)
        params, x = _setup(cfg)
        eng = EngineConfig(quant="w8a8", backend="ref")
        qparams = eng_lib.quantize_params(params, eng)
        prog = compiler.compile_calibrated(cfg, params, [x])
        assert compiler.fusion_stats(prog.graph)["fused_ops"] > 0
        seq = dataclasses.replace(prog, schedule=None)
        a = np.array(compiler.execute(prog, qparams, x, eng))
        b = np.array(compiler.execute(seq, qparams, x, eng))
        np.testing.assert_array_equal(a, b)
        # jit-vs-jit (XLA fusion can flip requant-boundary rounding against
        # the eager run, like the folding suite notes): scheduled and
        # sequential traces still agree bitwise
        ja = np.array(jax.jit(
            lambda p, im: compiler.execute(prog, p, im, eng))(qparams, x))
        jb = np.array(jax.jit(
            lambda p, im: compiler.execute(seq, p, im, eng))(qparams, x))
        np.testing.assert_array_equal(ja, jb)
        assert np.isfinite(ja).all()

    def test_dwc_fused_chain_executes(self):
        """The hand-built dwc->add->GAP chain runs fused on both backends
        and matches the unfused graph bitwise (static int8)."""
        from repro.compiler.executor import _finish_program

        unfused = Graph(nodes=(
            InputOp(0, ()),
            DwcOp(1, (0,), w=("wd",), b=("bd",), act="relu"),
            ConvOp(2, (0,), w=("wp",), b=("bp",)),
            AddOp(3, (1, 2), act="relu"),
            PoolOp(4, (3,), pool="global"),
            LinearOp(5, (4,), w=("head_w",), b=("head_b",)),
        ), output=5)
        c = 8
        rng = np.random.default_rng(0)
        params = {
            "wd": jnp.asarray(rng.normal(size=(3, 3, c)), jnp.float32) * 0.3,
            "bd": jnp.zeros((c,), jnp.float32),
            "wp": jnp.asarray(rng.normal(size=(1, 1, c, c)),
                              jnp.float32) * 0.3,
            "bp": jnp.zeros((c,), jnp.float32),
            "head_w": jnp.asarray(rng.normal(size=(c, 4)), jnp.float32) * 0.3,
            "head_b": jnp.zeros((4,), jnp.float32),
        }
        x = jnp.asarray(rng.normal(size=(2, 8, 8, c)), jnp.float32) * 0.5
        scales = compiler.calibrate(unfused, params, [x], None)
        fg, fscales = compiler.fuse_epilogues(unfused, scales)
        pu = _finish_program(unfused, None, scales, True)
        pf = _finish_program(fg, None, fscales, True)
        for backend in ("ref", "pallas"):
            eng = EngineConfig(quant="w8a8", backend=backend)
            qp = eng_lib.quantize_params(params, eng)
            a = np.array(compiler.execute(pf, qp, x, eng))
            b = np.array(compiler.execute(pu, qp, x, eng))
            np.testing.assert_array_equal(a, b)

    def test_serving_engine_serves_fused_programs(self):
        """CNNServeEngine binds fused programs from the ProgramCache by
        default, and its results match direct fused execution."""
        from repro.serve.cnn_engine import CNNServeEngine

        cfg = dataclasses.replace(CNN_ZOO["resnet50"], input_hw=32)
        params, x = _setup(cfg)
        eng = EngineConfig(quant="w8a8", backend="ref")
        engine = CNNServeEngine(eng, wave_size=2)
        engine.register(cfg, params, calib_batches=[x])
        got = engine.infer(cfg.name, np.asarray(x))
        prog = engine.program_for(cfg.name)
        assert compiler.fusion_stats(prog.graph)["fused_ops"] > 0
        qparams = eng_lib.quantize_params(params, eng)
        want = np.array(jax.jit(
            lambda p, im: compiler.execute(prog, p, im, eng))(
                compiler.fold_weight_layouts(prog.graph, qparams), x))
        np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# Slack (contention-aware) leveling
# ---------------------------------------------------------------------------

def _max_unit_width(g, sched):
    return sched.stats["max_unit_width"]


class TestSlackLeveling:
    @settings(deadline=None)
    @given(kinds=st.lists(st.sampled_from(KINDS), min_size=1, max_size=4),
           stem_ch=st.sampled_from([4, 8]),
           out_ch=st.sampled_from([8, 16]),
           stride=st.sampled_from([1, 2]))
    def test_valid_and_never_worse_than_asap(self, kinds, stem_ch, out_ch,
                                             stride):
        """Property: on fused and unfused random graphs, slack levelings
        validate, keep the critical-path length, never raise the worst
        same-unit width above ASAP, and never lower engine occupancy."""
        g = compiler.build_graph(_random_cfg(kinds, stem_ch, out_ch, stride))
        for gg in (g, compiler.fuse_epilogues(g)[0]):
            a = compiler.level_schedule(gg, "asap")
            s = compiler.level_schedule(gg, "slack")
            compiler.validate_schedule(gg, s)
            assert s.n_levels == a.n_levels
            assert _max_unit_width(gg, s) <= _max_unit_width(gg, a)
            assert (compiler.engine_occupancy(gg, s)["occupancy"]
                    >= compiler.engine_occupancy(gg, a)["occupancy"] - 1e-12)

    def test_zoo_slack_occupancy_at_least_asap(self):
        for name, cfg in CNN_ZOO.items():
            g, _ = compiler.fuse_epilogues(compiler.build_graph(cfg))
            a = compiler.level_schedule(g, "asap")
            s = compiler.level_schedule(g, "slack")
            compiler.validate_schedule(g, s)
            assert _max_unit_width(g, s) <= _max_unit_width(g, a), name
            assert (compiler.engine_occupancy(g, s)["occupancy"]
                    >= compiler.engine_occupancy(g, a)["occupancy"]
                    - 1e-12), name

    def test_slack_levels_down_contention(self):
        """The case the policy exists for: two independent convs next to a
        three-op MISC chain, all joining at the end.  ASAP stacks both
        convs in the first level (Conv PE contention 2) and leaves the
        later levels MISC-only; slack spreads one conv into the idle
        window, halving the worst same-unit width and raising occupancy."""
        g = Graph(nodes=(
            InputOp(0, ()),
            AddOp(1, (0, 0)),                        # 3-op MISC chain
            AddOp(2, (1, 1)),
            AddOp(3, (2, 2)),
            ConvOp(4, (0,), w=("a",)),               # independent convs:
            ConvOp(5, (0,), w=("b",)),               # slack window [1, 3]
            ConcatOp(6, (3, 4, 5)),
        ), output=6)
        a = compiler.level_schedule(g, "asap")
        s = compiler.level_schedule(g, "slack")
        compiler.validate_schedule(g, s)
        assert _max_unit_width(g, a) == 2            # conv4+conv5 co-leveled
        assert _max_unit_width(g, s) == 1            # spread across slack
        assert (compiler.engine_occupancy(g, s)["occupancy"]
                > compiler.engine_occupancy(g, a)["occupancy"])

    def test_slack_execution_bit_identical(self):
        """Slack-scheduled static execution matches sequential bitwise."""
        cfg = dataclasses.replace(CNN_ZOO["resnet50"], input_hw=32)
        params, x = _setup(cfg)
        eng = EngineConfig(quant="w8a8", backend="ref")
        qparams = eng_lib.quantize_params(params, eng)
        prog = compiler.compile_calibrated(cfg, params, [x], policy="slack")
        assert prog.schedule is not None
        seq = dataclasses.replace(prog, schedule=None)
        a = np.array(compiler.execute(prog, qparams, x, eng))
        b = np.array(compiler.execute(seq, qparams, x, eng))
        np.testing.assert_array_equal(a, b)

    def test_serving_engine_accepts_slack_policy(self):
        from repro.serve.cnn_engine import CNNServeEngine

        cfg = dataclasses.replace(CNN_ZOO["squeezenet"], input_hw=32)
        params, x = _setup(cfg)
        eng = EngineConfig(quant="w8a8", backend="ref")
        engine = CNNServeEngine(eng, wave_size=2, schedule_policy="slack")
        engine.register(cfg, params, calib_batches=[x])
        out = engine.infer(cfg.name, np.asarray(x))
        assert np.isfinite(out).all()
        prog = engine.program_for(cfg.name)
        compiler.validate_schedule(prog.graph, prog.schedule)


# ---------------------------------------------------------------------------
# Satellites: rope-table cache, precomputed scale arrays, perf model
# ---------------------------------------------------------------------------

class TestSatellites:
    def test_rope_tables_cached_across_executes(self):
        from repro import configs
        from repro.compiler import executor as ex
        from repro.models import transformer as T

        arch = configs.reduced(configs.get_arch("qwen2-1.5b"))
        params = init_params(T.lm_schema(arch), jax.random.PRNGKey(0))
        toks = jnp.asarray(np.random.default_rng(0).integers(
            0, arch.vocab_size, (2, 10)).astype(np.int32))
        eng = EngineConfig(quant="none", backend="ref")
        prog = compiler.compile_lm(arch)
        ex._rope_tables.clear()
        compiler.execute(prog, params, toks, eng)
        entries = compiler.rope_table_stats()["entries"]
        assert entries >= 1
        t0 = ex._rope_tables[next(iter(ex._rope_tables))]
        compiler.execute(prog, params, toks, eng)
        # second execute reuses the SAME table objects (no rebuild)
        assert ex._rope_tables[next(iter(ex._rope_tables))][0] is t0[0]
        assert compiler.rope_table_stats()["entries"] == entries
        # bounded: sweeping many shapes cannot grow it past capacity
        for l in range(4, 4 + ex._ROPE_TABLE_CAPACITY + 8):
            ex._rope_table(1, l, arch.head_dim, arch.rope_theta)
        assert (compiler.rope_table_stats()["entries"]
                <= ex._ROPE_TABLE_CAPACITY)

    def test_rope_tables_never_cache_tracers(self):
        from repro import configs
        from repro.compiler import executor as ex
        from repro.models import transformer as T

        arch = configs.reduced(configs.get_arch("qwen2-1.5b"))
        params = init_params(T.lm_schema(arch), jax.random.PRNGKey(0))
        toks = jnp.asarray(np.random.default_rng(0).integers(
            0, arch.vocab_size, (2, 7)).astype(np.int32))
        eng = EngineConfig(quant="none", backend="ref")
        prog = compiler.compile_lm(arch)
        ex._rope_tables.clear()
        jax.jit(lambda p, t: compiler.execute(prog, p, t, eng))(params, toks)
        for cos, sin in ex._rope_tables.values():
            assert not isinstance(cos, jax.core.Tracer)

    def test_plan_precomputes_scale_arrays(self):
        cfg = dataclasses.replace(CNN_ZOO["mobilenetv2"], input_hw=32)
        params, x = _setup(cfg)
        prog = compiler.compile_calibrated(cfg, params, [x])
        plan = prog.plan
        for n in prog.graph.nodes:
            if plan.emit_int8[n.id]:
                arr = plan.scale_arr[n.id]
                assert arr.dtype == jnp.float32
                np.testing.assert_allclose(
                    np.asarray(arr).ravel(),
                    np.asarray(plan.out_scale[n.id],
                               dtype=np.float32).ravel())

    def test_cnn_node_times_cover_fused_graph(self):
        from benchmarks import perf_model as pm

        for name in ("resnet50", "mobilenetv2"):
            cfg = CNN_ZOO[name]
            g, _ = compiler.fuse_epilogues(compiler.build_graph(cfg))
            times = pm.cnn_node_times(g, cfg)
            assert set(times) == {n.id for n in g.nodes}
            assert all(t >= 0.0 for t in times.values())
            tw = pm.cnn_busy_fractions(cfg, policy="slack")
            assert 0.0 < tw["occupancy"] <= 1.0
            # the fused graph's modeled span is never worse than unfused
            tw_unfused = pm.cnn_busy_fractions(cfg, policy="slack",
                                               fuse=False)
            assert tw["span_s"] <= tw_unfused["span_s"] + 1e-12

    def test_bench_payload_shape(self):
        from benchmarks import serve_cnn as sc

        zoo = sc.zoo_fusion_occupancy()
        assert set(zoo) == set(CNN_ZOO)
        for name, z in zoo.items():
            assert z["launches_fused"] <= z["launches_unfused"]
            assert (z["occupancy"]["slack"]
                    >= z["occupancy"]["asap"] - 1e-12), name
        assert zoo["resnet50"]["launch_reduction"] >= 0.25


@pytest.fixture(scope="module")
def fusion_golden():
    """One calibration + fused/unfused compile per model, shared across
    backend parametrizations."""
    cache = {}

    def get(name):
        if name not in cache:
            cfg = dataclasses.replace(CNN_ZOO[name], input_hw=32)
            params, x = _setup(cfg)
            fused = compiler.compile_calibrated(cfg, params, [x])
            unfused = compiler.compile_calibrated(cfg, params, [x],
                                                  fuse=False)
            assert compiler.fusion_stats(fused.graph)["fused_ops"] > 0, name
            cache[name] = (cfg, params, x, fused, unfused)
        return cache[name]

    return get
