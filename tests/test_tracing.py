"""Host spans of the serving path (serve/tracing.py) and the engine scopes
the executor puts on every node: the disabled tracer records nothing, a
pump records wave.fill / wave.to_device / wave.launch / wave.readback per
wave, and the scopes reach the lowered program without changing it."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import compiler
from repro.compiler import executor as ex
from repro.compiler.schedule import engine_unit
from repro.core import engine as eng_lib
from repro.core.config import CNNConfig, ConvSpec, EngineConfig
from repro.models import cnn
from repro.models.params import init_params
from repro.serve.cnn_engine import CNNServeEngine
from repro.serve.tracing import DISABLED, SpanRecorder

HW = 32
W8 = EngineConfig(quant="w8a8", backend="ref")
WAVE_SPANS = ("wave.fill", "wave.to_device", "wave.launch", "wave.readback")


def _images(n, seed=0):
    return np.random.default_rng(seed).normal(
        size=(n, HW, HW, 3)).astype(np.float32) * 0.5


@pytest.fixture(scope="module")
def served():
    """A tiny CNN at 32 px (Low-Channel stem, an inverted residual block,
    a conv) on the ref backend with a static int8 calibration, and four
    images."""
    cfg = CNNConfig(name="tiny", input_hw=HW, input_ch=3, stem_kernel=3,
                    stem_stride=2, stem_ch=8, num_classes=8, stages=(
                        ConvSpec("inverted", out_ch=8, kernel=3, stride=1,
                                 repeat=1, expand=2),
                        ConvSpec("conv", out_ch=16, kernel=3, stride=2,
                                 repeat=1)))
    params = init_params(cnn.cnn_schema(cfg), jax.random.PRNGKey(0))
    engine = CNNServeEngine(W8, wave_size=2)
    engine.register(cfg, params, calib_batches=[jnp.asarray(_images(2, 7))])
    return engine, cfg, params, _images(4)


# ---------------------------------------------------------------------------
# The recorder
# ---------------------------------------------------------------------------

def test_disabled_recorder_records_nothing():
    a, b = DISABLED.span("wave.fill"), DISABLED.span("wave.launch")
    assert a is b                         # one shared no-op context
    with a:
        pass
    assert DISABLED.spans == []


def test_enabled_recorder_records_wall_clock_triples():
    rec = SpanRecorder()
    t0 = time.time_ns()
    with rec.span("outer"):
        with rec.span("inner"):
            pass
    with pytest.raises(ValueError):
        with rec.span("raised"):
            raise ValueError
    t1 = time.time_ns()
    assert [n for n, _, _ in rec.spans] == ["inner", "outer", "raised"]
    (_, si, di), (_, so, do), (_, sr, dr) = rec.spans
    assert t0 <= so <= si and si + di <= so + do <= sr
    assert sr + dr <= t1 and min(di, do, dr) >= 0


def test_engine_tracer_defaults_to_disabled_and_pumps_record_nothing(served):
    engine, cfg, _, images = served
    assert engine.tracer is DISABLED
    for _ in range(2):
        for img in images:
            engine.submit(cfg.name, img)
        assert len(engine.pump()) == 4
    assert DISABLED.spans == []


def test_pump_records_four_disjoint_spans_per_wave(served):
    engine, cfg, _, images = served
    engine.tracer = rec = SpanRecorder()
    try:
        waves0 = engine.wave_stats.waves
        for img in images:
            engine.submit(cfg.name, img)
        assert len(engine.pump()) == 4
        waves = engine.wave_stats.waves - waves0
    finally:
        engine.tracer = DISABLED
    assert waves == 2
    # launches first, then the response edge reads every execution back
    assert [n for n, _, _ in rec.spans] == [
        "wave.fill", "wave.to_device", "wave.launch",
        "wave.fill", "wave.to_device", "wave.launch",
        "wave.readback", "wave.readback"]
    for a, b in zip(rec.spans, rec.spans[1:]):
        assert a[1] + a[2] <= b[1]        # in order and disjoint
    per_wave = {n: [s for s in rec.spans if s[0] == n] for n in WAVE_SPANS}
    for k in range(waves):
        starts = [per_wave[n][k][1] for n in WAVE_SPANS]
        assert starts == sorted(starts)


# ---------------------------------------------------------------------------
# Engine scopes
# ---------------------------------------------------------------------------

def _unscoped(eval_node, n, vals):
    return eval_node(n, vals)


def test_engine_scopes_reach_the_lowered_program(served, monkeypatch):
    engine, cfg, params, images = served
    prog = engine.program_for(cfg.name)
    qparams = eng_lib.quantize_params(params, W8)
    x = jnp.asarray(images[:2])

    def lower_and_run():
        # a fresh function each time, so jit traces it again
        fn = jax.jit(lambda p, im: compiler.execute(prog, p, im, W8))
        return fn.lower(qparams, x), np.asarray(fn(qparams, x))

    lowered, scoped = lower_and_run()
    text = lowered.as_text(debug_info=True)
    units = {engine_unit(n) for n in prog.graph.nodes}
    assert {"conv_pe", "dwc_pe", "low_channel", "mem"} <= units
    for unit in units:
        assert f"/{unit}/" in text, unit
    monkeypatch.setattr(ex, "_eval_scoped", _unscoped)
    bare_lowered, bare = lower_and_run()
    assert "/conv_pe/" not in bare_lowered.as_text(debug_info=True)
    # without locations the two programs are the same text, op for op
    assert bare_lowered.as_text() == lowered.as_text()
    np.testing.assert_array_equal(scoped, bare)
