"""INT8 quantization invariants (hypothesis property tests)."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp
try:
    from hypothesis import given, settings, strategies as st
except ImportError:                       # offline container
    from _hypothesis_compat import given, settings, strategies as st

from repro.core import engine as eng_lib
from repro.core.config import EngineConfig
from repro.core.quant import (Calibrator, QTensor, fake_quant, quantize,
                              quantize_act_dynamic, requantize)


@settings(deadline=None, max_examples=30)
@given(n=st.integers(2, 64), scale=st.floats(0.01, 100.0))
def test_roundtrip_error_bound(n, scale):
    """|x - dq(q(x))| <= scale/2 elementwise (symmetric quant property),
    up to f32 rounding."""
    rng = np.random.default_rng(n)
    x = (rng.normal(size=(n, n)) * scale).astype(np.float32)
    qt = quantize(jnp.array(x))
    err = np.abs(np.array(qt.dequant()) - x)
    # x / scale and q * scale are each rounded to f32: together at most one
    # ulp of the largest |x| on top of the exact scale/2 (at n=54,
    # scale=97 the product's rounding alone exceeds scale/2 by 2.5e-6)
    bound = float(qt.scale) / 2 + float(np.spacing(np.abs(x).max()))
    assert err.max() <= bound


@settings(deadline=None, max_examples=20)
@given(k=st.integers(2, 32), n=st.integers(2, 32))
def test_per_channel_tighter_than_per_tensor(k, n):
    rng = np.random.default_rng(k * 100 + n)
    x = rng.normal(size=(k, n)).astype(np.float32)
    x[:, 0] *= 100.0                      # one hot channel
    per_t = np.abs(np.array(fake_quant(jnp.array(x))) - x).mean()
    per_c = np.abs(np.array(fake_quant(jnp.array(x), axis=1)) - x).mean()
    assert per_c <= per_t + 1e-6


def test_quantize_range():
    x = jnp.array([[-10.0, 0.0, 10.0]])
    qt = quantize(x)
    assert int(qt.q.min()) >= -127 and int(qt.q.max()) <= 127
    assert int(qt.q[0, 2]) == 127


def test_dynamic_act_per_token():
    x = jnp.array([[1.0, 2.0], [100.0, 200.0]])
    qt = quantize_act_dynamic(x, per_token=True)
    assert qt.scale.shape == (2, 1)
    np.testing.assert_allclose(np.array(qt.dequant()), np.array(x),
                               rtol=0.02)


def test_requantize_matches_manual():
    acc = jnp.array([[1000, -2000]], jnp.int32)
    out = requantize(acc, jnp.float32(0.01), jnp.float32(0.02))
    np.testing.assert_allclose(np.array(out), [[0.2, -0.4]], rtol=1e-6)


def test_calibrator_running_max():
    c = Calibrator()
    c.observe("a", jnp.array([1.0, -3.0]))
    c.observe("a", jnp.array([2.0]))
    assert abs(c.scales()["a"] - 3.0 / 127) < 1e-9


class TestParamTreeQuant:
    def _params(self):
        rng = np.random.default_rng(0)
        return {
            "embed": jnp.array(rng.normal(size=(32, 8)).astype(np.float32)),
            "blocks": [{
                "norm": jnp.zeros((8,), jnp.float32),
                "attn": {"wq": jnp.array(
                    rng.normal(size=(8, 16)).astype(np.float32))},
                "mixer": {"conv_w": jnp.array(
                    rng.normal(size=(4, 8)).astype(np.float32))},
            }],
        }

    def test_quantizes_allowlisted_keys_only(self):
        eng = EngineConfig(quant="w8a8")
        q = eng_lib.quantize_params(self._params(), eng)
        assert isinstance(q["embed"], QTensor)
        assert isinstance(q["blocks"][0]["attn"]["wq"], QTensor)
        # conv_w (DWC taps) and norms stay float
        assert not isinstance(q["blocks"][0]["mixer"]["conv_w"], QTensor)
        assert not isinstance(q["blocks"][0]["norm"], QTensor)

    def test_embed_quantized_per_row(self):
        eng = EngineConfig(quant="w8a8")
        q = eng_lib.quantize_params(self._params(), eng)
        assert q["embed"].scale.shape == (32, 1)
        assert q["blocks"][0]["attn"]["wq"].scale.shape == (1, 16)

    def test_schema_matches_value_structure(self):
        """quantize_schema and quantize_params must produce the same tree
        structure (dry-run abstract args == real args)."""
        from repro.models.params import ParamSpec, abstract_params
        eng = EngineConfig(quant="w8a8")
        schema = {
            "embed": ParamSpec((32, 8), ("tp", None), "embed"),
            "blocks": [{
                "norm": ParamSpec((8,), (None,), "zeros"),
                "attn": {"wq": ParamSpec((8, 16), (None, "tp"))},
                "mixer": {"conv_w": ParamSpec((4, 8), (None, "tp"), "small")},
            }],
        }
        qschema = eng_lib.quantize_schema(schema, eng)
        abs_tree = abstract_params(qschema)
        qvals = eng_lib.quantize_params(self._params(), eng)
        t1 = jax.tree_util.tree_structure(abs_tree)
        t2 = jax.tree_util.tree_structure(qvals)
        assert t1 == t2
        # shapes/dtypes agree leaf-by-leaf
        for a, v in zip(jax.tree_util.tree_leaves(abs_tree),
                        jax.tree_util.tree_leaves(qvals)):
            assert a.shape == v.shape and a.dtype == v.dtype

    def test_w8a8_linear_accuracy(self):
        """End-to-end W8A8 relative error stays small on gaussian data."""
        rng = np.random.default_rng(1)
        x = rng.normal(size=(64, 128)).astype(np.float32)
        w = rng.normal(size=(128, 96)).astype(np.float32) / np.sqrt(128)
        from repro.kernels import ops
        wq = quantize(jnp.array(w), axis=1)
        got = np.array(ops.linear(jnp.array(x), wq, None, "none",
                                  EngineConfig(quant="w8a8", backend="ref")))
        want = x @ w
        rel = np.abs(got - want).mean() / np.abs(want).mean()
        assert rel < 0.02
