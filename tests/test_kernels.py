"""Per-kernel Pallas (interpret=True) vs pure-jnp oracle sweeps."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp
try:
    from hypothesis import given, settings, strategies as st
except ImportError:                       # offline container
    from _hypothesis_compat import given, settings, strategies as st

from repro.core.config import EngineConfig
from repro.core.quant import QTensor, quantize
from repro.kernels import (_epilogue, conv_pe, dwc_pe, low_channel, misc_pe,
                           ops, ref)

PALLAS = EngineConfig(quant="w8a8", backend="pallas")
REF = EngineConfig(quant="w8a8", backend="ref")
FLOAT_PALLAS = EngineConfig(quant="none", backend="pallas")


def _rand_q(rng, shape):
    return rng.integers(-127, 128, shape).astype(np.int8)


# ---------------------------------------------------------------------------
# Conv PE
# ---------------------------------------------------------------------------

class TestConvPE:
    @pytest.mark.parametrize("m,k,n", [(128, 128, 128), (128, 256, 128),
                                       (256, 512, 128), (128, 384, 256)])
    @pytest.mark.parametrize("act", ["none", "relu", "silu"])
    def test_int8_fused_matches_ref(self, rng, m, k, n, act):
        aq, bq = _rand_q(rng, (m, k)), _rand_q(rng, (k, n))
        asc = rng.uniform(0.01, 0.1, (m, 1)).astype(np.float32)
        wsc = rng.uniform(0.01, 0.1, (1, n)).astype(np.float32)
        bias = rng.normal(size=n).astype(np.float32)
        got = conv_pe.matmul_int8_fused(
            jnp.array(aq), jnp.array(bq), jnp.array(asc), jnp.array(wsc),
            jnp.array(bias), act, bm=128, bn=128, bk=128, interpret=True)
        want = ref.matmul_int8_fused(
            jnp.array(aq), jnp.array(bq), jnp.array(asc), jnp.array(wsc),
            jnp.array(bias), act)
        np.testing.assert_allclose(np.array(got), np.array(want),
                                   rtol=1e-5, atol=1e-5)

    def test_int8_accumulation_exact(self, rng):
        """The cascade accumulator must be exact int32 (no fp drift)."""
        m = k = n = 256
        aq, bq = _rand_q(rng, (m, k)), _rand_q(rng, (k, n))
        one_m = jnp.ones((m, 1), jnp.float32)
        one_n = jnp.ones((1, n), jnp.float32)
        got = conv_pe.matmul_int8_fused(
            jnp.array(aq), jnp.array(bq), one_m, one_n, None, "none",
            bm=128, bn=128, bk=128, interpret=True)
        want = aq.astype(np.int64) @ bq.astype(np.int64)
        np.testing.assert_array_equal(np.array(got).astype(np.int64), want)

    def test_int8_requantized_output(self, rng):
        m = k = n = 128
        aq, bq = _rand_q(rng, (m, k)), _rand_q(rng, (k, n))
        asc = np.full((m, 1), 0.02, np.float32)
        wsc = np.full((1, n), 0.03, np.float32)
        got = conv_pe.matmul_int8_fused(
            jnp.array(aq), jnp.array(bq), jnp.array(asc), jnp.array(wsc),
            None, "none", out_scale=0.5, bm=128, bn=128, bk=128,
            interpret=True)
        assert got.dtype == jnp.int8
        want = ref.matmul_int8_fused(
            jnp.array(aq), jnp.array(bq), jnp.array(asc), jnp.array(wsc),
            None, "none", out_scale=jnp.float32(0.5))
        np.testing.assert_array_equal(np.array(got), np.array(want))

    def test_bf16_variant(self, rng):
        m = k = n = 128
        a = rng.normal(size=(m, k)).astype(np.float32)
        b = rng.normal(size=(k, n)).astype(np.float32)
        got = conv_pe.matmul_f_fused(jnp.array(a), jnp.array(b), None, "gelu",
                                     bm=128, bn=128, bk=128, interpret=True)
        want = ref.act_fn("gelu")(a @ b)
        np.testing.assert_allclose(np.array(got), want, rtol=2e-3, atol=2e-3)

    def test_unfused_baseline_same_math(self, rng):
        """XVDPU-analog baseline differs in fusion, not in numerics."""
        x = rng.normal(size=(3, 40)).astype(np.float32)
        w = rng.normal(size=(40, 24)).astype(np.float32)
        wq = quantize(jnp.array(w), axis=1)
        ours = ops.linear(jnp.array(x), wq, None, "relu", REF)
        base = ops.linear(jnp.array(x), wq, None, "relu",
                          EngineConfig(quant="w8a8", baseline=True).resolved())
        np.testing.assert_allclose(np.array(ours), np.array(base),
                                   rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# DWC PE
# ---------------------------------------------------------------------------

class TestDwcPE:
    @pytest.mark.parametrize("k", [3, 5, 7])
    @pytest.mark.parametrize("stride", [1, 2])
    def test_quantized_sweep(self, rng, k, stride):
        x = rng.normal(size=(2, 17, 17, 64)).astype(np.float32)
        w = (rng.normal(size=(k, k, 64)) * 0.2).astype(np.float32)
        b = rng.normal(size=64).astype(np.float32)
        q = quantize(jnp.array(w.reshape(-1, 64)), axis=1)
        wq = QTensor(q.q.reshape(k, k, 64), q.scale)
        got = ops.dwc2d(jnp.array(x), wq, jnp.array(b), stride, "SAME",
                        "relu6", PALLAS)
        want = ops.dwc2d(jnp.array(x), wq, jnp.array(b), stride, "SAME",
                         "relu6", REF)
        np.testing.assert_allclose(np.array(got), np.array(want),
                                   rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("c", [32, 128, 192])
    def test_channel_padding(self, rng, c):
        """Lane alignment (the paper's zero-padded weights) is lossless."""
        x = rng.normal(size=(1, 9, 9, c)).astype(np.float32)
        w = (rng.normal(size=(3, 3, c)) * 0.2).astype(np.float32)
        got = ops.dwc2d(jnp.array(x), jnp.array(w), None, 1, "SAME",
                        "none", FLOAT_PALLAS)
        want = ref.dwc2d(jnp.pad(jnp.array(x), ((0, 0), (1, 1), (1, 1), (0, 0))),
                         jnp.array(w), None, 1)
        np.testing.assert_allclose(np.array(got), np.array(want),
                                   rtol=1e-4, atol=1e-4)

    def test_dwc_against_lax_conv(self, rng):
        import jax.lax as lax
        x = rng.normal(size=(2, 12, 12, 128)).astype(np.float32)
        w = (rng.normal(size=(3, 3, 128)) * 0.2).astype(np.float32)
        got = ops.dwc2d(jnp.array(x), jnp.array(w), None, 1, "SAME", "none",
                        FLOAT_PALLAS)
        want = lax.conv_general_dilated(
            jnp.array(x), jnp.array(w).reshape(3, 3, 1, 128), (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=128)
        np.testing.assert_allclose(np.array(got), np.array(want),
                                   rtol=1e-3, atol=1e-3)

    @pytest.mark.parametrize("k", [2, 4])
    def test_dwc1d_causal(self, rng, k):
        x = rng.normal(size=(2, 24, 96)).astype(np.float32)
        w = rng.normal(size=(k, 96)).astype(np.float32)
        b = rng.normal(size=96).astype(np.float32)
        got = ops.dwc1d_causal(jnp.array(x), jnp.array(w), jnp.array(b),
                               "silu", FLOAT_PALLAS)
        want = ref.dwc1d_causal(jnp.array(x), jnp.array(w), jnp.array(b),
                                "silu")
        np.testing.assert_allclose(np.array(got), np.array(want),
                                   rtol=1e-4, atol=1e-4)

    def test_causality(self, rng):
        """Future timesteps must not affect past outputs."""
        x1 = rng.normal(size=(1, 16, 128)).astype(np.float32)
        x2 = x1.copy()
        x2[:, 10:] += 5.0
        w = rng.normal(size=(4, 128)).astype(np.float32)
        y1 = np.array(ops.dwc1d_causal(jnp.array(x1), jnp.array(w), None,
                                       "none", FLOAT_PALLAS))
        y2 = np.array(ops.dwc1d_causal(jnp.array(x2), jnp.array(w), None,
                                       "none", FLOAT_PALLAS))
        np.testing.assert_allclose(y1[:, :10], y2[:, :10], rtol=1e-6)

    def test_baseline_diagonal_lowering(self, rng):
        """Without the DWC engine, depthwise runs as diagonalized dense conv
        (the paper's low-utilization path) -- same result."""
        x = rng.normal(size=(1, 8, 8, 16)).astype(np.float32)
        w = (rng.normal(size=(3, 3, 16)) * 0.2).astype(np.float32)
        nodwc = EngineConfig(quant="none", backend="ref", use_dwc_engine=False)
        got = ops.dwc2d(jnp.array(x), jnp.array(w), None, 1, "SAME", "none",
                        nodwc, out_dtype=jnp.float32)
        want = ops.dwc2d(jnp.array(x), jnp.array(w), None, 1, "SAME", "none",
                         EngineConfig(quant="none", backend="ref"))
        np.testing.assert_allclose(np.array(got), np.array(want),
                                   rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# Low-Channel Conv Unit
# ---------------------------------------------------------------------------

class TestLowChannel:
    @pytest.mark.parametrize("k,stride,ic,oc", [
        (7, 2, 3, 64), (3, 2, 3, 32), (6, 2, 3, 16), (5, 1, 4, 32)])
    def test_sweep(self, rng, k, stride, ic, oc):
        x = rng.normal(size=(2, 20, 20, ic)).astype(np.float32)
        w = (rng.normal(size=(k, k, ic, oc)) * 0.1).astype(np.float32)
        b = rng.normal(size=oc).astype(np.float32)
        got = low_channel.low_channel_conv(
            jnp.array(x), jnp.array(w), jnp.array(b), stride, "relu",
            interpret=True)
        want = ref.low_channel_conv(jnp.array(x), jnp.array(w), jnp.array(b),
                                    stride, "relu")
        np.testing.assert_allclose(np.array(got), np.array(want),
                                   rtol=1e-4, atol=1e-4)

    def test_matches_general_conv_pe(self, rng):
        """The specialized unit computes the same conv as the general path."""
        x = rng.normal(size=(1, 16, 16, 3)).astype(np.float32)
        w = (rng.normal(size=(7, 7, 3, 32)) * 0.1).astype(np.float32)
        b = rng.normal(size=32).astype(np.float32)
        eng_on = EngineConfig(quant="none", backend="ref")
        eng_off = EngineConfig(quant="none", backend="ref",
                               use_low_channel_unit=False)
        got = ops.first_layer_conv(jnp.array(x), jnp.array(w), jnp.array(b),
                                   2, "SAME", "relu", eng_on)
        want = ops.first_layer_conv(jnp.array(x), jnp.array(w), jnp.array(b),
                                    2, "SAME", "relu", eng_off)
        np.testing.assert_allclose(np.array(got), np.array(want),
                                   rtol=1e-3, atol=1e-3)


# ---------------------------------------------------------------------------
# Row tiling with a halo (the DWC engine and the Low-Channel unit)
# ---------------------------------------------------------------------------

class TestRowTiling:
    """Shapes large enough that the kernels split their output rows over
    several grid steps, each reading its own halo'd row window: every tile
    edge must agree with the untiled ref oracle."""

    @pytest.mark.parametrize("stride", [1, 2])
    def test_dwc2d_tiles_with_residual(self, rng, stride):
        k, c = 3, 128
        hp = 97 if stride == 1 else 193         # 95 (prime) / 96 output rows
        ho = (hp - k) // stride + 1
        assert dwc_pe.row_tile(ho, ho) < ho     # several tiles
        x = jnp.array(_rand_q(rng, (1, hp, hp, c)))
        w = jnp.array(_rand_q(rng, (k, k, c)))
        b = jnp.array(rng.normal(size=c).astype(np.float32))
        wsc = jnp.array(rng.uniform(0.001, 0.01, c).astype(np.float32))
        r = jnp.array(_rand_q(rng, (1, ho, ho, c)))
        got = dwc_pe.dwc2d(x, w, b, stride, "relu6", a_scale=0.02,
                           w_scale=wsc, residual=r, res_scale=0.05,
                           add_act="relu", interpret=True)
        y = ref.dwc2d(x, w, b, stride, "relu6", a_scale=jnp.float32(0.02),
                      w_scale=wsc)
        want = _epilogue.fused_chain(y, residual=r, res_scale=0.05,
                                     add_act="relu")
        np.testing.assert_allclose(np.array(got), np.array(want),
                                   rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("pool", ["none", "max"])
    def test_low_channel_stem_tiles(self, rng, pool):
        """The ResNet stem at 112 px: 7x7/2 conv, 56x56 output, optionally
        with the fused 3x3/2 max pool, whose tiles recompute the conv rows
        their windows share across a tile edge."""
        k, s, ic, oc = 7, 2, 3, 64
        hp = 117
        ho = (hp - k) // s + 1
        pk, ps = (3, 2) if pool == "max" else (0, 0)
        *_, nt = low_channel._tiles(ho, ho, pool, pk, ps)
        assert nt > 1
        x = jnp.array(_rand_q(rng, (1, hp, hp, ic)))
        w = jnp.array(_rand_q(rng, (k, k, ic, oc)))
        b = jnp.array(rng.normal(size=oc).astype(np.float32))
        wsc = jnp.array(rng.uniform(0.001, 0.01, oc).astype(np.float32))
        got = low_channel.low_channel_conv(
            x, w, b, s, "relu", a_scale=0.02, w_scale=wsc, pool=pool,
            pool_kernel=pk, pool_stride=ps, interpret=True)
        y = ref.low_channel_conv(x, w, b, s, "relu",
                                 a_scale=jnp.float32(0.02), w_scale=wsc)
        want = _epilogue.fused_chain(y, pool=pool, pool_kernel=pk,
                                     pool_stride=ps)
        assert got.shape == want.shape
        np.testing.assert_allclose(np.array(got), np.array(want),
                                   rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# MISC core
# ---------------------------------------------------------------------------

class TestMisc:
    @pytest.mark.parametrize("shape", [(7,), (3, 5), (2, 9, 9, 32)])
    def test_add_shapes(self, rng, shape):
        a = rng.normal(size=shape).astype(np.float32)
        b = rng.normal(size=shape).astype(np.float32)
        got = misc_pe.misc_add(jnp.array(a), jnp.array(b), 1.5, -0.5, "relu",
                               interpret=True)
        want = ref.misc_add(jnp.array(a), jnp.array(b), 1.5, -0.5, "relu")
        np.testing.assert_allclose(np.array(got), np.array(want),
                                   rtol=1e-5, atol=1e-5)

    def test_avgpool(self, rng):
        x = rng.normal(size=(2, 8, 8, 128)).astype(np.float32)
        got = misc_pe.avgpool2d(jnp.array(x), 2, 2, interpret=True)
        want = ref.avgpool2d(jnp.array(x), 2, 2)
        np.testing.assert_allclose(np.array(got), np.array(want),
                                   rtol=1e-5, atol=1e-5)

    def test_requantized_add(self, rng):
        a = rng.normal(size=(4, 128)).astype(np.float32)
        b = rng.normal(size=(4, 128)).astype(np.float32)
        got = misc_pe.misc_add(jnp.array(a), jnp.array(b), 1.0, 1.0, "none",
                               out_scale=0.05, interpret=True)
        assert got.dtype == jnp.int8
        want = ref.misc_add(jnp.array(a), jnp.array(b), 1.0, 1.0, "none",
                            out_scale=jnp.float32(0.05))
        np.testing.assert_array_equal(np.array(got), np.array(want))


# ---------------------------------------------------------------------------
# ops.linear property test
# ---------------------------------------------------------------------------

@settings(deadline=None, max_examples=10)
@given(m=st.integers(1, 40), k=st.integers(8, 96), n=st.integers(8, 80))
def test_linear_pallas_equals_ref_property(m, k, n):
    rng = np.random.default_rng(m * 1000 + k * 10 + n)
    x = rng.normal(size=(m, k)).astype(np.float32)
    w = rng.normal(size=(k, n)).astype(np.float32)
    wq = quantize(jnp.array(w), axis=1)
    got = ops.linear(jnp.array(x), wq, None, "none", PALLAS)
    want = ops.linear(jnp.array(x), wq, None, "none", REF)
    np.testing.assert_allclose(np.array(got), np.array(want),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# Flash attention kernel (beyond-paper)
# ---------------------------------------------------------------------------

class TestFlashKernel:
    @pytest.mark.parametrize("l,s", [(128, 128), (256, 256), (128, 256)])
    def test_causal_matches_oracle(self, rng, l, s):
        q = jnp.array(rng.normal(size=(2, 3, l, 32)).astype(np.float32))
        k = jnp.array(rng.normal(size=(2, 3, s, 32)).astype(np.float32))
        v = jnp.array(rng.normal(size=(2, 3, s, 32)).astype(np.float32))
        got = ops.flash_mha(q, k, v, causal=True)
        want = ref.attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.array(got), np.array(want),
                                   rtol=2e-4, atol=2e-4)

    def test_ragged_and_softcap(self, rng):
        q = jnp.array(rng.normal(size=(1, 2, 200, 32)).astype(np.float32))
        k = jnp.array(rng.normal(size=(1, 2, 200, 32)).astype(np.float32))
        v = jnp.array(rng.normal(size=(1, 2, 200, 32)).astype(np.float32))
        got = ops.flash_mha(q, k, v, causal=True, softcap=20.0)
        want = ref.attention(q, k, v, causal=True, logit_softcap=20.0)
        np.testing.assert_allclose(np.array(got), np.array(want),
                                   rtol=2e-4, atol=2e-4)

    def test_non_causal(self, rng):
        q = jnp.array(rng.normal(size=(1, 2, 128, 32)).astype(np.float32))
        k = jnp.array(rng.normal(size=(1, 2, 128, 32)).astype(np.float32))
        v = jnp.array(rng.normal(size=(1, 2, 128, 32)).astype(np.float32))
        got = ops.flash_mha(q, k, v, causal=False)
        want = ref.attention(q, k, v, causal=False)
        np.testing.assert_allclose(np.array(got), np.array(want),
                                   rtol=2e-4, atol=2e-4)
