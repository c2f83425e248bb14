"""Ahead-of-time compiles of the main-path Pallas kernels for a TPU v5e.

The TPU compiler is installed even where no chip is attached, so each test
compiles one engine kernel at real widths for a described `v5e:2x2`
topology and checks that Mosaic built it (a `tpu_custom_call` in the
compiled module).  This is what interpret mode cannot show: Mosaic refuses
int32 matmuls, strided value slices and over-budget VMEM blocks that the
interpreter runs happily.  Nothing executes here.

The topology is described inside a module-scoped fixture, never at import,
so every pytest worker collects the same tests and only the worker that
runs this file loads the TPU library.
"""
import functools
import os

import pytest
import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core.config import EngineConfig
from repro.kernels import (conv_pe, dwc_pe, flash_attn, low_channel, misc_pe,
                           ops)

I8, F32, BF16, I32 = jnp.int8, jnp.float32, jnp.bfloat16, jnp.int32


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def chip(topo):
    """Returns compile(fn, *(shape, dtype)) -> number of Mosaic kernels."""
    one = SingleDeviceSharding(topo.devices[0])

    def compile_for_chip(fn, *specs):
        args = [jax.ShapeDtypeStruct(s, d, sharding=one) for s, d in specs]
        return jax.jit(fn).lower(*args).compile().as_text().count(
            "tpu_custom_call")
    return compile_for_chip


def test_matmul_int8_resnet50_im2col(chip):
    """ResNet50 stage-2 3x3 conv as its im2col GEMM, batch 8 (28x28x128),
    with the DSE-chosen blocks and the fused requant epilogue."""
    m, k, n = 8 * 28 * 28, 9 * 128, 128
    bm, bn, bk = ops.pick_blocks(m, n, k, 1, EngineConfig(quant="w8a8"))
    mp, kp = -(-m // bm) * bm, -(-k // bk) * bk
    fn = functools.partial(conv_pe.matmul_int8_fused, act="relu",
                           out_scale=0.05, bm=bm, bn=bn, bk=bk)
    assert chip(fn, ((mp, kp), I8), ((kp, n), I8), ((mp, 1), F32),
                ((1, n), F32), ((n,), F32)) == 1


def test_matmul_int8_residual_epilogue(chip):
    """The absorbed residual add: int8 second operand in the NL core."""
    def fn(a, b, asc, wsc, r):
        return conv_pe.matmul_int8_fused(
            a, b, asc, wsc, None, "none", out_scale=0.05, residual=r,
            res_scale=0.1, mid_scale=0.03, add_act="relu",
            bm=256, bn=256, bk=512)
    assert chip(fn, ((1024, 512), I8), ((512, 256), I8), ((1024, 1), F32),
                ((1, 256), F32), ((1024, 256), I8)) == 1


def test_matmul_int8_pool_resnet50_tail(chip):
    """ResNet50's last 1x1 conv + residual + global pool, 7x7 per image."""
    def fn(a, b, asc, wsc, bias, r):
        return conv_pe.matmul_int8_pool(
            a, b, asc, wsc, bias, "none", ho=7, wo=7, residual=r,
            res_scale=0.1, mid_scale=0.05, add_act="relu", add_scale=0.04,
            pool="global", out_scale=0.03, bn=256, bk=512)
    assert chip(fn, ((8, 64, 512), I8), ((512, 2048), I8),
                ((8, 64, 1), F32), ((1, 2048), F32), ((2048,), F32),
                ((8, 64, 2048), I8)) == 1


def test_matmul_int4_qwen2_decode(chip):
    """qwen2-1.5b's gate projection at decode (M=8, K=1536, N=8960),
    int4 weights in groups of 64."""
    m, k, n, g = 8, 1536, 8960, 1536 // 64
    fn = functools.partial(conv_pe.matmul_int4_fused, act="silu",
                           bm=8, bn=640)
    assert chip(fn, ((m, k), I8), ((k // 2, n), jnp.uint8), ((m, 1), F32),
                ((g, n), jnp.float16), ((g, n), jnp.float16),
                ((n,), F32)) == 1


@pytest.mark.parametrize("hw,c,stride", [
    (112, 128, 1),      # MobileNetV2 block 1 (32 ch, lane-padded)
    (112, 128, 2),      # 96 ch expanded, 112 -> 56
    (56, 256, 1),       # 144 ch expanded at 56 px
    (56, 256, 2),       # 144 ch expanded, 56 -> 28
])
def test_dwc2d_mobilenetv2(chip, hw, c, stride):
    """The DWC engine at MobileNetV2's published resolutions, static int8
    in and out (SAME padding applied by the wrapper)."""
    hp = hw + 2 if stride == 1 else hw + 1

    def fn(x, w, b, ws):
        return dwc_pe.dwc2d(x, w, b, stride, "relu6", a_scale=0.02,
                            w_scale=ws, out_scale=0.05)
    assert chip(fn, ((8, hp, hp, c), I8), ((3, 3, c), I8), ((c,), F32),
                ((c,), F32)) == 1


def test_low_channel_resnet50_stem_maxpool(chip):
    """ResNet50's 7x7/2 stem at 224 px with its fused 3x3/2 max pool."""
    def fn(x, w, b, ws):
        return low_channel.low_channel_conv(
            x, w, b, 2, "relu", a_scale=0.02, w_scale=ws, mid_scale=0.05,
            pool="max", pool_kernel=3, pool_stride=2)
    assert chip(fn, ((8, 229, 229, 3), I8), ((7, 7, 3, 64), I8),
                ((64,), F32), ((64,), F32)) == 1


def test_misc_add(chip):
    def fn(a, b):
        return misc_pe.misc_add(a, b, 0.1, 0.2, "relu", out_scale=0.05)
    assert chip(fn, ((8, 56, 56, 256), I8), ((8, 56, 56, 256), I8)) == 1


def test_avgpool2d(chip):
    fn = functools.partial(misc_pe.avgpool2d, window=2, stride=2)
    assert chip(fn, ((8, 56, 56, 256), F32)) == 1


def test_flash_attention(chip):
    """qwen2-1.5b prefill heads: 8 x 12 heads of 128, 512 positions."""
    shape = ((96, 512, 128), BF16)
    assert chip(flash_attn.flash_attention, shape, shape, shape) == 1


def test_paged_gather(chip):
    """qwen2-1.5b paged KV: 2 kv heads of 128, pages of 16, 8 slots."""
    assert chip(flash_attn.paged_gather, ((72, 16, 2, 128), BF16),
                ((8, 9), I32)) == 1
