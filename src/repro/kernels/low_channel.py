"""Low-Channel Conv Unit: the first-layer specialization.

Paper (Section V-B): the graph-level Conv PE has 64(IC) x 128(OC) parallelism,
so a ResNet50 stage-0 conv (7x7, IC=3, OC=64) runs at 13.1% utilization; a
dedicated PL unit with 4(H) x 21(IC) x 32(OC) parallelism (672 DSP58s) handles
it concurrently, buying +1.14x throughput / -7.5% latency.

TPU adaptation: the MXU has the same pathology (IC=3 against a 128-deep
contraction).  The fix is *the same idea the paper's 21-wide IC datapath
exploits*: fold the kernel's W taps into the contraction, so the effective
IC becomes IC*K (3*7 = 21 for the ResNet stem).  The wrapper lays the image
out once with its W taps (and W stride) folded into lanes -- a K-fold
widening of a tiny first-layer input, never the K*K-fold patch tensor --
and the kernel runs the K row taps as [pixels, IC*K] x [IC*K, OC] MXU
matmuls into a revolving accumulator.

Grid: (N, row tiles) -- each step reads its own stride-phased row window
(halo rows included) and writes a band of output rows, so the accumulator
stays small at 224, 416 and 640 px.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels.ref import act_fn
from repro.kernels import _epilogue, dwc_pe


def _kernel(x_ref, w_ref, bias_ref, scale_ref, o_ref, *scratch,
            k: int, stride: int, ch: int, wo: int, act: str,
            quant: bool, out_scale: Optional[float],
            mid_scale: Optional[float], pool: str, pool_kernel: int,
            pool_stride: int):
    kic = x_ref.shape[-1]               # k * IC: the W-folded contraction
    oc = o_ref.shape[-1]
    acc_dtype = jnp.int32 if quant else jnp.float32
    acc = jnp.zeros((ch * wo, oc), acc_dtype)
    phases = [x_ref[0, 0, p] for p in range(stride)]
    for kh in range(k):                 # one MXU matmul per kernel row
        xs = phases[kh % stride][kh // stride:kh // stride + ch]
        acc = acc + jnp.dot(xs.reshape(ch * wo, kic), w_ref[kh],
                            preferred_element_type=acc_dtype)
    xf = acc.astype(jnp.float32)
    if quant:
        xf = xf * scale_ref[0]             # [OC] per-channel dequant
    xf = xf + bias_ref[0]
    xf = act_fn(act)(xf)
    if pool != "none":
        # fused pool tail (e.g. the stem -> max-pool chain): the pre-pool
        # stem feature map never leaves the unit
        y = _epilogue.fused_chain(
            xf.reshape(ch, wo, oc), mid_scale=mid_scale, pool=pool,
            pool_kernel=pool_kernel, pool_stride=pool_stride,
            out_scale=out_scale,
            taps=_epilogue.scratch_taps(scratch[0]) if scratch else None)
        if pool == "global":
            y = y.reshape(1, 1, oc)
        o_ref[0] = y.astype(o_ref.dtype)
        return
    if out_scale is not None:              # fused requant (NL epilogue)
        xf = jnp.clip(jnp.round(xf / out_scale), -127, 127)
    o_ref[0] = xf.reshape(ch, wo, oc).astype(o_ref.dtype)


def _tiles(ho: int, wo: int, pool: str, pk: int, ps: int):
    """Row tiling: (conv rows per step, conv-row step between tiles, output
    rows per step, steps).  Without a pool each step owns `th` conv rows;
    a windowed pool tiles its OUTPUT rows and recomputes the conv rows its
    windows share across a tile edge; a global pool takes the whole map."""
    if pool == "none":
        th = dwc_pe.row_tile(ho, wo)
        return th, th, th, ho // th
    if pool == "global":
        return ho, ho, 1, 1
    pho, pwo = _epilogue.pooled_hw(ho, wo, pool, pk, ps)
    best = 1
    for pth in range(1, pho + 1):
        if pho % pth == 0 and ((pth - 1) * ps + pk) * wo <= 2048:
            best = pth
    return (best - 1) * ps + pk, best * ps, best, pho // best


def low_channel_conv(x: jax.Array, w: jax.Array, bias: Optional[jax.Array],
                     stride: int, act: str = "none",
                     a_scale: Optional[float] = None,
                     w_scale: Optional[float] = None,
                     out_scale: Optional[float] = None,
                     out_dtype=jnp.float32, *,
                     mid_scale: Optional[float] = None,
                     pool: str = "none", pool_kernel: int = 0,
                     pool_stride: int = 0,
                     interpret: bool = False) -> jax.Array:
    """First-layer conv on pre-padded input (VALID).

    x: [N, Hp, Wp, IC] (IC small), w: [k, k, IC, OC], bias: [OC].
    Quantized path fuses the activation scale with the weight scale
    (per-tensor scalar or per-output-channel [OC]); a_scale / w_scale may
    be Python floats or (traced) arrays.  out_scale requants to int8 in
    the epilogue and must be static.

    pool ("avg" | "global" | "max") fuses an absorbed pool tail into the
    epilogue (mid_scale: the static pre-pool edge scale); the output is
    then [N, PHo, PWo, OC].
    """
    n, hp, wp, ic = x.shape
    k, _, _, oc = w.shape
    ho = (hp - k) // stride + 1
    wo = (wp - k) // stride + 1
    quant = a_scale is not None
    scale = (jnp.asarray(a_scale, jnp.float32)
             * jnp.asarray(w_scale, jnp.float32) if quant
             else jnp.ones((), jnp.float32))
    scale_arr = jnp.broadcast_to(scale.reshape(-1), (oc,)).reshape(1, oc)
    bias_arr = (bias.astype(jnp.float32).reshape(1, oc) if bias is not None
                else jnp.zeros((1, oc), jnp.float32))
    pho, pwo = _epilogue.pooled_hw(ho, wo, pool, pool_kernel, pool_stride)
    if pool != "none":
        odt = _epilogue.chain_out_dtype(mid_scale, pool, out_scale, out_dtype)
    else:
        odt = jnp.int8 if out_scale is not None else out_dtype
    # Fold the kernel's W taps (and the W stride) into the contraction:
    # column j, lane kw*IC + c holds x[:, :, j*s + kw, c].  The row taps
    # stay in the kernel, read from stride-phased row windows.
    xw = jnp.concatenate(
        [x[:, :, kw:kw + (wo - 1) * stride + 1:stride, :] for kw in range(k)],
        axis=-1)
    ch, step, out_rows, nt = _tiles(ho, wo, pool, pool_kernel, pool_stride)
    xw = dwc_pe.phase_windows(xw, stride, 1, [t * step for t in range(nt)],
                              ch + (k - 1) // stride)
    return pl.pallas_call(
        functools.partial(_kernel, k=k, stride=stride, ch=ch, wo=wo, act=act,
                          quant=quant, out_scale=out_scale,
                          mid_scale=mid_scale, pool=pool,
                          pool_kernel=pool_kernel, pool_stride=pool_stride),
        grid=(n, nt),
        in_specs=[
            pl.BlockSpec((1, 1) + xw.shape[2:],
                         lambda i, t: (i, t, 0, 0, 0, 0)),
            pl.BlockSpec((k, k * ic, oc), lambda i, t: (0, 0, 0)),
            pl.BlockSpec((1, oc), lambda i, t: (0, 0)),
            pl.BlockSpec((1, oc), lambda i, t: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, out_rows, pwo, oc),
                               lambda i, t: (i, t, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, pho, pwo, oc), odt),
        scratch_shapes=_epilogue.tap_scratch(pool, ch, wo, oc),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(xw, w.reshape(k, k * ic, oc), bias_arr, scale_arr)
