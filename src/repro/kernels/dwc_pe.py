"""DWC PE: depthwise-convolution engine.

TPU adaptation of the paper's DWC PE (Section IV-C, Fig. 6-8):

  * The paper's problem: depthwise conv has no IC reduction, so the MAC
    cascade is useless and fmap sharing across kernels is impossible.  Their
    answer: tile the feature map per core, keep the *channel* dimension on the
    16-lane vector unit, zero-pad weights to bank alignment, and fuse
    accumulate+NL in the RACNL core.
  * TPU mapping: channels ride the 128-wide lane dimension of the VPU (the
    16-lane AIE vector analogue), the spatial tile lives in sublanes, the
    kernel taps are unrolled as aligned strided loads from a VMEM-resident
    input tile (loaded ONCE per (batch, channel-block) -- the data-reuse the
    paper engineers with its atomic-DWC schedule), and bias/act/requant are
    fused in the epilogue (RACNL core).
  * The paper's weight zero-padding for bank alignment maps to channel
    padding to a multiple of 128 lanes (done by the ops.py wrapper).

Grid: (N, C/BC, row tiles); each cell owns a band of output rows plus its
kernel apron (the halo rows, cut by the wrapper) -- the analogue of each MAC
core owning a fmap tile plus apron.  Strided convs are split into stride
phases in the wrapper, so every tap is a unit-stride slice in VMEM.

A 1-D causal variant (dwc1d) serves the mamba / RG-LRU temporal conv and is
the same engine with H=1 semantics.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels.ref import act_fn
from repro.kernels import _epilogue


def _dwc2d_kernel(*refs, k: int, stride: int, th: int, wo: int, act: str,
                  quant: bool, out_scale: Optional[float], has_res: bool,
                  mid_scale: Optional[float], res_scale: float, add_act: str,
                  add_scale: Optional[float], pool: str, pool_kernel: int,
                  pool_stride: int):
    x_ref, w_ref, bias_ref, wscale_ref = refs[:4]
    r_ref = refs[4] if has_res else None
    o_ref = refs[4 + has_res]
    taps = (_epilogue.scratch_taps(refs[5 + has_res])
            if len(refs) > 5 + has_res else None)
    acc_dtype = jnp.int32 if quant else jnp.float32
    acc = jnp.zeros((th, wo, x_ref.shape[-1]), acc_dtype)
    phases = [x_ref[0, 0, p] for p in range(stride * stride)]
    for kh in range(k):                # unrolled taps: the atomic-DWC schedule
        for kw in range(k):
            # tap (kh, kw) reads stride phase (kh % s, kw % s) at offset
            # (kh // s, kw // s): unit-stride slices only
            x = phases[(kh % stride) * stride + kw % stride]
            xs = x[kh // stride:kh // stride + th,
                   kw // stride:kw // stride + wo, :]
            acc = acc + xs.astype(acc_dtype) * w_ref[kh, kw, :].astype(acc_dtype)
    xf = acc.astype(jnp.float32)
    if quant:
        xf = xf * wscale_ref[0, 0, :]
    xf = xf + bias_ref[0, 0, :]
    xf = act_fn(act)(xf)
    if has_res or pool != "none":
        # fused MISC tail: the RACNL core absorbs the residual add / pool
        y = _epilogue.fused_chain(
            xf, mid_scale=mid_scale, residual=r_ref[0] if has_res else None,
            res_scale=res_scale, add_act=add_act, add_scale=add_scale,
            pool=pool, pool_kernel=pool_kernel, pool_stride=pool_stride,
            out_scale=out_scale, taps=taps)
        if pool == "global":
            y = y.reshape(1, 1, -1)
        o_ref[0] = y.astype(o_ref.dtype)
        return
    if out_scale is not None:
        xf = jnp.clip(jnp.round(xf / out_scale), -127, 127)
    o_ref[0] = xf.astype(o_ref.dtype)


def row_tile(ho: int, wo: int, budget: int = 2048) -> int:
    """Output rows per grid step: the largest divisor of `ho` whose tile
    keeps <= `budget` pixels, so the per-step accumulator stays a small
    slice of VMEM at any published resolution (at least one row)."""
    best = 1
    for th in range(1, ho + 1):
        if ho % th == 0 and th * wo <= budget:
            best = th
    return best


def phase_windows(x: jax.Array, sh: int, sw: int, starts,
                  rows: int) -> jax.Array:
    """Split a padded NHWC map into its stride phases and cut row windows.

    x [N, H, W, C] -> [N, T, sh*sw, rows, ceil(W/sw), C] where phase
    p = ph*sw + pw holds x[:, ph::sh, pw::sw] and window t holds phase rows
    starts[t] .. starts[t] + rows - 1 (windows may overlap: the halo).  A
    kernel then reads every strided tap as a unit-stride slice, and each
    grid step holds only its own rows in VMEM.
    """
    n, h, w, c = x.shape
    if sh * sw > 1:
        hq, wq = -(-h // sh), -(-w // sw)
        x = jnp.pad(x, ((0, 0), (0, hq * sh - h), (0, wq * sw - w), (0, 0)))
        x = x.reshape(n, hq, sh, wq, sw, c).transpose(0, 2, 4, 1, 3, 5)
        x = x.reshape(n, sh * sw, hq, wq, c)
    else:
        x = x[:, None]
    return jnp.stack([x[:, :, s0:s0 + rows] for s0 in starts], axis=1)


def dwc2d(x: jax.Array, w: jax.Array, bias: Optional[jax.Array],
          stride: int = 1, act: str = "none",
          a_scale: Optional[float] = None,
          w_scale: Optional[jax.Array] = None,
          out_scale: Optional[float] = None,
          out_dtype=jnp.float32, *,
          residual: Optional[jax.Array] = None,
          res_scale: float = 1.0,
          mid_scale: Optional[float] = None,
          add_act: str = "none",
          add_scale: Optional[float] = None,
          pool: str = "none", pool_kernel: int = 0, pool_stride: int = 0,
          bc: int = 128, interpret: bool = False) -> jax.Array:
    """Depthwise conv on pre-padded input (VALID). x: [N, Hp, Wp, C] with
    C % bc == 0; w: [k, k, C]; bias: [C].

    residual [N, Ho, Wo, C] (int8 with `res_scale`, or f32) and/or
    pool ("avg" | "global" | "max") fuse the absorbed MISC tail into the
    RACNL epilogue: one launch, no intermediate feature map.  mid_scale /
    add_scale are the static interior requant points (None = dynamic f32
    chain).  With a pool tail the output is [N, PHo, PWo, C].

    Grid (N, C/bc, Ho/th): each step computes `th` output rows from its
    own stride-phased row window (phase_windows), halo included.  A pool
    tail needs whole windows of the map, so it takes the map in one step.
    """
    n, hp, wp, c = x.shape
    k = w.shape[0]
    assert c % bc == 0, (c, bc)
    ho = (hp - k) // stride + 1
    wo = (wp - k) // stride + 1
    quant = a_scale is not None
    # Fold the (scalar per-tensor) activation scale into the per-channel
    # weight scale so the epilogue is one multiply -- the RACNL requant.
    # a_scale may be a Python float (static programs) or a traced scalar
    # (dynamic quantization under jit).
    wsc = (jnp.asarray(w_scale, jnp.float32).reshape(1, 1, c)
           * jnp.asarray(a_scale, jnp.float32)
           if quant else jnp.zeros((1, 1, c), jnp.float32))
    bias_arr = (bias.astype(jnp.float32).reshape(1, 1, c) if bias is not None
                else jnp.zeros((1, 1, c), jnp.float32))
    pho, pwo = _epilogue.pooled_hw(ho, wo, pool, pool_kernel, pool_stride)
    if residual is not None or pool != "none":
        odt = _epilogue.chain_out_dtype(mid_scale, pool, out_scale, out_dtype)
    else:
        odt = jnp.int8 if out_scale is not None else out_dtype
    th = ho if pool != "none" else row_tile(ho, wo)
    nt = ho // th
    rows = th + (k - 1) // stride
    xw = phase_windows(x, stride, stride, [t * th for t in range(nt)], rows)

    in_specs = [
        pl.BlockSpec((1, 1) + xw.shape[2:5] + (bc,),
                     lambda i, j, t: (i, t, 0, 0, 0, j)),
        pl.BlockSpec((k, k, bc), lambda i, j, t: (0, 0, j)),
        pl.BlockSpec((1, 1, bc), lambda i, j, t: (0, 0, j)),
        pl.BlockSpec((1, 1, bc), lambda i, j, t: (0, 0, j)),
    ]
    operands = [xw, w, bias_arr, wsc]
    if residual is not None:
        assert residual.shape == (n, ho, wo, c), (residual.shape, n, ho, wo, c)
        in_specs.append(pl.BlockSpec((1, th, wo, bc),
                                     lambda i, j, t: (i, t, 0, j)))
        operands.append(residual)
    out_rows = pho if pool != "none" else th
    return pl.pallas_call(
        functools.partial(
            _dwc2d_kernel, k=k, stride=stride, th=th, wo=wo, act=act,
            quant=quant, out_scale=out_scale, has_res=residual is not None,
            mid_scale=mid_scale, res_scale=res_scale, add_act=add_act,
            add_scale=add_scale, pool=pool, pool_kernel=pool_kernel,
            pool_stride=pool_stride),
        grid=(n, c // bc, nt),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, out_rows, pwo, bc),
                               lambda i, j, t: (i, t, 0, j)),
        out_shape=jax.ShapeDtypeStruct((n, pho, pwo, c), odt),
        scratch_shapes=_epilogue.tap_scratch(pool, ho, wo, bc),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=interpret,
    )(*operands)


# ---------------------------------------------------------------------------
# 1-D causal variant (mamba / RG-LRU temporal conv)
# ---------------------------------------------------------------------------

def _dwc1d_kernel(x_ref, w_ref, bias_ref, o_ref, *, k: int, l: int, act: str):
    x = x_ref[0]                       # [L + k - 1, BC]
    acc = jnp.zeros((l, x.shape[-1]), jnp.float32)
    for i in range(k):
        acc = acc + x[i:i + l, :].astype(jnp.float32) * w_ref[i, :].astype(jnp.float32)
    acc = acc + bias_ref[0, :]
    o_ref[0] = act_fn(act)(acc).astype(o_ref.dtype)


def dwc1d_causal(x: jax.Array, w: jax.Array,
                 bias: Optional[jax.Array] = None, act: str = "none",
                 out_dtype=jnp.float32, *,
                 bc: int = 128, interpret: bool = False) -> jax.Array:
    """x: [B, L, C] (C % bc == 0), w: [k, C], bias: [C]."""
    b, l, c = x.shape
    k = w.shape[0]
    assert c % bc == 0, (c, bc)
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    bias_arr = (bias.astype(jnp.float32).reshape(1, c) if bias is not None
                else jnp.zeros((1, c), jnp.float32))
    return pl.pallas_call(
        functools.partial(_dwc1d_kernel, k=k, l=l, act=act),
        grid=(b, c // bc),
        in_specs=[
            pl.BlockSpec((1, l + k - 1, bc), lambda i, j: (i, 0, j)),
            pl.BlockSpec((k, bc), lambda i, j: (0, j)),
            pl.BlockSpec((1, bc), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, l, bc), lambda i, j: (i, 0, j)),
        out_shape=jax.ShapeDtypeStruct((b, l, c), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(xp, w, bias_arr)
