"""Conv PE: int8 GEMM with cascade K-accumulation and a fused NL epilogue.

TPU adaptation of the paper's MAC->ACC->NL chain (Section IV-B, Fig. 3-4):

  * The MAC chain's in-flight cascade accumulation over IC becomes the K grid
    axis with a revolving int32 VMEM accumulator (`acc_ref`): partial sums
    live in VMEM for the whole reduction and never round-trip HBM -- exactly
    the property the cascade stream buys on the AIE array.
  * The ACC core's PsumStack is `acc_ref` (BM*BN*4 B); its bank budget
    (paper Eq. 3-4) is the VMEM constraint solved by core/dse.py.
  * The NL core is the fused epilogue on the last K step: dequant (per-token
    activation scale x per-channel weight scale), bias add, activation,
    optional requantization to int8.
  * Pallas's double-buffered software pipeline plays the role of the paper's
    ping-pong buffers and bubble-elimination protocol (Fig. 5): the grid is
    declared ("parallel", "parallel", "arbitrary") so the K walk is a clean
    revolving pipeline with no inter-step stalls after warmup.

Block shapes default to the DSE solver's choice (core/dse.solve_conv_blocks),
mirroring how the paper derives OC=32 / IH*IW=64 from Table I.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels.ref import act_fn, int4_group_combine
from repro.kernels import _epilogue


# v5e has 128 MiB of VMEM and Mosaic's default scoped limit is 16 MiB.  The
# DSE sizes blocks against a 12 MiB model of the pipelined operand,
# accumulator and output buffers (core/dse.py); a fused residual block, the
# staged int32 dot result and the f32 epilogue temporaries of a 1024-row
# block come on top (18.4 MiB for qwen2-1.5b's prefill down projection with
# its residual), so the GEMM kernels run under a 48 MiB limit.
_VMEM_LIMIT = 48 << 20


def _kernel(a_ref, b_ref, a_scale_ref, w_scale_ref, bias_ref, os_ref, o_ref,
            acc_ref, *, nk: int, act: str, has_bias: bool,
            out_scale: Optional[float], vector_os: bool):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # MAC chain link: one cascade step of the IC reduction.
    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            preferred_element_type=jnp.int32)

    # NL core: fused epilogue once the cascade completes.
    @pl.when(k == nk - 1)
    def _epilogue():
        x = acc_ref[...].astype(jnp.float32)
        x = x * a_scale_ref[...] * w_scale_ref[...]
        if has_bias:
            x = x + bias_ref[...]
        x = act_fn(act)(x)
        if vector_os:
            # per-output-channel requant (e.g. a per-channel edge feeding
            # the channelwise DWC engine): the divisor streams in blocked
            # [1, bn] like the weight scales.
            x = jnp.clip(jnp.round(x / os_ref[...]), -127, 127)
        elif out_scale is not None:
            x = jnp.clip(jnp.round(x / out_scale), -127, 127)
        o_ref[...] = x.astype(o_ref.dtype)


def _kernel_res(a_ref, b_ref, a_scale_ref, w_scale_ref, bias_ref, os_ref,
                r_ref, o_ref, acc_ref, *, nk: int, act: str, has_bias: bool,
                out_scale: Optional[float], vector_os: bool,
                mid_scale: Optional[float], res_scale: float, add_act: str):
    """The residual-epilogue variant: a second input operand streams into
    the NL core and the absorbed MISC add runs in-register after the
    cascade -- the fused conv->add(->act) chain as ONE launch."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            preferred_element_type=jnp.int32)

    @pl.when(k == nk - 1)
    def _tail():
        x = acc_ref[...].astype(jnp.float32)
        x = x * a_scale_ref[...] * w_scale_ref[...]
        if has_bias:
            x = x + bias_ref[...]
        x = act_fn(act)(x)
        if mid_scale is not None:
            # in-register requant to the absorbed conv edge's static scale
            # (what the unfused program materialized): bit-identical values
            x = jnp.clip(jnp.round(x / mid_scale), -127.0, 127.0) * mid_scale
        x = x + r_ref[...].astype(jnp.float32) * res_scale
        x = act_fn(add_act)(x)
        if vector_os:
            x = jnp.clip(jnp.round(x / os_ref[...]), -127, 127)
        elif out_scale is not None:
            x = jnp.clip(jnp.round(x / out_scale), -127, 127)
        o_ref[...] = x.astype(o_ref.dtype)


def matmul_int8_fused(a_q: jax.Array, b_q: jax.Array,
                      a_scale: jax.Array, w_scale: jax.Array,
                      bias: Optional[jax.Array] = None,
                      act: str = "none",
                      out_scale=None,
                      out_dtype=jnp.float32,
                      *,
                      residual: Optional[jax.Array] = None,
                      res_scale: float = 1.0,
                      mid_scale: Optional[float] = None,
                      add_act: str = "none",
                      bm: int = 128, bn: int = 128, bk: int = 512,
                      interpret: bool = False) -> jax.Array:
    """Fused int8 GEMM. Shapes must be multiples of the block shapes
    (kernels/ops.py pads).  a_q [M,K] int8, b_q [K,N] int8,
    a_scale [M,1] f32, w_scale [1,N] f32, bias [N] f32 or None.
    out_scale: None (float out), a scalar (per-tensor int8 requant), or a
    [N]-broadcastable array (per-output-channel requant, pre-padded).

    residual [M,N] (int8 with `res_scale`, or f32) adds the fused-epilogue
    second operand: the absorbed residual add + `add_act` run in-register
    after the cascade (`mid_scale`: the static scale of the absorbed conv
    edge; None on the dynamic path).
    """
    m, kdim = a_q.shape
    _, n = b_q.shape
    assert m % bm == 0 and n % bn == 0 and kdim % bk == 0, (m, n, kdim, bm, bn, bk)
    nk = kdim // bk
    has_bias = bias is not None
    bias2d = (bias.reshape(1, n).astype(jnp.float32) if has_bias
              else jnp.zeros((1, n), jnp.float32))
    vector_os = out_scale is not None and not isinstance(
        out_scale, (int, float))
    os2d = (jnp.asarray(out_scale, jnp.float32).reshape(1, n) if vector_os
            else jnp.ones((1, n), jnp.float32))
    odt = jnp.int8 if out_scale is not None else out_dtype

    grid = (m // bm, n // bn, nk)
    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),     # A
        pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),     # B
        pl.BlockSpec((bm, 1), lambda i, j, kk: (i, 0)),       # a_scale
        pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),       # w_scale
        pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),       # bias
        pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),       # out_scale
    ]
    operands = [a_q, b_q, a_scale.astype(jnp.float32).reshape(m, 1),
                w_scale.astype(jnp.float32).reshape(1, n), bias2d, os2d]
    if residual is None:
        kernel = functools.partial(
            _kernel, nk=nk, act=act, has_bias=has_bias,
            out_scale=None if vector_os else out_scale, vector_os=vector_os)
    else:
        assert residual.shape == (m, n), (residual.shape, m, n)
        in_specs.append(pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)))
        operands.append(residual)
        kernel = functools.partial(
            _kernel_res, nk=nk, act=act, has_bias=has_bias,
            out_scale=None if vector_os else out_scale, vector_os=vector_os,
            mid_scale=mid_scale, res_scale=res_scale, add_act=add_act)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), odt),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],         # PsumStack
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT,
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*operands)


# ---------------------------------------------------------------------------
# Int4 weight-only variant (XEGEMM_INT4 idiom): the weight operand streams
# PACKED (two nibbles per byte along K, per-group scale + zero), halving the
# GEMM's weight bytes; the kernel unpacks and dequantizes IN-REGISTER.  The
# per-group int32 partial sums stay exact (ref.int4_group_dot is the value
# oracle); one combine + NL epilogue per (bm, bn) block.  K is not gridded:
# the decode GEMMs this path serves have small reduction dims, and a whole-K
# block keeps the group reduction inside one kernel instance.
# ---------------------------------------------------------------------------

def int4_groups(a_q: jax.Array, b_packed: jax.Array, g: int):
    """Lay the int4 GEMM operands out by group, so every group is a
    leading-dim index in VMEM: a_q [M, K] -> [G, 2, M, gs/2] (the even /
    odd K columns, which meet the low / high nibbles), b_packed [K//2, N]
    -> [G, gs/2, N].  Nibble pairs never straddle a group, so the split is
    a reshape."""
    m, k = a_q.shape
    gs = k // g
    a = a_q.reshape(m, g, gs // 2, 2).transpose(1, 3, 0, 2)
    return a, b_packed.reshape(g, gs // 2, -1)


def int4_group_mac(a_lo: jax.Array, a_hi: jax.Array, packed: jax.Array):
    """The exact integer core, all groups at once: int32 partial sums
    [G, M, N] of the activations (even / odd K columns [G, M, gs/2])
    against the codes, and the activations' int32 row sums [G, M, 1] (what
    each group's zero point multiplies).  Nibbles unpack to int8 and feed
    the MXU's int8 path (Mosaic has no int32 matmul)."""
    p = packed.astype(jnp.int32)
    lo = (p & 0xF).astype(jnp.int8)
    hi = (p >> 4).astype(jnp.int8)
    dot = functools.partial(jnp.einsum, "gmk,gkn->gmn",
                            preferred_element_type=jnp.int32)
    part = dot(a_lo, lo) + dot(a_hi, hi)
    asum = jnp.sum(a_lo.astype(jnp.int32) + a_hi.astype(jnp.int32),
                   axis=-1, keepdims=True)
    return part, asum


def _w4_mac(a_ref, b_ref, wsc_ref, wz_ref):
    """ref.int4_group_dot over this block's group-major VMEM refs."""
    part, asum = int4_group_mac(a_ref[:, 0], a_ref[:, 1], b_ref[...])
    return int4_group_combine(part, asum, wsc_ref[...], wz_ref[...])


def _kernel_w4(a_ref, b_ref, wsc_ref, wz_ref, a_scale_ref, bias_ref, os_ref,
               o_ref, *, act: str, has_bias: bool,
               out_scale: Optional[float], vector_os: bool):
    x = _w4_mac(a_ref, b_ref, wsc_ref, wz_ref)
    x = x * a_scale_ref[...]
    if has_bias:
        x = x + bias_ref[...]
    x = act_fn(act)(x)
    if vector_os:
        x = jnp.clip(jnp.round(x / os_ref[...]), -127, 127)
    elif out_scale is not None:
        x = jnp.clip(jnp.round(x / out_scale), -127, 127)
    o_ref[...] = x.astype(o_ref.dtype)


def _kernel_w4_res(a_ref, b_ref, wsc_ref, wz_ref, a_scale_ref, bias_ref,
                   os_ref, r_ref, o_ref, *, act: str, has_bias: bool,
                   out_scale: Optional[float], vector_os: bool,
                   mid_scale: Optional[float], res_scale: float,
                   add_act: str):
    """Residual-epilogue variant: the absorbed MISC add after an O/down
    projection rides the same in-register-dequant launch."""
    x = _w4_mac(a_ref, b_ref, wsc_ref, wz_ref)
    x = x * a_scale_ref[...]
    if has_bias:
        x = x + bias_ref[...]
    x = act_fn(act)(x)
    if mid_scale is not None:
        x = jnp.clip(jnp.round(x / mid_scale), -127.0, 127.0) * mid_scale
    x = x + r_ref[...].astype(jnp.float32) * res_scale
    x = act_fn(add_act)(x)
    if vector_os:
        x = jnp.clip(jnp.round(x / os_ref[...]), -127, 127)
    elif out_scale is not None:
        x = jnp.clip(jnp.round(x / out_scale), -127, 127)
    o_ref[...] = x.astype(o_ref.dtype)


def matmul_int4_fused(a_q: jax.Array, b_packed: jax.Array,
                      a_scale: jax.Array, w_scale: jax.Array,
                      w_zero: jax.Array,
                      bias: Optional[jax.Array] = None,
                      act: str = "none",
                      out_scale=None,
                      out_dtype=jnp.float32,
                      *,
                      residual: Optional[jax.Array] = None,
                      res_scale: float = 1.0,
                      mid_scale: Optional[float] = None,
                      add_act: str = "none",
                      bm: int = 128, bn: int = 128,
                      interpret: bool = False) -> jax.Array:
    """Fused int4 weight-only GEMM: a_q [M, K] int8 x b_packed [K//2, N]
    uint8 nibble pairs, w_scale/w_zero [G, N] per-group (K = G * gs).
    M and N must be multiples of the block shapes (kernels/ops.py pads);
    the group dim pads in whole groups with zero scale/zero.  Epilogue and
    residual contract match matmul_int8_fused.
    """
    m, kdim = a_q.shape
    k2, n = b_packed.shape
    g = w_scale.shape[0]
    assert kdim == 2 * k2 and kdim % g == 0, (kdim, k2, g)
    assert m % bm == 0 and n % bn == 0, (m, n, bm, bn)
    has_bias = bias is not None
    bias2d = (bias.reshape(1, n).astype(jnp.float32) if has_bias
              else jnp.zeros((1, n), jnp.float32))
    vector_os = out_scale is not None and not isinstance(
        out_scale, (int, float))
    os2d = (jnp.asarray(out_scale, jnp.float32).reshape(1, n) if vector_os
            else jnp.ones((1, n), jnp.float32))
    odt = jnp.int8 if out_scale is not None else out_dtype

    # group-major operands: each group is a leading-dim index in VMEM
    a_g, b_g = int4_groups(a_q, b_packed, g)
    gs2 = kdim // g // 2
    grid = (m // bm, n // bn)
    in_specs = [
        pl.BlockSpec((g, 2, bm, gs2), lambda i, j: (0, 0, i, 0)),  # A
        pl.BlockSpec((g, gs2, bn), lambda i, j: (0, 0, j)),   # B packed
        pl.BlockSpec((g, 1, bn), lambda i, j: (0, 0, j)),     # group scales
        pl.BlockSpec((g, 1, bn), lambda i, j: (0, 0, j)),     # group zeros
        pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),           # a_scale
        pl.BlockSpec((1, bn), lambda i, j: (0, j)),           # bias
        pl.BlockSpec((1, bn), lambda i, j: (0, j)),           # out_scale
    ]
    operands = [a_g, b_g,
                w_scale.astype(jnp.float32).reshape(g, 1, n),
                w_zero.astype(jnp.float32).reshape(g, 1, n),
                a_scale.astype(jnp.float32).reshape(m, 1), bias2d, os2d]
    if residual is None:
        kernel = functools.partial(
            _kernel_w4, act=act, has_bias=has_bias,
            out_scale=None if vector_os else out_scale, vector_os=vector_os)
    else:
        assert residual.shape == (m, n), (residual.shape, m, n)
        in_specs.append(pl.BlockSpec((bm, bn), lambda i, j: (i, j)))
        operands.append(residual)
        kernel = functools.partial(
            _kernel_w4_res, act=act, has_bias=has_bias,
            out_scale=None if vector_os else out_scale, vector_os=vector_os,
            mid_scale=mid_scale, res_scale=res_scale, add_act=add_act)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), odt),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT,
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(*operands)


# ---------------------------------------------------------------------------
# Pooled-epilogue variant: per-image M blocking so the absorbed avg/global/
# max pool tail accumulates in-kernel (the GAP tail never materializes the
# pre-pool feature map)
# ---------------------------------------------------------------------------

def _kernel_pool(*refs, nk: int, act: str, has_bias: bool, has_res: bool,
                 rows: int, ho: int, wo: int, out_rows: int,
                 mid_scale: Optional[float], res_scale: float, add_act: str,
                 add_scale: Optional[float], pool: str, pool_kernel: int,
                 pool_stride: int, out_scale: Optional[float]):
    a_ref, b_ref, asc_ref, wsc_ref, bias_ref = refs[:5]
    r_ref = refs[5] if has_res else None
    o_ref, acc_ref = refs[5 + has_res:7 + has_res]
    taps = (_epilogue.scratch_taps(refs[7 + has_res])
            if len(refs) > 7 + has_res else None)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[0], b_ref[...],
                            preferred_element_type=jnp.int32)

    @pl.when(k == nk - 1)
    def _tail():
        x = acc_ref[...].astype(jnp.float32)        # [rows_p, bn]
        x = x * asc_ref[0] * wsc_ref[...]
        if has_bias:
            x = x + bias_ref[...]
        x = act_fn(act)(x)
        bn = x.shape[-1]
        xs = x[:rows].reshape(ho, wo, bn)           # valid rows only
        rs = (r_ref[0][:rows].reshape(ho, wo, bn) if has_res else None)
        y = _epilogue.fused_chain(
            xs, mid_scale=mid_scale, residual=rs, res_scale=res_scale,
            add_act=add_act, add_scale=add_scale, pool=pool,
            pool_kernel=pool_kernel, pool_stride=pool_stride,
            out_scale=out_scale, taps=taps)
        y = y.reshape(-1, bn)
        y = jnp.pad(y, ((0, out_rows - y.shape[0]), (0, 0)))
        o_ref[0] = y.astype(o_ref.dtype)


def matmul_int8_pool(a_q: jax.Array, b_q: jax.Array, a_scale: jax.Array,
                     w_scale: jax.Array, bias: Optional[jax.Array],
                     act: str, *, ho: int, wo: int,
                     residual: Optional[jax.Array] = None,
                     res_scale: float = 1.0,
                     mid_scale: Optional[float] = None,
                     add_act: str = "none",
                     add_scale: Optional[float] = None,
                     pool: str = "global", pool_kernel: int = 0,
                     pool_stride: int = 0,
                     out_scale: Optional[float] = None,
                     out_dtype=jnp.float32,
                     bn: int = 128, bk: int = 512,
                     interpret: bool = False) -> jax.Array:
    """Fused GEMM + pooled epilogue, ONE launch per program.

    a_q [G, rows_p, K] int8: the im2col rows blocked per image (G = batch;
    rows_p >= ho*wo, padded); b_q [K, N]; a_scale [G, rows_p, 1];
    w_scale [1, N]; residual [G, rows_p, N] or None.  The epilogue slices
    the valid ho*wo rows, runs the fused chain (qdq/add/act), and POOLS
    in-register before the single write-out, so the pre-pool feature map
    never reaches memory.  Returns [G, out_rows, N] where out_rows rows 0..
    pooled_h*pooled_w-1 are valid (caller slices + reshapes).

    VMEM note: the accumulator holds the image's full [rows_p, bn] tile --
    sized for the tail-of-network feature maps where pool chains live.
    """
    g, rows_p, kdim = a_q.shape
    _, n = b_q.shape
    assert n % bn == 0 and kdim % bk == 0, (n, kdim, bn, bk)
    nk = kdim // bk
    rows = ho * wo
    assert rows <= rows_p, (rows, rows_p)
    pho, pwo = _epilogue.pooled_hw(ho, wo, pool, pool_kernel, pool_stride)
    out_rows = max(8, -(-(pho * pwo) // 8) * 8)
    has_bias = bias is not None
    bias2d = (bias.reshape(1, n).astype(jnp.float32) if has_bias
              else jnp.zeros((1, n), jnp.float32))
    odt = _epilogue.chain_out_dtype(mid_scale, pool, out_scale, out_dtype)

    in_specs = [
        pl.BlockSpec((1, rows_p, bk), lambda i, j, kk: (i, 0, kk)),   # A
        pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),             # B
        pl.BlockSpec((1, rows_p, 1), lambda i, j, kk: (i, 0, 0)),     # asc
        pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),               # wsc
        pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),               # bias
    ]
    operands = [a_q, b_q, a_scale.astype(jnp.float32),
                w_scale.astype(jnp.float32).reshape(1, n), bias2d]
    if residual is not None:
        assert residual.shape == (g, rows_p, n), (residual.shape, g, rows_p, n)
        in_specs.append(
            pl.BlockSpec((1, rows_p, bn), lambda i, j, kk: (i, 0, j)))
        operands.append(residual)
    return pl.pallas_call(
        functools.partial(
            _kernel_pool, nk=nk, act=act, has_bias=has_bias,
            has_res=residual is not None, rows=rows, ho=ho, wo=wo,
            out_rows=out_rows, mid_scale=mid_scale, res_scale=res_scale,
            add_act=add_act, add_scale=add_scale, pool=pool,
            pool_kernel=pool_kernel, pool_stride=pool_stride,
            out_scale=out_scale),
        grid=(g, n // bn, nk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, out_rows, bn), lambda i, j, kk: (i, 0, j)),
        out_shape=jax.ShapeDtypeStruct((g, out_rows, n), odt),
        scratch_shapes=([pltpu.VMEM((rows_p, bn), jnp.int32)]
                        + _epilogue.tap_scratch(pool, ho, wo, bn)),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT,
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*operands)


# ---------------------------------------------------------------------------
# bf16 variant (training-path GEMM with fused epilogue; same dataflow)
# ---------------------------------------------------------------------------

def _kernel_f(a_ref, b_ref, bias_ref, o_ref, acc_ref,
              *, nk: int, act: str, has_bias: bool):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _epilogue():
        x = acc_ref[...]
        if has_bias:
            x = x + bias_ref[...]
        o_ref[...] = act_fn(act)(x).astype(o_ref.dtype)


def matmul_f_fused(a: jax.Array, b: jax.Array,
                   bias: Optional[jax.Array] = None, act: str = "none",
                   out_dtype=jnp.float32, *,
                   bm: int = 128, bn: int = 128, bk: int = 512,
                   interpret: bool = False) -> jax.Array:
    m, kdim = a.shape
    _, n = b.shape
    assert m % bm == 0 and n % bn == 0 and kdim % bk == 0
    nk = kdim // bk
    has_bias = bias is not None
    bias2d = (bias.reshape(1, n).astype(jnp.float32) if has_bias
              else jnp.zeros((1, n), jnp.float32))
    return pl.pallas_call(
        functools.partial(_kernel_f, nk=nk, act=act, has_bias=has_bias),
        grid=(m // bm, n // bn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT,
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, b, bias2d)
