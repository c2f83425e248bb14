"""Pure-jnp reference oracles for every DPUV4E kernel.

These define the semantics the Pallas kernels must match bit-for-bit (int
paths) or to float tolerance (epilogue paths).  They are also the "ref"
backend used for CPU execution and for the dry-run lowering (XLA-TPU fuses
the same epilogues the Pallas kernels fuse by hand).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# Activations (the NL core's menu, Section IV-B2)
# ---------------------------------------------------------------------------

def act_fn(name: str):
    return {
        "none": lambda x: x,
        "relu": jax.nn.relu,
        "relu2": lambda x: jnp.square(jax.nn.relu(x)),
        "relu6": lambda x: jnp.clip(x, 0.0, 6.0),
        "silu": jax.nn.silu,
        "gelu": lambda x: jax.nn.gelu(x, approximate=True),
        "hardswish": jax.nn.hard_swish,
    }[name]


def softcap(x: jax.Array, cap: float) -> jax.Array:
    return cap * jnp.tanh(x / cap)


# ---------------------------------------------------------------------------
# C2: Conv PE -- int8 GEMM with cascade accumulation + fused NL epilogue
# ---------------------------------------------------------------------------

def matmul_int8_fused(a_q: jax.Array, b_q: jax.Array,
                      a_scale: jax.Array, w_scale: jax.Array,
                      bias: Optional[jax.Array] = None,
                      act: str = "none",
                      out_scale: Optional[jax.Array] = None,
                      out_dtype=jnp.float32) -> jax.Array:
    """out = requant(act(dequant(a_q @ b_q) + bias)).

    a_q:      int8 [M, K];     a_scale: f32 [M, 1] (per-token) or scalar
    b_q:      int8 [K, N];     w_scale: f32 [1, N] (per-channel) or scalar
    bias:     f32 [N] or None
    out_scale: f32 scalar -> int8 output, None -> float output.
    """
    acc = jnp.dot(a_q.astype(jnp.int32), b_q.astype(jnp.int32),
                  preferred_element_type=jnp.int32)
    x = acc.astype(jnp.float32) * a_scale * w_scale
    if bias is not None:
        x = x + bias
    x = act_fn(act)(x)
    if out_scale is not None:
        q = jnp.clip(jnp.round(x / out_scale), -127, 127)
        return q.astype(jnp.int8)
    return x.astype(out_dtype)


def matmul_int8_unfused(a_q, b_q, a_scale, w_scale, bias=None, act="none",
                        out_scale=None, out_dtype=jnp.float32):
    """XVDPU-analog baseline: the int32 partial sums round-trip to HBM and the
    epilogue runs as separate (PL-side, in the paper) ops."""
    acc = jnp.dot(a_q.astype(jnp.int32), b_q.astype(jnp.int32),
                  preferred_element_type=jnp.int32)
    acc = jax.lax.optimization_barrier(acc)     # forbid XLA epilogue fusion
    x = acc.astype(jnp.float32) * a_scale * w_scale
    if bias is not None:
        x = jax.lax.optimization_barrier(x + bias)
    x = act_fn(act)(x)
    if out_scale is not None:
        q = jnp.clip(jnp.round(x / out_scale), -127, 127)
        return q.astype(jnp.int8)
    return x.astype(out_dtype)


def int4_group_dot(a_q: jax.Array, codes: jax.Array,
                   w_scale: jax.Array, w_zero: jax.Array) -> jax.Array:
    """The int4 weight-only MAC the Conv PE runs in-register.

    a_q: int8 [M, K]; codes: int8 [K, N] in [0, 15]; w_scale/w_zero:
    [G, N] per-group (K = G * gs).  Partial sums stay exact int32 per group
    (one batched int8 dot), then int4_group_combine applies the per-group
    f32 scale/zero.
    """
    m, k = a_q.shape
    g, n = w_scale.shape
    gs = k // g
    ag = a_q.astype(jnp.int8).reshape(m, g, gs)
    part = jnp.einsum("mgk,gkn->gmn", ag,
                      codes.astype(jnp.int8).reshape(g, gs, n),
                      preferred_element_type=jnp.int32)
    asum = jnp.sum(ag.astype(jnp.int32), axis=-1).T[..., None]   # [G, M, 1]
    return int4_group_combine(part, asum, w_scale[:, None], w_zero[:, None])


def int4_group_combine(part: jax.Array, asum: jax.Array,
                       w_scale: jax.Array, w_zero: jax.Array) -> jax.Array:
    """The f32 epilogue of the int4 MAC, one definition for ref and kernel:
    int32 group partial sums part [G, M, N] and activation row sums asum
    [G, M, 1] against the group scales / zeros [G, 1, N]:

        out[m, n] = sum_g scale[g, n] * (a[m, g*gs:..] . codes[g*gs:.., n])
                  + zero[g, n] * (sum_k a[m, g*gs + k])
    """
    return jnp.sum(part.astype(jnp.float32) * w_scale.astype(jnp.float32)
                   + asum.astype(jnp.float32) * w_zero.astype(jnp.float32),
                   axis=0)


def matmul_int4_fused(a_q: jax.Array, b_packed: jax.Array,
                      a_scale: jax.Array, w_scale: jax.Array,
                      w_zero: jax.Array,
                      bias: Optional[jax.Array] = None,
                      act: str = "none",
                      out_scale: Optional[jax.Array] = None,
                      out_dtype=jnp.float32) -> jax.Array:
    """Int4 weight-only GEMM oracle: unpack -> group dot -> NL epilogue.

    a_q: int8 [M, K] with a_scale f32 [M, 1] (per-token) or scalar;
    b_packed: uint8 [K//2, N] nibble pairs with w_scale/w_zero [G, N].
    Same epilogue contract as matmul_int8_fused.
    """
    from repro.core.quant import unpack_int4

    codes = unpack_int4(b_packed)
    x = int4_group_dot(a_q, codes, w_scale, w_zero) * a_scale
    if bias is not None:
        x = x + bias
    x = act_fn(act)(x)
    if out_scale is not None:
        q = jnp.clip(jnp.round(x / out_scale), -127, 127)
        return q.astype(jnp.int8)
    return x.astype(out_dtype)


# ---------------------------------------------------------------------------
# C4: DWC PE -- depthwise convolution, NHWC
# ---------------------------------------------------------------------------

def dwc2d(x: jax.Array, w: jax.Array, bias: Optional[jax.Array],
          stride: int = 1, act: str = "none",
          a_scale: Optional[jax.Array] = None,
          w_scale: Optional[jax.Array] = None,
          out_scale: Optional[jax.Array] = None,
          out_dtype=jnp.float32) -> jax.Array:
    """Depthwise conv on a pre-padded input (VALID semantics).

    x: [N, H, W, C] (int8 or float), w: [k, k, C], bias: [C].
    Quantized mode when a_scale/w_scale given (int8 x int8 -> int32).
    """
    k = w.shape[0]
    n, h, wd, c = x.shape
    ho = (h - k) // stride + 1
    wo = (wd - k) // stride + 1
    quant = a_scale is not None
    acc_dtype = jnp.int32 if quant else jnp.float32
    acc = jnp.zeros((n, ho, wo, c), acc_dtype)
    for kh in range(k):
        for kw in range(k):
            xs = jax.lax.slice(
                x, (0, kh, kw, 0),
                (n, kh + (ho - 1) * stride + 1, kw + (wo - 1) * stride + 1, c),
                (1, stride, stride, 1))
            acc = acc + xs.astype(acc_dtype) * w[kh, kw, :].astype(acc_dtype)
    if quant:
        xf = acc.astype(jnp.float32) * a_scale * w_scale
    else:
        xf = acc
    if bias is not None:
        xf = xf + bias
    xf = act_fn(act)(xf)
    if out_scale is not None:
        q = jnp.clip(jnp.round(xf / out_scale), -127, 127)
        return q.astype(jnp.int8)
    return xf.astype(out_dtype)


def dwc1d_causal(x: jax.Array, w: jax.Array,
                 bias: Optional[jax.Array] = None,
                 act: str = "none", out_dtype=jnp.float32) -> jax.Array:
    """Causal depthwise temporal conv (mamba / RG-LRU frontend).

    x: [B, L, C] float, w: [k, C], bias: [C].
    """
    k = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    acc = jnp.zeros_like(x, dtype=jnp.float32)
    l = x.shape[1]
    for i in range(k):
        acc = acc + xp[:, i:i + l, :].astype(jnp.float32) * w[i].astype(jnp.float32)
    if bias is not None:
        acc = acc + bias
    return act_fn(act)(acc).astype(out_dtype)


# ---------------------------------------------------------------------------
# C5: Low-Channel Conv Unit -- first-layer conv (small IC)
# ---------------------------------------------------------------------------

def low_channel_conv(x: jax.Array, w: jax.Array, bias: Optional[jax.Array],
                     stride: int, act: str = "none",
                     a_scale: Optional[jax.Array] = None,
                     w_scale: Optional[jax.Array] = None,
                     out_scale: Optional[jax.Array] = None,
                     out_dtype=jnp.float32) -> jax.Array:
    """Standard conv on pre-padded input (VALID), small IC.

    x: [N, H, W, IC], w: [k, k, IC, OC], bias: [OC].
    """
    k = w.shape[0]
    n, h, wd, ic = x.shape
    oc = w.shape[-1]
    ho = (h - k) // stride + 1
    wo = (wd - k) // stride + 1
    quant = a_scale is not None
    acc_dtype = jnp.int32 if quant else jnp.float32
    acc = jnp.zeros((n, ho, wo, oc), acc_dtype)
    for kh in range(k):
        for kw in range(k):
            xs = jax.lax.slice(
                x, (0, kh, kw, 0),
                (n, kh + (ho - 1) * stride + 1, kw + (wo - 1) * stride + 1, ic),
                (1, stride, stride, 1))
            tap = jnp.einsum("nhwc,co->nhwo", xs.astype(acc_dtype),
                             w[kh, kw].astype(acc_dtype))
            acc = acc + tap
    xf = acc.astype(jnp.float32)
    if quant:
        xf = xf * a_scale * w_scale
    if bias is not None:
        xf = xf + bias
    xf = act_fn(act)(xf)
    if out_scale is not None:
        q = jnp.clip(jnp.round(xf / out_scale), -127, 127)
        return q.astype(jnp.int8)
    return xf.astype(out_dtype)


# ---------------------------------------------------------------------------
# C6: MISC core -- fused elementwise / pooling
# ---------------------------------------------------------------------------

def misc_add(a: jax.Array, b: jax.Array,
             sa: float = 1.0, sb: float = 1.0, act: str = "none",
             out_scale: Optional[jax.Array] = None,
             out_dtype=jnp.float32) -> jax.Array:
    x = a.astype(jnp.float32) * sa + b.astype(jnp.float32) * sb
    x = act_fn(act)(x)
    if out_scale is not None:
        q = jnp.clip(jnp.round(x / out_scale), -127, 127)
        return q.astype(jnp.int8)
    return x.astype(out_dtype)


def avgpool2d(x: jax.Array, window: int, stride: int,
              out_dtype=jnp.float32) -> jax.Array:
    """[N, H, W, C] average pool, VALID."""
    s = jax.lax.reduce_window(
        x.astype(jnp.float32), 0.0, jax.lax.add,
        (1, window, window, 1), (1, stride, stride, 1), "VALID")
    return (s / (window * window)).astype(out_dtype)


def maxpool2d(x: jax.Array, window: int, stride: int) -> jax.Array:
    if jnp.issubdtype(x.dtype, jnp.floating):
        init = -jnp.inf
    else:  # int8 path: the MISC comparator works on quantized values directly
        init = jnp.array(jnp.iinfo(x.dtype).min, x.dtype)
    return jax.lax.reduce_window(
        x, init, jax.lax.max,
        (1, window, window, 1), (1, stride, stride, 1), "VALID")


def global_avgpool(x: jax.Array, out_dtype=jnp.float32) -> jax.Array:
    return jnp.mean(x.astype(jnp.float32), axis=(1, 2)).astype(out_dtype)


# ---------------------------------------------------------------------------
# Attention oracle (for the flash kernel / flash-decode combine)
# ---------------------------------------------------------------------------

def attention(q: jax.Array, k: jax.Array, v: jax.Array,
              causal: bool = True, window: int = 0,
              logit_softcap: float = 0.0, scale: Optional[float] = None) -> jax.Array:
    """q: [B, Hq, Lq, D], k/v: [B, Hkv, Lk, D] (GQA by head repetition)."""
    b, hq, lq, d = q.shape
    hkv = k.shape[1]
    if hkv != hq:
        rep = hq // hkv
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    scale = scale if scale is not None else d ** -0.5
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    if logit_softcap > 0:
        logits = softcap(logits, logit_softcap)
    lk = k.shape[2]
    qpos = jnp.arange(lq)[:, None] + (lk - lq)
    kpos = jnp.arange(lk)[None, :]
    mask = jnp.ones((lq, lk), bool)
    if causal:
        mask = mask & (kpos <= qpos)
    if window > 0:
        mask = mask & (kpos > qpos - window)
    logits = jnp.where(mask[None, None], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v)


# ---------------------------------------------------------------------------
# Paged KV gather (block-table indexed cache -> slot-ordered dense view)
# ---------------------------------------------------------------------------

def paged_gather(pool: jax.Array, tables: jax.Array) -> jax.Array:
    """Gather block-paged cache state into a slot-ordered dense view.

    pool:   [N, P, ...] -- N blocks of P positions (K, V, or scale pools).
    tables: [B, M] int32 -- block id of slot b's m-th page (values are
            clipped into [0, N-1], so sentinel/unallocated entries read
            SOME finite block whose data the decode mask discards).
    Returns [B, M*P, ...]: the exact values the dense cache would hold at
    every in-length position -- a pure copy, the paged/dense bit-identity
    anchor the Pallas kernel is checked against.
    """
    n, p = pool.shape[0], pool.shape[1]
    b, m = tables.shape
    blk = jnp.clip(tables, 0, n - 1)
    flat = (blk[..., None] * p + jnp.arange(p)[None, None, :]
            ).reshape(b, m * p)
    return pool.reshape((n * p,) + pool.shape[2:])[flat]
