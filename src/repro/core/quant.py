"""INT8 symmetric quantization (Vitis-AI-style) for the DPUV4E engines.

The paper requires all models to be quantized to INT8 before running on the
DPU (Section III-A).  We implement the TPU-side equivalent:

  * per-output-channel symmetric weight quantization (scale = absmax/127),
  * per-tensor (static, calibrated) or per-token (dynamic) activation
    quantization,
  * int32 accumulation with a fused dequant -> bias -> activation -> requant
    epilogue (the NL core's job, Section IV-B2),
  * per-group asymmetric int4 weight-only packing (`Q4Tensor`) for the
    weight-bandwidth-bound LM decode GEMMs: two nibbles per byte along the
    reduction dim, one (scale, zero) pair per `group_size` rows per output
    channel, dequantized in-register by the Conv-PE kernel.

All functions are jit-safe and shard-transparent (elementwise + reductions).
"""
from __future__ import annotations

import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

INT8_MAX = 127.0
INT4_LEVELS = 15.0                  # asymmetric codes in [0, 15]


class QTensor(NamedTuple):
    """A quantized tensor: int8 values + float32 scale (broadcastable)."""
    q: jax.Array          # int8
    scale: jax.Array      # f32, shape broadcastable against q along quant axis

    @property
    def shape(self):
        return self.q.shape

    def dequant(self, dtype=jnp.float32) -> jax.Array:
        return (self.q.astype(jnp.float32) * self.scale).astype(dtype)


class Q4Tensor(NamedTuple):
    """An int4 weight-only packed tensor (XEGEMM_INT4-style).

    Two 4-bit codes per byte along the reduction dim K (row 2i in the low
    nibble of byte-row i, row 2i+1 in the high nibble), with one asymmetric
    (scale, zero) pair per `group_size` K-rows per output column:

        w[k, n] = code[k, n] * scale[k // gs, n] + zero[k // gs, n]

    All fields are arrays, so the container is a plain jax pytree (jit /
    device_put / sharding transparent); the group size is derived from the
    shapes, which keeps it static under tracing.
    """
    packed: jax.Array     # uint8 [K // 2, N], two codes per byte
    scale: jax.Array      # f16 [K // gs, N]
    zero: jax.Array       # f16 [K // gs, N]

    @property
    def shape(self):
        return (2 * self.packed.shape[0],) + self.packed.shape[1:]

    @property
    def group_size(self) -> int:
        return (2 * self.packed.shape[0]) // self.scale.shape[0]

    def dequant(self, dtype=jnp.float32) -> jax.Array:
        k, n = self.shape
        g = self.scale.shape[0]
        codes = unpack_int4(self.packed).reshape(g, k // g, n)
        w = (codes.astype(jnp.float32) * self.scale.astype(jnp.float32)[:, None]
             + self.zero.astype(jnp.float32)[:, None])
        return w.reshape(k, n).astype(dtype)


def snap_group_size(k: int, group_size: int) -> int:
    """Largest divisor of K that is <= group_size and even (nibble pairs
    never straddle a group boundary).  K must be even."""
    if k % 2:
        raise ValueError(f"int4 packing needs an even reduction dim, got {k}")
    gs = math.gcd(int(group_size), k)
    if gs % 2:
        gs = math.gcd(2 * gs, k)    # K even => this lands on an even divisor
    return gs


def unpack_int4(packed: jax.Array) -> jax.Array:
    """[K//2, N] packed bytes -> [K, N] int8 codes in [0, 15]."""
    low = (packed & 0xF).astype(jnp.int8)
    high = (packed >> 4).astype(jnp.int8)
    k2, n = packed.shape
    return jnp.stack([low, high], axis=1).reshape(2 * k2, n)


def pack_int4(w: jax.Array, group_size: int = 64) -> Q4Tensor:
    """Per-group asymmetric int4 packing of a [K, N] GEMM weight.

    scale = (max - min) / 15 and zero = min per (group, column), so the
    codes span the full [0, 15] range of each group.  Scales and zeros are
    stored f16 -- with the default group of 64 that prices the container at
    ~0.55x of the int8 + per-channel-scale layout.
    """
    if w.ndim != 2:
        raise ValueError(f"pack_int4 expects a 2-D GEMM weight, got {w.shape}")
    k, n = w.shape
    gs = snap_group_size(k, group_size)
    g = k // gs
    wg = w.astype(jnp.float32).reshape(g, gs, n)
    lo = jnp.min(wg, axis=1)
    hi = jnp.max(wg, axis=1)
    # Round scale/zero to their stored f16 values BEFORE coding, so the codes
    # minimize error against exactly what dequant will multiply/add.
    scale = jnp.maximum(((hi - lo) / INT4_LEVELS).astype(jnp.float16),
                        jnp.float16(1e-6))
    zero = lo.astype(jnp.float16)
    s32 = scale.astype(jnp.float32)[:, None]
    z32 = zero.astype(jnp.float32)[:, None]
    codes = jnp.clip(jnp.round((wg - z32) / s32), 0, 15)
    codes = codes.reshape(k, n).astype(jnp.uint8)
    packed = (codes[0::2] | (codes[1::2] << 4)).astype(jnp.uint8)
    return Q4Tensor(packed, scale, zero)


def container_nbytes(w) -> int:
    """Weight-container bytes as shipped to the PE (QTensor / Q4Tensor /
    raw array)."""
    if isinstance(w, (QTensor, Q4Tensor)):
        return sum(int(a.size) * a.dtype.itemsize for a in w)
    return int(w.size) * w.dtype.itemsize


def _absmax(x: jax.Array, axis, keepdims=True) -> jax.Array:
    return jnp.max(jnp.abs(x.astype(jnp.float32)), axis=axis, keepdims=keepdims)


def quantize(x: jax.Array, axis: Optional[int] = None) -> QTensor:
    """Symmetric int8 quantization.

    axis=None   -> per-tensor scale.
    axis=k      -> per-channel scales along all dims *except* k reduced;
                   i.e. one scale per index of dim k (weights: axis=out_dim).
    """
    if axis is None:
        amax = _absmax(x, axis=None, keepdims=False)
        scale = jnp.maximum(amax / INT8_MAX, 1e-8)
        q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127)
        return QTensor(q.astype(jnp.int8), scale.astype(jnp.float32))
    axis = axis % x.ndim
    red = tuple(i for i in range(x.ndim) if i != axis)
    amax = _absmax(x, axis=red, keepdims=True)
    scale = jnp.maximum(amax / INT8_MAX, 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127)
    return QTensor(q.astype(jnp.int8), scale.astype(jnp.float32))


def quantize_act_dynamic(x: jax.Array, per_token: bool = True) -> QTensor:
    """Dynamic activation quantization: scale per leading-dims row (token)."""
    if per_token:
        amax = _absmax(x, axis=-1, keepdims=True)
    else:
        amax = _absmax(x, axis=None, keepdims=False)
    scale = jnp.maximum(amax / INT8_MAX, 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127)
    return QTensor(q.astype(jnp.int8), scale.astype(jnp.float32))


def quantize_static(x: jax.Array, scale: jax.Array) -> jax.Array:
    """Quantize with a pre-calibrated scale; returns int8 values only."""
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127)
    return q.astype(jnp.int8)


def fake_quant(x: jax.Array, axis: Optional[int] = None) -> jax.Array:
    """Quantize-dequantize (QAT-style straight-through value)."""
    qt = quantize(x, axis)
    return qt.dequant(x.dtype)


class Calibrator:
    """Running absmax calibration over representative batches (per-tensor)."""

    def __init__(self):
        self.amax = {}

    def observe(self, name: str, x: jax.Array) -> None:
        v = float(jnp.max(jnp.abs(x)))
        self.amax[name] = max(self.amax.get(name, 0.0), v)

    def scales(self) -> dict:
        return {k: max(v / INT8_MAX, 1e-8) for k, v in self.amax.items()}


# ---------------------------------------------------------------------------
# Weight-tree quantization: walk a param pytree and quantize matmul weights.
# ---------------------------------------------------------------------------

def quantize_param_tree(params, predicate=None):
    """Quantize every rank>=2 float leaf to (int8, scale) along its last dim.

    Returns a pytree of the same structure where quantized leaves become
    QTensor namedtuples.  `predicate(path, leaf)` may veto quantization
    (e.g. embeddings, norm scales, conv depthwise taps stay fp).
    """
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    treedef = jax.tree_util.tree_structure(params)
    out = []
    for path, leaf in flat:
        keep = (
            leaf.ndim >= 2
            and jnp.issubdtype(leaf.dtype, jnp.floating)
            and (predicate is None or predicate(path, leaf))
        )
        out.append(quantize(leaf, axis=leaf.ndim - 1) if keep else leaf)
    return jax.tree_util.tree_unflatten(treedef, out)


def requantize(acc_i32: jax.Array, in_scale: jax.Array, w_scale: jax.Array,
               out_scale: Optional[jax.Array] = None) -> jax.Array:
    """int32 accumulator -> float (or int8 when out_scale given)."""
    x = acc_i32.astype(jnp.float32) * in_scale * w_scale
    if out_scale is None:
        return x
    q = jnp.clip(jnp.round(x / out_scale), -127, 127)
    return q.astype(jnp.int8)
