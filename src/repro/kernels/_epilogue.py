"""In-register fused-epilogue chain: the math every PE tail shares.

`passes.fuse_epilogues` folds {residual add, avg/global/max pool tail,
activation, requant} chains into the producing Conv/DWC launch.  This module
is the single definition of that chain's VALUE semantics, applied to the
PE's post-activation output while it is still in registers/VMEM:

  * the Pallas kernels (conv_pe, dwc_pe, low_channel) call `fused_chain`
    inside their epilogue, so the whole chain is one launch with no
    intermediate tensor materialized;
  * the ref / baseline backends call it from kernels/ops.py on the full
    array -- XLA fuses it into the surrounding computation, and it serves as
    the bit-exact oracle for the Pallas path.

Static programs (mid_scale given) quantize-dequantize IN-REGISTER at
exactly the interior edge scales the unfused program materialized tensors
at, so fused int8 execution is bit-identical to running the ops separately:
the value stream is unchanged, only the memory traffic disappears.  Dynamic
programs (mid_scale None) run the chain in f32.
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels.ref import act_fn


def _qdq(x: jnp.ndarray, scale: float) -> jnp.ndarray:
    """In-register requant to an interior edge scale: integer-valued f32."""
    return jnp.clip(jnp.round(x / scale), -127.0, 127.0)


def _taps(x, k: int, stride: int):
    """VALID pooling windows as strided tap slices over the trailing
    (H, W, C) dims -- the same unrolled-tap walk the PE kernels use."""
    h, w = x.shape[-3], x.shape[-2]
    ho = (h - k) // stride + 1
    wo = (w - k) // stride + 1
    for kh in range(k):
        for kw in range(k):
            yield x[..., kh:kh + (ho - 1) * stride + 1:stride,
                    kw:kw + (wo - 1) * stride + 1:stride, :]


def scratch_taps(scr):
    """In-kernel `_taps`: Mosaic slices loaded values at unit stride only,
    so the pre-pool [H, W, C] map is staged in the f32 VMEM scratch `scr`
    and each window is a strided load from it (pool inputs are f32 or
    int32 codes, both exact in f32)."""
    def taps(x, k: int, stride: int):
        h, w = x.shape[-3], x.shape[-2]
        ho = (h - k) // stride + 1
        wo = (w - k) // stride + 1
        scr[...] = x.astype(scr.dtype)
        for kh in range(k):
            for kw in range(k):
                yield scr[pl.ds(kh, ho, stride=stride),
                          pl.ds(kw, wo, stride=stride), :].astype(x.dtype)
    return taps


def tap_scratch(pool: str, h: int, w: int, c: int):
    """Scratch shapes a kernel declares for `scratch_taps` (windowed pools
    only; the global pool reduces in-register)."""
    if pool in ("avg", "max"):
        return [pltpu.VMEM((h, w, c), jnp.float32)]
    return []


def fused_chain(x: jnp.ndarray, *,
                mid_scale: Optional[float] = None,
                residual: Optional[jnp.ndarray] = None,
                res_scale: float = 1.0,
                add_act: str = "none",
                add_scale: Optional[float] = None,
                pool: str = "none",
                pool_kernel: int = 0,
                pool_stride: int = 0,
                out_scale: Optional[float] = None,
                taps=None) -> jnp.ndarray:
    """Apply a fused Epilogue chain to the PE output.

    x: f32 [..., H, W, C], the conv/dwc result AFTER its own bias +
    activation, BEFORE any requant.  residual: raw operand values (int8 or
    f32), same shape.  Scales are compile-time python floats (static chain)
    or None (dynamic f32 chain).  Returns int8 for a static chain ending in
    a requant (or the scale-preserving max tail), f32 otherwise.  `taps`
    walks the avg/max windows: `scratch_taps(ref)` inside a kernel.
    """
    taps = taps or _taps
    static = mid_scale is not None
    if static:
        x = _qdq(x, mid_scale)                 # the absorbed conv edge
    cur = mid_scale
    if residual is not None:
        r = residual.astype(jnp.float32) * res_scale
        x = (x * mid_scale + r) if static else (x + r)
        x = act_fn(add_act)(x)
        if static:
            cur = add_scale if pool != "none" else out_scale
            if cur is not None:
                x = _qdq(x, cur)               # the absorbed add edge
    if pool == "none":
        if static and cur is not None:
            return x.astype(jnp.int8)
        return x
    if pool == "max":
        # Order-preserving on the quantized values: scale passes through,
        # exactly like the standalone max pool's scale-preserving rule.
        y = None
        for t in taps(x, pool_kernel, pool_stride):
            y = t if y is None else jnp.maximum(y, t)
        return y.astype(jnp.int8) if static else y
    if pool == "global":
        if static:
            # Sum in int32 like every engine accumulator, then one fused
            # scale + requant -- the executor's standalone GAP, in-register.
            px = x.shape[-3] * x.shape[-2]
            acc = jnp.sum(x.astype(jnp.int32), axis=(-3, -2))
            y = acc.astype(jnp.float32) * (cur / px)
        else:
            y = jnp.mean(x, axis=(-3, -2))
    else:                                       # avg
        if static:
            acc = None
            for t in taps(x.astype(jnp.int32), pool_kernel, pool_stride):
                acc = t if acc is None else acc + t
            y = acc.astype(jnp.float32) * (cur / pool_kernel ** 2)
        else:
            acc = None
            for t in taps(x, pool_kernel, pool_stride):
                acc = t if acc is None else acc + t
            y = acc / pool_kernel ** 2
    if static and out_scale is not None:
        return jnp.clip(jnp.round(y / out_scale), -127, 127).astype(jnp.int8)
    return y


def chain_out_dtype(mid_scale, pool: str, out_scale, out_dtype):
    """The dtype `fused_chain` emits (for kernel out_shape declarations)."""
    if mid_scale is not None and (out_scale is not None or pool == "max"):
        return jnp.int8
    return out_dtype


def pooled_hw(ho: int, wo: int, pool: str, k: int, stride: int):
    """Output spatial dims after the chain's pool stage."""
    if pool == "none":
        return ho, wo
    if pool == "global":
        return 1, 1
    return (ho - k) // stride + 1, (wo - k) // stride + 1
