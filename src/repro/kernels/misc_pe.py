"""MISC core: fused elementwise / pooling epilogues.

Paper (Section III-A, C6): element-wise addition, pooling and activations run
on AIE cores instead of PL DSPs, saving 95.8% of DSP slices.  The TPU
analogue of "keep it in the compute array" is "keep it in VMEM in one fused
kernel" -- a residual add + requant that would otherwise be two HBM
round-trips becomes a single pass.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels.dwc_pe import phase_windows
from repro.kernels.ref import act_fn


def _add_kernel(a_ref, b_ref, o_ref, *, sa: float, sb: float, act: str,
                out_scale: Optional[float]):
    x = a_ref[...].astype(jnp.float32) * sa + b_ref[...].astype(jnp.float32) * sb
    x = act_fn(act)(x)
    if out_scale is not None:
        x = jnp.clip(jnp.round(x / out_scale), -127, 127)
    o_ref[...] = x.astype(o_ref.dtype)


def misc_add(a: jax.Array, b: jax.Array, sa: float = 1.0, sb: float = 1.0,
             act: str = "none", out_scale: Optional[float] = None,
             out_dtype=jnp.float32, *, block: int = 1024,
             interpret: bool = False) -> jax.Array:
    """Fused scaled add (+ activation + requant). a, b same shape."""
    shape = a.shape
    n = 1
    for d in shape:
        n *= d
    # Flatten to [rows, 128] lanes; pad rows to the block size.
    lanes = 128
    rows = (n + lanes - 1) // lanes
    rows_p = ((rows + block - 1) // block) * block
    af = jnp.pad(a.reshape(-1), (0, rows_p * lanes - n)).reshape(rows_p, lanes)
    bf = jnp.pad(b.reshape(-1), (0, rows_p * lanes - n)).reshape(rows_p, lanes)
    odt = jnp.int8 if out_scale is not None else out_dtype
    out = pl.pallas_call(
        functools.partial(_add_kernel, sa=sa, sb=sb, act=act,
                          out_scale=out_scale),
        grid=(rows_p // block,),
        in_specs=[pl.BlockSpec((block, lanes), lambda i: (i, 0)),
                  pl.BlockSpec((block, lanes), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block, lanes), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows_p, lanes), odt),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(af, bf)
    return out.reshape(-1)[:n].reshape(shape)


def _avgpool_kernel(x_ref, o_ref, *, window: int, stride: int,
                    ho: int, wo: int):
    acc = jnp.zeros((ho, wo, x_ref.shape[-1]), jnp.float32)
    phases = [x_ref[0, 0, p] for p in range(stride * stride)]
    for kh in range(window):
        for kw in range(window):
            # stride phase (kh % s, kw % s) at offset (kh // s, kw // s)
            x = phases[(kh % stride) * stride + kw % stride]
            acc = acc + x[kh // stride:kh // stride + ho,
                          kw // stride:kw // stride + wo, :].astype(
                              jnp.float32)
    o_ref[0] = (acc / (window * window)).astype(o_ref.dtype)


def avgpool2d(x: jax.Array, window: int, stride: int,
              out_dtype=jnp.float32, *, bc: int = 128,
              interpret: bool = False) -> jax.Array:
    """[N, H, W, C] VALID average pool (C % bc == 0).  The wrapper splits
    the map into its stride phases (dwc_pe.phase_windows), so the kernel
    reads every window tap as a unit-stride slice."""
    n, h, w, c = x.shape
    assert c % bc == 0
    ho = (h - window) // stride + 1
    wo = (w - window) // stride + 1
    xp = phase_windows(x, stride, stride, [0], -(-h // stride))
    return pl.pallas_call(
        functools.partial(_avgpool_kernel, window=window, stride=stride,
                          ho=ho, wo=wo),
        grid=(n, c // bc),
        in_specs=[pl.BlockSpec((1, 1) + xp.shape[2:5] + (bc,),
                               lambda i, j: (i, 0, 0, 0, 0, j))],
        out_specs=pl.BlockSpec((1, ho, wo, bc), lambda i, j: (i, 0, 0, j)),
        out_shape=jax.ShapeDtypeStruct((n, ho, wo, c), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(xp)
