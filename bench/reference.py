"""The plain reference: the configuration's layer list in jax.numpy.

float32 throughout, every conv and matmul at HIGHEST precision (on a TPU a
float32 matmul otherwise runs in bfloat16 passes).  It reads the layer list
of bench/model.py and the float weights the benchmark made from the seed;
it imports nothing of the program and takes nothing the program made.

`bits` gives the lower-precision control: the same forward with every
conv, depthwise and classifier input fake-quantized to `bits`-bit integers
(one static scale per tensor, from the absmax over the calibration batch,
as the reference measures it itself) and every weight to `bits`-bit
integers with one scale per output channel.  `bits=4` is the control that
the check must fail; `bits=8` is a witness of what int8 alone costs.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.model import Layer, get_param, layers

HIGHEST = jax.lax.Precision.HIGHEST
QUANT_KINDS = ("conv", "dw", "fc")


def _act(x, act: str):
    if act == "relu":
        return jnp.maximum(x, 0.0)
    if act == "relu6":
        return jnp.clip(x, 0.0, 6.0)
    return x


def _fake_quant(x, scale, bits: int):
    top = 2.0 ** (bits - 1) - 1
    return jnp.clip(jnp.round(x / scale), -top, top) * scale


def _weight_q(w, bits: int):
    top = 2.0 ** (bits - 1) - 1
    axes = tuple(range(w.ndim - 1))
    scale = jnp.maximum(jnp.max(jnp.abs(w), axis=axes, keepdims=True),
                        1e-12) / top
    return jnp.clip(jnp.round(w / scale), -top, top) * scale


def _linear(l: Layer, x, w):
    """The layer's conv, depthwise conv or matmul, without bias."""
    if l.kind == "conv":
        return jax.lax.conv_general_dilated(
            x, w, (l.stride, l.stride), l.padding,
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST)
    if l.kind == "dw":
        return jax.lax.conv_general_dilated(
            x, w[:, :, None, :], (l.stride, l.stride), l.padding,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=l.cout, precision=HIGHEST)
    return jnp.dot(x, w, precision=HIGHEST)


def _layer(l: Layer, ins, params, bits: Optional[int], in_scale):
    if l.kind in QUANT_KINDS:
        w, b = get_param(params, l.w), get_param(params, l.b)
        x = ins[0]
        if bits is not None:
            x = _fake_quant(x, in_scale, bits)
            w = _weight_q(w, bits)
        return _act(_linear(l, x, w) + b, l.act)
    if l.kind == "add":
        return _act(ins[0] + ins[1], l.act)
    if l.kind == "maxpool":
        return jax.lax.reduce_window(ins[0], -jnp.inf, jax.lax.max,
                                     (1, l.k, l.k, 1),
                                     (1, l.stride, l.stride, 1), l.padding)
    if l.kind == "gap":
        return jnp.mean(ins[0], axis=(1, 2))
    raise ValueError(f"unknown layer kind {l.kind!r}")


def fold_batch_norm(ls: List[Layer], weights: dict, x,
                    beta: float) -> dict:
    """Batch norm folded into the weights, as a deployed model has it:
    walking the layers over the batch x, every conv and depthwise conv gets
    the scale and bias that give each of its output channels mean `beta`
    and unit variance over x, and the classifier mean 0.  `weights` maps
    each layer's weight path to its array; returns weight and bias paths."""
    out = {}
    vals = {"input": x}
    for l in ls:
        ins = [vals[i] for i in l.inputs]
        if l.kind in QUANT_KINDS:
            w = weights[l.w]
            y = _linear(l, ins[0], w)
            axes = tuple(range(y.ndim - 1))
            mu = jnp.mean(y, axis=axes)
            inv = jax.lax.rsqrt(jnp.var(y, axis=axes) + 1e-5)
            shift = beta if l.kind != "fc" else 0.0
            out[l.w], out[l.b] = w * inv, shift - mu * inv
            vals[l.name] = _act((y - mu) * inv + shift, l.act)
        else:
            vals[l.name] = _layer(l, ins, None, None, None)
    return out


def _forward(ls: List[Layer], params, x, bits=None, scales=None,
             record=False):
    vals: Dict[str, jax.Array] = {"input": x}
    absmax: Dict[str, jax.Array] = {}
    for l in ls:
        ins = [vals[i] for i in l.inputs]
        if record and l.kind in QUANT_KINDS:
            absmax[l.name] = jnp.max(jnp.abs(ins[0]))
        scale = (scales[l.name] if bits is not None and l.kind in QUANT_KINDS
                 else None)
        vals[l.name] = _layer(l, ins, params, bits, scale)
    return vals[ls[-1].name], absmax


@functools.lru_cache(maxsize=None)
def _jitted(ls: Tuple[Layer, ...], bits: Optional[int]):
    """One jitted forward per layer list and precision, shared by every
    Reference of that configuration in the process."""
    return jax.jit(lambda p, s, x: _forward(ls, p, x, bits=bits,
                                            scales=s)[0])


@functools.lru_cache(maxsize=None)
def _jitted_absmax(ls: Tuple[Layer, ...]):
    return jax.jit(lambda p, x: _forward(ls, p, x, record=True)[1])


class Reference:
    """Jitted reference forward of one configuration, run in fixed blocks
    of images so that it fits beside nothing else on the chip."""

    def __init__(self, cfg: dict, params, block: int = 32,
                 bits: Optional[int] = None, calib=None):
        ls = tuple(layers(cfg))
        self.params = params
        self.block = block
        self.scales = None
        if bits is not None:
            top = 2.0 ** (bits - 1) - 1
            absmax = _jitted_absmax(ls)(params, jnp.asarray(calib))
            self.scales = {k: jnp.maximum(v, 1e-12) / top
                           for k, v in absmax.items()}
        self._run = _jitted(ls, bits)

    def __call__(self, images: np.ndarray) -> np.ndarray:
        """images [N, H, W, C] float32 -> logits [N, classes] float32."""
        n = len(images)
        out = []
        with jax.default_matmul_precision("highest"):
            for i in range(0, n, self.block):
                chunk = images[i:i + self.block]
                pad = self.block - len(chunk)
                if pad:
                    chunk = np.concatenate(
                        [chunk, np.zeros((pad,) + chunk.shape[1:],
                                         chunk.dtype)])
                y = self._run(self.params, self.scales, jnp.asarray(chunk))
                out.append(np.asarray(y)[:self.block - pad])
        return np.concatenate(out)[:n]
