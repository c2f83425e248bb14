"""Public kernel wrappers: backend selection, padding, DSE-chosen blocks.

Every op has three backends:
  * "ref"     -- pure-jnp oracle (kernels/ref.py): the test oracle and the
                 dry-run lowering path.
  * "pallas"  -- the Pallas TPU kernel, compiled through Mosaic on a TPU and
                 interpreted only where JAX's default backend is the CPU
                 (`interpret_pallas`).
  * baseline  -- the XVDPU-analog unfused path (ref.matmul_int8_unfused).

Wrappers own all shape legalization: flattening leading dims, padding M/N/K
to block multiples (the paper's bank-alignment / zero-padding steps), and
channel padding to the 128-lane width for the DWC engine.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import dse
from repro.core.config import EngineConfig
from repro.core.quant import (Q4Tensor, QTensor, quantize_act_dynamic,
                              quantize_static)
from repro.kernels import _epilogue, conv_pe, dwc_pe, low_channel, misc_pe, ref

# Quant modes with int8 activations on the Conv PE fabric (w4a8 packs LM
# projection *weights* to int4; everything else runs exactly like w8a8).
_INT8_ACTS = ("w8a8", "w4a8")


def interpret_pallas() -> bool:
    """The one place that decides Pallas interpret mode: kernels interpret
    only on the CPU backend (tests); on a TPU they always compile."""
    return jax.default_backend() == "cpu"


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _chain_kwargs(ep, static: bool, out_scale):
    """Epilogue spec -> kernels/_epilogue.fused_chain kwargs.  Static
    programs carry the interior requant points; dynamic chains run f32."""
    return dict(
        mid_scale=ep.mid_scale if static and ep.mid_scale else None,
        add_act=ep.add_act,
        add_scale=ep.add_scale if static and ep.add_scale else None,
        pool=ep.pool, pool_kernel=ep.pool_kernel, pool_stride=ep.pool_stride,
        out_scale=out_scale if static else None)


def _pad2d(x: jax.Array, m: int, n: int) -> jax.Array:
    pm, pn = m - x.shape[0], n - x.shape[1]
    if pm == 0 and pn == 0:
        return x
    return jnp.pad(x, ((0, pm), (0, pn)))


def pick_blocks(m: int, n: int, k: int, in_bytes: int,
                cfg: EngineConfig):
    """Block shapes: explicit config overrides, else the DSE solver."""
    if cfg.block_m and cfg.block_n and cfg.cascade_bk:
        return cfg.block_m, cfg.block_n, cfg.cascade_bk
    t = dse.solve_conv_blocks(m, n, k, in_dtype_bytes=in_bytes)
    bm = min(t.bm, _round_up(m, 128))
    bn = min(t.bn, _round_up(n, 128))
    bk = min(t.bk, _round_up(k, 128))
    return bm, bn, bk


# ---------------------------------------------------------------------------
# Conv PE: quantized linear (the LM projection / 1x1-conv path)
# ---------------------------------------------------------------------------

def linear_int8(x, w: QTensor, bias: Optional[jax.Array],
                act: str, cfg: EngineConfig,
                out_dtype=jnp.float32,
                out_scale=None,
                residual: Optional[jax.Array] = None,
                res_scale: float = 1.0,
                mid_scale: Optional[float] = None,
                add_act: str = "none") -> jax.Array:
    """x: float [..., K] (dynamic per-token act quant) OR QTensor with a
    static pre-calibrated per-tensor scale (the compiled engine-program
    path); w: QTensor(q=[K, N] int8, scale=[1, N]).

    out_scale: static requant scale -> int8 output via the NL epilogue
    (activations stay int8 engine-to-engine); a per-output-channel tuple
    requants each channel at its own scale (a per-channel edge feeding the
    channelwise DWC engine); None -> float output.

    residual [..., N] streams a fused-epilogue second operand into the
    Pallas kernel (the absorbed residual add; conv2d_pe's epilogue path);
    only the pallas backend takes it -- ref/baseline compose the chain in
    the wrapper instead.
    """
    static = isinstance(x, QTensor)
    xv = x.q if static else x
    lead = xv.shape[:-1]
    kdim = xv.shape[-1]
    n = w.q.shape[-1]
    if out_scale is not None and not isinstance(out_scale, (int, float)):
        out_scale = jnp.asarray(out_scale, jnp.float32).reshape(1, n)
    m = 1
    for d in lead:
        m *= d
    x2 = xv.reshape(m, kdim)
    if static:
        xq = QTensor(x2, jnp.full((m, 1), float(x.scale), jnp.float32))
    else:
        xq = quantize_act_dynamic(x2, per_token=True)      # a_scale [M, 1]
    w_scale = w.scale.reshape(1, n)

    if cfg.baseline:
        assert residual is None, "fused residual composes in the wrapper"
        out = ref.matmul_int8_unfused(xq.q, w.q, xq.scale, w_scale, bias, act,
                                      out_scale=out_scale, out_dtype=out_dtype)
    elif cfg.backend == "pallas":
        bm, bn, bk = pick_blocks(m, n, kdim, 1, cfg)
        mp, np_, kp = _round_up(m, bm), _round_up(n, bn), _round_up(kdim, bk)
        aq = _pad2d(xq.q, mp, kp)
        bq = _pad2d(w.q, kp, np_)
        asc = jnp.pad(xq.scale, ((0, mp - m), (0, 0)))
        wsc = jnp.pad(w_scale, ((0, 0), (0, np_ - n)))
        b = (jnp.pad(bias.astype(jnp.float32), (0, np_ - n))
             if bias is not None else None)
        osc = out_scale
        if out_scale is not None and not isinstance(out_scale, (int, float)):
            # per-channel requant vector: pad with 1s alongside N
            osc = jnp.pad(jnp.asarray(out_scale, jnp.float32).reshape(1, n),
                          ((0, 0), (0, np_ - n)), constant_values=1.0)
        r = (_pad2d(residual.reshape(m, n), mp, np_)
             if residual is not None else None)
        out = conv_pe.matmul_int8_fused(
            aq, bq, asc, wsc, b, act, out_scale=osc, out_dtype=out_dtype,
            residual=r, res_scale=res_scale, mid_scale=mid_scale,
            add_act=add_act, bm=bm, bn=bn, bk=bk,
            interpret=interpret_pallas())[:m, :n]
    else:
        assert residual is None, "fused residual composes in the wrapper"
        out = ref.matmul_int8_fused(xq.q, w.q, xq.scale, w_scale, bias, act,
                                    out_scale=out_scale, out_dtype=out_dtype)
    return out.reshape(*lead, n)


def linear_w4(x, w: Q4Tensor, bias: Optional[jax.Array],
              act: str, cfg: EngineConfig,
              out_dtype=jnp.float32,
              out_scale=None,
              residual: Optional[jax.Array] = None,
              res_scale: float = 1.0,
              mid_scale: Optional[float] = None,
              add_act: str = "none") -> jax.Array:
    """Int4 weight-only GEMM over int8 activations (quant='w4a8').

    x: float [..., K] (dynamic per-token act quant) OR QTensor with a static
    pre-calibrated per-tensor scale; w: Q4Tensor (packed [K//2, N] nibble
    pairs + per-group f16 scale/zero).  The Pallas kernel unpacks and
    dequantizes the weight block in-register (XEGEMM_INT4 idiom); K is never
    padded -- the kernel runs whole-K blocks so per-group partial sums stay
    exact and bitwise-match the ref oracle.  Epilogue contract (out_scale /
    residual / mid_scale / add_act) matches linear_int8.
    """
    static = isinstance(x, QTensor)
    xv = x.q if static else x
    lead = xv.shape[:-1]
    kdim = xv.shape[-1]
    n = w.packed.shape[-1]
    if out_scale is not None and not isinstance(out_scale, (int, float)):
        out_scale = jnp.asarray(out_scale, jnp.float32).reshape(1, n)
    m = 1
    for d in lead:
        m *= d
    x2 = xv.reshape(m, kdim)
    if static:
        xq = QTensor(x2, jnp.full((m, 1), float(x.scale), jnp.float32))
    else:
        xq = quantize_act_dynamic(x2, per_token=True)      # a_scale [M, 1]

    if cfg.backend == "pallas" and not cfg.baseline:
        bm, bn, _ = pick_blocks(m, n, kdim, 1, cfg)
        # the A block holds the whole K (double-buffered): keep it <= 4 MiB
        while bm > 32 and 2 * bm * kdim > 4 << 20:
            bm //= 2
        mp, np_ = _round_up(m, bm), _round_up(n, bn)
        aq = _pad2d(xq.q, mp, kdim)                        # pad M only
        asc = jnp.pad(xq.scale, ((0, mp - m), (0, 0)))
        # N padding: packed columns pad with zero codes and zero
        # scale/zero, so padded outputs are exactly 0 and slice off.
        bq = _pad2d(w.packed, kdim // 2, np_)
        wsc = jnp.pad(w.scale, ((0, 0), (0, np_ - n)))
        wz = jnp.pad(w.zero, ((0, 0), (0, np_ - n)))
        b = (jnp.pad(bias.astype(jnp.float32), (0, np_ - n))
             if bias is not None else None)
        osc = out_scale
        if out_scale is not None and not isinstance(out_scale, (int, float)):
            osc = jnp.pad(jnp.asarray(out_scale, jnp.float32).reshape(1, n),
                          ((0, 0), (0, np_ - n)), constant_values=1.0)
        r = (_pad2d(residual.reshape(m, n), mp, np_)
             if residual is not None else None)
        out = conv_pe.matmul_int4_fused(
            aq, bq, asc, wsc, wz, b, act, out_scale=osc, out_dtype=out_dtype,
            residual=r, res_scale=res_scale, mid_scale=mid_scale,
            add_act=add_act, bm=bm, bn=bn,
            interpret=interpret_pallas())[:m, :n]
    else:
        assert residual is None, "fused residual composes in the wrapper"
        out = ref.matmul_int4_fused(xq.q, w.packed, xq.scale, w.scale, w.zero,
                                    bias, act, out_scale=out_scale,
                                    out_dtype=out_dtype)
    return out.reshape(*lead, n)


def linear_w8(x: jax.Array, w: QTensor, bias: Optional[jax.Array],
              act: str, cfg: EngineConfig, out_dtype=jnp.float32) -> jax.Array:
    """Weight-only int8: dequantize weights, bf16 MAC (memory-bound decode)."""
    wf = w.dequant(x.dtype)
    out = jnp.dot(x, wf)
    if bias is not None:
        out = out + bias.astype(out.dtype)
    return ref.act_fn(act)(out).astype(out_dtype)


def linear_f(x: jax.Array, w: jax.Array, bias: Optional[jax.Array],
             act: str, cfg: EngineConfig, out_dtype=None) -> jax.Array:
    """Float path (training)."""
    out_dtype = out_dtype or x.dtype
    out = jnp.dot(x, w.astype(x.dtype))
    if bias is not None:
        out = out + bias.astype(out.dtype)
    return ref.act_fn(act)(out).astype(out_dtype)


def linear(x, w, bias, act: str, cfg: EngineConfig,
           out_dtype=None, out_scale: Optional[float] = None,
           residual: Optional[jax.Array] = None, res_scale: float = 1.0,
           mid_scale: Optional[float] = None,
           add_act: str = "none") -> jax.Array:
    """Dispatch on quant mode and weight container type.

    x may be a QTensor (pre-quantized int8 activations with a static scale);
    that path requires int8-act quant (w8a8/w4a8) + quantized weights.
    out_scale (static) requests int8 output via the fused requant epilogue.
    residual/res_scale/mid_scale/add_act thread a fused residual epilogue
    into the int8/int4 kernel (the pallas paths only).
    """
    if isinstance(w, Q4Tensor):
        if cfg.quant != "w4a8":
            raise ValueError(
                "Q4Tensor weights require quant='w4a8' (got %r)" % cfg.quant)
        return linear_w4(x, w, bias, act, cfg,
                         out_dtype=out_dtype or jnp.float32,
                         out_scale=out_scale, residual=residual,
                         res_scale=res_scale, mid_scale=mid_scale,
                         add_act=add_act)
    if isinstance(w, QTensor) and cfg.quant in _INT8_ACTS:
        return linear_int8(x, w, bias, act, cfg,
                           out_dtype=out_dtype or jnp.float32,
                           out_scale=out_scale, residual=residual,
                           res_scale=res_scale, mid_scale=mid_scale,
                           add_act=add_act)
    if residual is not None:
        raise ValueError("fused residual epilogues require quant='w8a8'/"
                         "'w4a8' with quantized weights")
    if isinstance(x, QTensor) or out_scale is not None:
        raise ValueError(
            "static int8 activations / out_scale require quant='w8a8'/'w4a8' "
            "with quantized weights (got quant=%r, w=%s)"
            % (cfg.quant, type(w).__name__))
    if isinstance(w, QTensor):
        return linear_w8(x, w, bias, act, cfg,
                         out_dtype=out_dtype or x.dtype)
    return linear_f(x, w, bias, act, cfg, out_dtype=out_dtype)


def linear_group(x, ws, bs, acts, cfg: EngineConfig,
                 out_dtype=None):
    """Fused multi-output projection group (Q/K/V, gate/up): one shared
    input, member outputs returned as a tuple.

    On the pallas int8/int4 paths the member weights concatenate along N
    into ONE kernel launch (the XEGEMM ``hgemm_qkv_wint4(q, out0, out1,
    out2, ...)`` idiom): the activation row is quantized and read once and
    every member's columns MAC in the same grid.  Column blocks never mix
    members' reductions, so slicing the fused f32 output is bitwise
    identical to member-wise launches.  Float / ref / baseline paths compose
    member-wise -- bit-identical to the unfused graph by construction.
    """
    ns = [w.shape[-1] for w in ws]
    kinds = {type(w) for w in ws}
    pallas = cfg.backend == "pallas" and not cfg.baseline
    fused = None
    if pallas and kinds == {QTensor} and cfg.quant in _INT8_ACTS:
        fused = QTensor(
            jnp.concatenate([w.q for w in ws], axis=1),
            jnp.concatenate([w.scale.reshape(1, -1) for w in ws], axis=1))
    elif pallas and kinds == {Q4Tensor} and cfg.quant == "w4a8":
        # Members share K (one input) and the snapped group size, so the
        # per-group scale/zero tables concatenate along N too.
        fused = Q4Tensor(
            jnp.concatenate([w.packed for w in ws], axis=1),
            jnp.concatenate([w.scale for w in ws], axis=1),
            jnp.concatenate([w.zero for w in ws], axis=1))
    if fused is None:
        return tuple(linear(x, w, b, a, cfg, out_dtype=out_dtype)
                     for w, b, a in zip(ws, bs, acts))
    bias = None
    if any(b is not None for b in bs):
        bias = jnp.concatenate(
            [b.astype(jnp.float32) if b is not None
             else jnp.zeros((nn,), jnp.float32) for b, nn in zip(bs, ns)])
    out = linear(x, fused, bias, "none", cfg, out_dtype=jnp.float32)
    outs, off = [], 0
    for nn, a in zip(ns, acts):
        y = out[..., off:off + nn]
        if a != "none":
            y = ref.act_fn(a)(y)
        outs.append(y.astype(out_dtype) if out_dtype is not None else y)
        off += nn
    return tuple(outs)


def linear_ep(x, w, bias, act: str, ep, residual, cfg: EngineConfig, *,
              res_scale: float = 1.0, out_scale=None,
              out_dtype=jnp.float32) -> jax.Array:
    """LinearOp with a fused epilogue: the residual add after an O/down
    projection rides the Conv PE launch (passes.fuse_epilogues on LM
    graphs never attaches pool tails to LinearOps).

    Pallas int8/int4 paths stream the residual into the kernel's NL core
    (ep.mid_scale re-quantizes the GEMM output at its pre-fusion edge
    scale); ref / baseline / float paths compose the identical chain math
    on the GEMM output (_epilogue.fused_chain, the bit-exact oracle).
    """
    static = isinstance(x, QTensor)
    quanted = ((isinstance(w, QTensor) and cfg.quant in _INT8_ACTS)
               or (isinstance(w, Q4Tensor) and cfg.quant == "w4a8"))
    pallas = (cfg.backend == "pallas" and not cfg.baseline and quanted
              and ep.pool == "none")
    if pallas:
        return linear(x, w, bias, act, cfg, out_dtype=out_dtype,
                      out_scale=out_scale, residual=residual,
                      res_scale=res_scale,
                      mid_scale=(ep.mid_scale if static and ep.mid_scale
                                 else None),
                      add_act=ep.add_act)
    y = linear(x, w, bias, act, cfg, out_dtype=jnp.float32)
    return _epilogue.fused_chain(
        y, residual=residual, res_scale=res_scale,
        **_chain_kwargs(ep, static and quanted, out_scale))


# ---------------------------------------------------------------------------
# Conv2D via Conv PE (im2col -> GEMM), the CNN standard-conv path
# ---------------------------------------------------------------------------

def conv2d_pe(x, w, bias: Optional[jax.Array],
              stride: int, padding: str, act: str,
              cfg: EngineConfig, out_dtype=jnp.float32,
              out_scale: Optional[float] = None,
              epilogue=None,
              residual: Optional[jax.Array] = None,
              res_scale: float = 1.0) -> jax.Array:
    """Standard conv: x [N,H,W,IC] float or QTensor (static int8 activations
    with a per-tensor scale); w [k,k,IC,OC] float or QTensor, or the
    compile-time-folded GEMM layout [k*k*IC, OC]
    (passes.fold_weight_layouts).

    Float x under a quant mode quantizes activations dynamically per-image;
    QTensor x skips that round-trip (the compiled engine-program path).  The
    conv lowers to the Conv PE GEMM with K = k*k*IC (the paper's IC-cascade
    contraction); out_scale requants to int8 in the fused NL epilogue.
    SAME zero-padding is exact for int8 inputs (symmetric quant, zero
    point 0).

    `epilogue` (a graph.Epilogue from passes.fuse_epilogues) runs the
    absorbed MISC tail -- residual add (`residual` raw values at
    `res_scale`), activation, avg/global/max pool, requant -- inside the
    SAME launch on the pallas backend (kernel second operand / pooled
    accumulation); the ref and baseline backends compose the identical
    chain math on the GEMM output (the bit-exact oracle).
    """
    static = isinstance(x, QTensor)
    if static and not isinstance(w, QTensor):
        x = x.dequant()                       # float weights: float math
        static = False
    xv = x.q if static else x
    wq = w.q if isinstance(w, QTensor) else w
    ic = xv.shape[-1]
    if wq.ndim == 2:
        # Pre-laid-out GEMM weights [k*k*IC, OC] (passes.fold_weight_layouts
        # ran the im2col reshape at compile time); recover the window size.
        oc = wq.shape[1]
        k = round((wq.shape[0] // ic) ** 0.5)
        if k * k * ic != wq.shape[0]:
            raise ValueError(
                f"folded conv weight K={wq.shape[0]} does not factor as "
                f"k*k*IC for IC={ic}")
        wmat = wq
    else:
        k = wq.shape[0]
        oc = wq.shape[3]
        wmat = wq.reshape(k * k * ic, oc)
    if padding == "SAME":
        ph = _same_pad(xv.shape[1], k, stride)
        pw = _same_pad(xv.shape[2], k, stride)
        xv = jnp.pad(xv, ((0, 0), ph, pw, (0, 0)))
    n, hp, wp, _ = xv.shape
    ho = (hp - k) // stride + 1
    wo = (wp - k) // stride + 1
    # im2col: [N*HO*WO, k*k*IC]
    patches = []
    for kh in range(k):
        for kw in range(k):
            xs = jax.lax.slice(
                xv, (0, kh, kw, 0),
                (n, kh + (ho - 1) * stride + 1, kw + (wo - 1) * stride + 1, ic),
                (1, stride, stride, 1))
            patches.append(xs)
    col = jnp.concatenate(patches, axis=-1).reshape(n * ho * wo, k * k * ic)
    if isinstance(w, QTensor):
        wt = QTensor(wmat, w.scale.reshape(1, oc))
        col_in = QTensor(col, x.scale) if static else col
        if epilogue is not None:
            return _conv_epilogue(col_in, wt, bias, act, epilogue, residual,
                                  res_scale, out_scale, cfg, out_dtype,
                                  n, ho, wo, oc)
        out = linear(col_in, wt, bias, act, cfg, out_dtype=out_dtype,
                     out_scale=out_scale)
    else:
        if out_scale is not None:
            raise ValueError("out_scale requires QTensor weights")
        out = linear_f(col, wmat, bias, act, cfg, out_dtype=out_dtype)
        if epilogue is not None:
            return _epilogue.fused_chain(
                out.reshape(n, ho, wo, oc), residual=residual,
                res_scale=res_scale,
                **_chain_kwargs(epilogue, False, None))
    return out.reshape(n, ho, wo, oc)


def _conv_epilogue(col_in, wt: QTensor, bias, act: str, ep, residual,
                   res_scale: float, out_scale, cfg: EngineConfig,
                   out_dtype, n: int, ho: int, wo: int, oc: int) -> jax.Array:
    """Fused Conv PE epilogue dispatch (quantized GEMM path)."""
    static = isinstance(col_in, QTensor)
    pallas = (cfg.backend == "pallas" and not cfg.baseline
              and cfg.quant in _INT8_ACTS)
    if pallas and ep.pool == "none":
        # residual second operand streams into the GEMM kernel's NL core
        out = linear(col_in, wt, bias, act, cfg, out_dtype=out_dtype,
                     out_scale=out_scale,
                     residual=residual.reshape(n * ho * wo, oc),
                     res_scale=res_scale,
                     mid_scale=ep.mid_scale if static and ep.mid_scale
                     else None,
                     add_act=ep.add_act)
        return out.reshape(n, ho, wo, oc)
    if pallas:
        return _conv_pool_pallas(col_in, wt, bias, act, ep, residual,
                                 res_scale, out_scale, cfg, out_dtype,
                                 n, ho, wo, oc)
    # ref / baseline: the GEMM part (f32, pre-requant) + the shared
    # in-register chain math -- XLA fuses it; bit-exact vs the unfused ops
    y = linear(col_in, wt, bias, act, cfg, out_dtype=jnp.float32)
    return _epilogue.fused_chain(y.reshape(n, ho, wo, oc),
                                 residual=residual, res_scale=res_scale,
                                 **_chain_kwargs(ep, static, out_scale))


def _conv_pool_pallas(col_in, wt: QTensor, bias, act: str, ep, residual,
                      res_scale: float, out_scale, cfg: EngineConfig,
                      out_dtype, n: int, ho: int, wo: int, oc: int):
    """Pooled-epilogue launch: per-image M blocking so the avg/global/max
    tail accumulates in-kernel (conv_pe.matmul_int8_pool)."""
    static = isinstance(col_in, QTensor)
    rows = ho * wo
    kdim = (col_in.q if static else col_in).shape[-1]
    if static:
        colq = col_in.q
        asc = jnp.full((n, rows, 1), float(col_in.scale), jnp.float32)
    else:
        xq = quantize_act_dynamic(col_in, per_token=True)
        colq, asc = xq.q, xq.scale.reshape(n, rows, 1)
    _, bn, bk = pick_blocks(rows, oc, kdim, 1, cfg)
    rows_p = _round_up(rows, 32)
    kp, np_ = _round_up(kdim, bk), _round_up(oc, bn)
    a3 = jnp.pad(colq.reshape(n, rows, kdim),
                 ((0, 0), (0, rows_p - rows), (0, kp - kdim)))
    asc3 = jnp.pad(asc, ((0, 0), (0, rows_p - rows), (0, 0)))
    bq = _pad2d(wt.q, kp, np_)
    wsc = jnp.pad(wt.scale.reshape(1, oc), ((0, 0), (0, np_ - oc)))
    b = (jnp.pad(bias.astype(jnp.float32), (0, np_ - oc))
         if bias is not None else None)
    r3 = None
    if residual is not None:
        r3 = jnp.pad(residual.reshape(n, rows, oc),
                     ((0, 0), (0, rows_p - rows), (0, np_ - oc)))
    if out_scale is not None and not isinstance(out_scale, (int, float)):
        raise ValueError("pooled epilogues requant per-tensor")
    out = conv_pe.matmul_int8_pool(
        a3, bq, asc3, wsc, b, act, ho=ho, wo=wo, residual=r3,
        res_scale=res_scale,
        mid_scale=ep.mid_scale if static and ep.mid_scale else None,
        add_act=ep.add_act,
        add_scale=ep.add_scale if static and ep.add_scale else None,
        pool=ep.pool, pool_kernel=ep.pool_kernel, pool_stride=ep.pool_stride,
        out_scale=out_scale if static else None, out_dtype=out_dtype,
        bn=bn, bk=bk, interpret=interpret_pallas())
    pho, pwo = _epilogue.pooled_hw(ho, wo, ep.pool, ep.pool_kernel,
                                   ep.pool_stride)
    out = out[:, :pho * pwo, :oc]
    if ep.pool == "global":
        return out.reshape(n, oc)
    return out.reshape(n, pho, pwo, oc)


def _same_pad(size: int, k: int, stride: int):
    out = -(-size // stride)
    pad = max((out - 1) * stride + k - size, 0)
    return (pad // 2, pad - pad // 2)


# ---------------------------------------------------------------------------
# DWC PE
# ---------------------------------------------------------------------------

def dwc2d(x, w, bias: Optional[jax.Array], stride: int,
          padding: str, act: str, cfg: EngineConfig,
          out_dtype=jnp.float32,
          out_scale: Optional[float] = None,
          epilogue=None,
          residual: Optional[jax.Array] = None,
          res_scale: float = 1.0) -> jax.Array:
    """Depthwise conv. x [N,H,W,C] float or QTensor (static int8 with a
    per-tensor scale); w [k,k,C] float or QTensor, possibly pre-padded to
    [k,k,round_up(C,128)] by passes.fold_weight_layouts (bias and scales
    padded alongside).  out_scale requants to int8 in the RACNL epilogue.

    `epilogue` fuses an absorbed MISC tail (residual add / pool / requant)
    into the RACNL core -- in-kernel on the pallas DWC engine, composed
    chain math elsewhere (see conv2d_pe).

    Without the DWC engine (baseline), this runs as the paper's "low
    utilization" path: dense GEMM with a channel-diagonal weight matrix.
    """
    static = isinstance(x, QTensor)
    is_q = isinstance(w, QTensor)
    if static and not is_q:
        x = x.dequant()               # float weights: float math
        static = False
    wq = w.q if is_q else w
    k = wq.shape[0]
    c = (x.q if static else x).shape[-1]
    cw = wq.shape[2]
    prepadded = cw != c
    if prepadded:
        if cw != _round_up(c, 128):
            raise ValueError(f"dwc weight channels {cw} match neither C={c} "
                             f"nor the 128-lane padded width")
        if not cfg.use_dwc_engine:
            # the dense-diagonal baseline works on true channels; un-pad
            wq = wq[:, :, :c]
            if is_q:
                w = QTensor(wq, w.scale[..., :c])
            else:
                w = wq
            if bias is not None:
                bias = bias[:c]
            prepadded = False
    if not cfg.use_dwc_engine:
        # Baseline (no DWC engine).  A grouped conv with group-count ==
        # channels is exactly a per-channel depthwise conv, so lower it
        # through the depthwise taps directly instead of materializing the
        # [k, k, C, C] channel-diagonal weight matrix and running a full
        # C**2 GEMM -- the old lowering burned O(C) compute and memory for
        # identical values (adding the off-diagonal zeros is IEEE-exact).
        # Static int8 inputs still pay the full dequant/requant round-trip
        # here -- exactly the cost the DWC engine's fused epilogue avoids.
        if static:
            x = x.dequant()
        if padding == "SAME":
            ph = _same_pad(x.shape[1], k, stride)
            pw = _same_pad(x.shape[2], k, stride)
            x = jnp.pad(x, ((0, 0), ph, pw, (0, 0)))
        wf = (w.dequant() if is_q else wq).astype(jnp.float32)
        out = ref.dwc2d(x.astype(jnp.float32), wf, bias, stride, act,
                        out_dtype=jnp.float32)
        if epilogue is not None:
            return _epilogue.fused_chain(
                out, residual=residual, res_scale=res_scale,
                **_chain_kwargs(epilogue, static, out_scale))
        if out_scale is not None:
            return quantize_static(out, jnp.float32(out_scale))
        return out.astype(out_dtype)

    quant = (is_q and cfg.quant in _INT8_ACTS) or static
    if quant:
        if static:
            xin = x.q
            # per-tensor float scale, or a per-channel [C] vector (the
            # channelwise engine dequantizes each lane at its own scale)
            a_scale = (float(x.scale) if jnp.ndim(x.scale) == 0
                       else jnp.asarray(x.scale, jnp.float32))
        else:
            xq = quantize_act_dynamic(x, per_token=False)
            a_scale = xq.scale
            xin = xq.q
        w_scale = w.scale.reshape(-1)
        w_in = w.q
    else:
        xin = x
        w_in = w.dequant(x.dtype) if is_q else w
        a_scale = w_scale = None
    if padding == "SAME":
        ph = _same_pad(xin.shape[1], k, stride)
        pw = _same_pad(xin.shape[2], k, stride)
        xin = jnp.pad(xin, ((0, 0), ph, pw, (0, 0)))

    cp = _round_up(c, 128)
    bc = min(128, cp)
    if cp != c:  # lane alignment: the paper's zero-padded weights
        xin = jnp.pad(xin, ((0, 0), (0, 0), (0, 0), (0, cp - c)))
        if not prepadded:   # else weights/bias/scales were folded at compile
            w_in = jnp.pad(w_in, ((0, 0), (0, 0), (0, cp - c)))
            if bias is not None:
                bias = jnp.pad(bias, (0, cp - c))
            if w_scale is not None:
                w_scale = jnp.pad(w_scale, (0, cp - c))
        if a_scale is not None and jnp.ndim(a_scale) == 1:
            # per-channel activation scales pad alongside the lanes
            a_scale = jnp.pad(a_scale, (0, cp - c), constant_values=1.0)

    if epilogue is not None:
        ep = epilogue
        if cfg.backend == "pallas":
            rin = residual
            if rin is not None and cp != c:
                rin = jnp.pad(rin, ((0, 0), (0, 0), (0, 0), (0, cp - c)))
            out = dwc_pe.dwc2d(
                xin, w_in, bias, stride, act,
                a_scale=a_scale if quant else None, w_scale=w_scale,
                out_scale=out_scale if static else None, out_dtype=out_dtype,
                residual=rin, res_scale=res_scale,
                mid_scale=ep.mid_scale if static and ep.mid_scale else None,
                add_act=ep.add_act,
                add_scale=ep.add_scale if static and ep.add_scale else None,
                pool=ep.pool, pool_kernel=ep.pool_kernel,
                pool_stride=ep.pool_stride, bc=bc,
                interpret=interpret_pallas())
        else:
            y = ref.dwc2d(xin, w_in, bias, stride, act,
                          a_scale=a_scale if quant else None,
                          w_scale=w_scale, out_dtype=jnp.float32)
            rin = residual
            if rin is not None and cp != c:
                rin = jnp.pad(rin, ((0, 0), (0, 0), (0, 0), (0, cp - c)))
            out = _epilogue.fused_chain(y, residual=rin, res_scale=res_scale,
                                        **_chain_kwargs(ep, static, out_scale))
        out = out[..., :c]
        if ep.pool == "global" and out.ndim == 4:
            out = out.reshape(out.shape[0], c)    # [N,1,1,C] -> [N,C]
        return out

    if cfg.backend == "pallas":
        out = dwc_pe.dwc2d(xin, w_in, bias, stride, act,
                           a_scale=a_scale if quant else None,
                           w_scale=w_scale, out_scale=out_scale,
                           out_dtype=out_dtype,
                           bc=bc, interpret=interpret_pallas())
    else:
        out = ref.dwc2d(xin, w_in, bias, stride, act,
                        a_scale=a_scale if quant else None,
                        w_scale=w_scale, out_scale=out_scale,
                        out_dtype=out_dtype)
    return out[..., :c]


def dwc1d_causal(x: jax.Array, w: jax.Array, bias: Optional[jax.Array],
                 act: str, cfg: EngineConfig) -> jax.Array:
    """Causal temporal depthwise conv. x [B,L,C] float, w [k,C]."""
    c = x.shape[-1]
    cp = _round_up(c, 128)
    if cfg.backend == "pallas" and cfg.use_dwc_engine:
        xin = jnp.pad(x, ((0, 0), (0, 0), (0, cp - c))) if cp != c else x
        w_in = jnp.pad(w, ((0, 0), (0, cp - c))) if cp != c else w
        b_in = (jnp.pad(bias, (0, cp - c)) if (bias is not None and cp != c)
                else bias)
        out = dwc_pe.dwc1d_causal(xin, w_in, b_in, act, out_dtype=x.dtype,
                                  bc=min(128, cp),
                                  interpret=interpret_pallas())
        return out[..., :c]
    return ref.dwc1d_causal(x, w, bias, act, out_dtype=x.dtype)


# ---------------------------------------------------------------------------
# Low-Channel Conv Unit
# ---------------------------------------------------------------------------

def first_layer_conv(x, w, bias: Optional[jax.Array],
                     stride: int, padding: str, act: str,
                     cfg: EngineConfig, out_dtype=jnp.float32,
                     out_scale: Optional[float] = None,
                     epilogue=None,
                     residual: Optional[jax.Array] = None,
                     res_scale: float = 1.0) -> jax.Array:
    """Stage-0 conv. Dispatches to the low-channel unit when enabled,
    otherwise to the general Conv PE (the paper's 13.1%-utilization path).

    x may be a QTensor (the compiled program quantizes the input image with
    the calibrated static scale); out_scale requants the stem output to int8
    so the whole engine pipeline stays int8 from the first layer on.

    `epilogue` fuses an absorbed pool tail (the stem -> max-pool chain)
    into the unit's epilogue; residual adds never fuse into the stem
    (fuse_epilogues does not create them).
    """
    static = isinstance(x, QTensor)
    if not cfg.use_low_channel_unit:
        return conv2d_pe(x, w, bias, stride, padding, act, cfg,
                         out_dtype=out_dtype, out_scale=out_scale,
                         epilogue=epilogue, residual=residual,
                         res_scale=res_scale)
    if epilogue is not None and epilogue.add:
        raise ValueError("the Low-Channel unit fuses pool tails only")
    is_q = isinstance(w, QTensor)
    if static and not is_q:
        x = x.dequant()               # float weights: float math
        static = False
    wq = w.q if is_q else w
    k = wq.shape[0]
    quant = (is_q and cfg.quant in _INT8_ACTS) or static
    if quant:
        if static:
            xin, a_scale = x.q, float(x.scale)   # compile-time constant
        else:
            xq = quantize_act_dynamic(x, per_token=False)
            xin, a_scale = xq.q, xq.scale        # traced scalar (jit-safe)
        w_in = w.q
        w_scale = w.scale.reshape(-1)       # per-output-channel [OC]
    else:
        xin = x                     # static was cleared by the fallback above
        w_in = w.dequant(xin.dtype) if is_q else w
        a_scale = w_scale = None
    if padding == "SAME":
        ph = _same_pad(xin.shape[1], k, stride)
        pw = _same_pad(xin.shape[2], k, stride)
        xin = jnp.pad(xin, ((0, 0), ph, pw, (0, 0)))
    if epilogue is not None:
        ep = epilogue
        if cfg.backend == "pallas":
            out = low_channel.low_channel_conv(
                xin, w_in, bias, stride, act, a_scale=a_scale,
                w_scale=w_scale,
                out_scale=out_scale if static else None, out_dtype=out_dtype,
                mid_scale=ep.mid_scale if static and ep.mid_scale else None,
                pool=ep.pool, pool_kernel=ep.pool_kernel,
                pool_stride=ep.pool_stride, interpret=interpret_pallas())
        else:
            y = ref.low_channel_conv(xin, w_in, bias, stride, act,
                                     a_scale=a_scale, w_scale=w_scale,
                                     out_dtype=jnp.float32)
            out = _epilogue.fused_chain(y, **_chain_kwargs(ep, static,
                                                           out_scale))
        if ep.pool == "global" and out.ndim == 4:
            out = out.reshape(out.shape[0], out.shape[-1])
        return out
    if cfg.backend == "pallas":
        return low_channel.low_channel_conv(
            xin, w_in, bias, stride, act, a_scale=a_scale, w_scale=w_scale,
            out_scale=out_scale, out_dtype=out_dtype,
            interpret=interpret_pallas())
    return ref.low_channel_conv(xin, w_in, bias, stride, act,
                                a_scale=a_scale, w_scale=w_scale,
                                out_scale=out_scale, out_dtype=out_dtype)


# ---------------------------------------------------------------------------
# MISC core
# ---------------------------------------------------------------------------

def misc_add(a: jax.Array, b: jax.Array, act: str, cfg: EngineConfig,
             sa: float = 1.0, sb: float = 1.0,
             out_dtype=jnp.float32,
             out_scale: Optional[float] = None) -> jax.Array:
    """Residual add.  In the compiled int8 program a/b are int8 and sa/sb are
    their static edge scales; out_scale requants the sum in the same pass."""
    if not cfg.misc_on_engine:
        # Baseline: separate ops (paper: PL DSP adders).
        x = jax.lax.optimization_barrier(
            a.astype(jnp.float32) * sa + b.astype(jnp.float32) * sb)
        x = ref.act_fn(act)(x)
        if out_scale is not None:
            return quantize_static(x, jnp.float32(out_scale))
        return x.astype(out_dtype)
    if cfg.backend == "pallas":
        return misc_pe.misc_add(a, b, sa, sb, act, out_scale=out_scale,
                                out_dtype=out_dtype,
                                interpret=interpret_pallas())
    return ref.misc_add(a, b, sa, sb, act, out_scale=out_scale,
                        out_dtype=out_dtype)


def avgpool2d(x: jax.Array, window: int, stride: int, cfg: EngineConfig,
              out_dtype=jnp.float32) -> jax.Array:
    """Standalone average pool on the MISC core; channels pad to the
    128-lane width (as for the DWC engine) and slice back."""
    if not (cfg.misc_on_engine and cfg.backend == "pallas"):
        return ref.avgpool2d(x, window, stride, out_dtype=out_dtype)
    c = x.shape[-1]
    cp = _round_up(c, 128)
    xin = jnp.pad(x, ((0, 0), (0, 0), (0, 0), (0, cp - c))) if cp != c else x
    out = misc_pe.avgpool2d(xin, window, stride, out_dtype=out_dtype,
                            interpret=interpret_pallas())
    return out[..., :c]


# ---------------------------------------------------------------------------
# Flash attention (Pallas prefill kernel) -- beyond-paper
# ---------------------------------------------------------------------------

def flash_mha(q: jax.Array, k: jax.Array, v: jax.Array, *,
              causal: bool = True, softcap: float = 0.0,
              cfg: Optional[EngineConfig] = None) -> jax.Array:
    """q: [B, H, L, D]; k, v: [B, H, S, D] (same head count: the caller
    repeats or groups GQA heads).  Pads L/S to block multiples."""
    from repro.kernels import flash_attn
    cfg = cfg or EngineConfig(backend="pallas")
    b, h, l, d = q.shape
    s = k.shape[2]
    bq = bkv = 128
    lp, sp = _round_up(l, bq), _round_up(s, bkv)
    qf = jnp.pad(q.reshape(b * h, l, d), ((0, 0), (0, lp - l), (0, 0)))
    kf = jnp.pad(k.reshape(b * h, s, d), ((0, 0), (0, sp - s), (0, 0)))
    vf = jnp.pad(v.reshape(b * h, s, d), ((0, 0), (0, sp - s), (0, 0)))
    if cfg.backend == "pallas":
        # padded queries attend to nothing real; slice them off below
        out = flash_attn.flash_attention(
            qf, kf, vf, causal=causal, softcap=softcap,
            scale=d ** -0.5, bq=bq, bkv=bkv, interpret=interpret_pallas())
    else:
        out = ref.attention(qf[:, None].transpose(1, 0, 2, 3), kf[:, None
                            ].transpose(1, 0, 2, 3), vf[:, None].transpose(
                            1, 0, 2, 3), causal=causal,
                            logit_softcap=softcap)[0]
    return out[:, :l].reshape(b, h, l, d)


# ---------------------------------------------------------------------------
# Paged KV cache gather -- beyond-paper (LM serving)
# ---------------------------------------------------------------------------

def paged_gather(pool: jax.Array, tables: jax.Array,
                 cfg: EngineConfig) -> jax.Array:
    """Gather a block-paged KV pool [N, P, ...] into the slot-ordered dense
    view [B, M*P, ...] a dense cache would hold, via block table [B, M].

    Table entries are clipped into [0, N-1] HERE, once, for both backends:
    unallocated pages carry the positive sentinel N (negative sentinels
    would WRAP under JAX gather), and the Pallas index_map cannot take an
    out-of-range block id.  Whatever a clipped sentinel reads sits at
    positions >= the slot's length and is masked to -inf downstream, so the
    two backends stay bitwise identical.
    """
    tables = jnp.clip(tables, 0, pool.shape[0] - 1)
    if cfg.backend == "pallas" and not cfg.baseline:
        from repro.kernels import flash_attn
        return flash_attn.paged_gather(pool, tables,
                                       interpret=interpret_pallas())
    return ref.paged_gather(pool, tables)
