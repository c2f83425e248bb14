"""Flash attention (prefill) as a Pallas TPU kernel -- beyond-paper.

The paper's cascade insight (partial results live in on-array scratch and
never round-trip HBM) applied to attention: the running (max, sum, acc)
online-softmax state is VMEM scratch swept along the KV grid axis, exactly
like conv_pe's PsumStack along the IC axis.

Layout: q [BH, L, D], k/v [BH, S, D] (heads flattened into the batch dim by
the ops wrapper).  Grid (BH, L/bq, S/bkv) with the KV axis "arbitrary"
(revolving accumulator).  Causal masking with optional logit softcap;
fully-masked blocks still execute (the masked-rectangle baseline -- the
triangle-skip variant lives in the jnp path where it is differentiable).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu


NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
            *, nk: int, bq: int, bkv: int, scale: float, causal: bool,
            softcap: float, seq_q: int, seq_kv: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)                 # [bq, D]
    k = k_ref[0].astype(jnp.float32)                 # [bkv, D]
    v = v_ref[0].astype(jnp.float32)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    if softcap > 0:
        s = softcap * jnp.tanh(s / softcap)

    qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0) \
        + (seq_kv - seq_q)                           # align ends (prefill)
    kpos = ki * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
    mask = kpos < seq_kv
    if causal:
        mask = mask & (kpos <= qpos)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, scale: Optional[float] = None,
                    softcap: float = 0.0,
                    bq: int = 128, bkv: int = 128,
                    interpret: bool = False) -> jax.Array:
    """q: [BH, L, D]; k, v: [BH, S, D].  L, S padded to block multiples by
    the wrapper (ops.flash_mha)."""
    bh, l, d = q.shape
    s = k.shape[1]
    assert l % bq == 0 and s % bkv == 0, (l, s, bq, bkv)
    scale = scale if scale is not None else d ** -0.5
    nk = s // bkv
    return pl.pallas_call(
        functools.partial(_kernel, nk=nk, bq=bq, bkv=bkv, scale=scale,
                          causal=causal, softcap=softcap, seq_q=l, seq_kv=s),
        grid=(bh, l // bq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bkv, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bkv, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, l, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),        # running max
            pltpu.VMEM((bq, 1), jnp.float32),        # running sum
            pltpu.VMEM((bq, d), jnp.float32),        # accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)


# ---------------------------------------------------------------------------
# Paged KV gather (scalar-prefetch block-table indexed copy)
# ---------------------------------------------------------------------------

def _gather_kernel(tbl_ref, pool_ref, o_ref):
    del tbl_ref  # consumed by the index maps
    o_ref[0] = pool_ref[0]


def paged_gather(pool: jax.Array, tables: jax.Array, *,
                 interpret: bool = False) -> jax.Array:
    """Block-table gather: pool [N, P, ...] + tables [B, M] (entries already
    clipped into [0, N-1] by the ops wrapper) -> dense view [B, M*P, ...].

    The table rides scalar prefetch (PrefetchScalarGridSpec) so each grid
    step's input BlockSpec picks pool block `tables[b, m]` directly -- the
    copy itself is a straight VMEM move, one (P, F) tile per page.
    """
    n, p = pool.shape[0], pool.shape[1]
    b, m = tables.shape
    trailing = pool.shape[2:]
    f = 1
    for dim in trailing:
        f *= dim
    pool_f = pool.reshape(n, p, f)
    out = pl.pallas_call(
        _gather_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, m),
            in_specs=[
                pl.BlockSpec((1, p, f), lambda bi, mi, tbl: (tbl[bi, mi],
                                                             0, 0)),
            ],
            out_specs=pl.BlockSpec((1, p, f), lambda bi, mi, tbl: (
                bi * m + mi, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((b * m, p, f), pool.dtype),
        interpret=interpret,
    )(tables, pool_f)
    return out.reshape((b, m * p) + trailing)
