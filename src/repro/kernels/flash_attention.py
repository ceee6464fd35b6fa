"""Flash attention (causal, GQA-aware) as a Pallas TPU kernel.

TPU adaptation of the FlashAttention-2 schedule: the grid iterates
(batch, q-head, q-block) in parallel and the KV-block axis sequentially
(innermost, 'arbitrary' semantics); running max / sum / accumulator live in
VMEM scratch across KV steps and the output block is flushed once at the
last KV step.  Block shapes are BlockSpec'd so each step touches
``q[Bq,D] + k[Bk,D] + v[Bk,D]`` in VMEM (MXU-aligned: Bq,Bk,D multiples of
128 on real TPU; the interpret-mode tests also sweep smaller shapes).

GQA is handled in the index maps: KV blocks are indexed by ``h // group``
so query-head groups share one KV stream — no KV replication in HBM.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, block_q: int, block_k: int,
                  n_kv_blocks: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(F32)                    # [Bq, D]
    k = k_ref[0, 0].astype(F32)                    # [Bk, D]
    v = v_ref[0, 0].astype(F32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=F32) * scale  # [Bq, Bk]
    if causal:
        q_pos = iq * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        k_pos = ik * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        s = jnp.where(k_pos <= q_pos, s, NEG_INF)

    m_prev = m_scr[...]
    l_prev = l_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    # guard fully-masked rows (exp(NEG_INF - NEG_INF) would be exp(0)=1)
    safe_m = jnp.where(m_new <= NEG_INF, 0.0, m_new)
    p = jnp.exp(jnp.where(s <= NEG_INF, NEG_INF, s - safe_m[:, None]))
    alpha = jnp.where(m_prev <= NEG_INF, 0.0, jnp.exp(m_prev - safe_m))
    l_new = alpha * l_prev + jnp.sum(p, axis=1)
    acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=F32)
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(ik == n_kv_blocks - 1)
    def _flush():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0, :, :] = (acc_scr[...] / denom[:, None]).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, scale=None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False):
    """q [B,H,S,D]; k,v [B,Hk,T,D] -> [B,H,S,D].  H must be G*Hk."""
    B, H, S, D = q.shape
    Hk, T = k.shape[1], k.shape[2]
    G = H // Hk
    scale = scale if scale is not None else D ** -0.5
    block_q = min(block_q, S)
    block_k = min(block_k, T)
    assert S % block_q == 0 and T % block_k == 0, (S, T, block_q, block_k)
    nq = S // block_q
    nk = T // block_k

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, n_kv_blocks=nk)

    grid = (B, H, nq, nk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, i, j: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, i, j: (b, h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D),
                               lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), F32),
            pltpu.VMEM((block_q,), F32),
            pltpu.VMEM((block_q, D), F32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
