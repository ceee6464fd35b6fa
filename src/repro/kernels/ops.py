"""jit'd public wrappers for the Pallas kernels.

On CPU (this container) the kernels execute in interpret mode — the kernel
body runs in Python per grid cell, validating kernel logic against the
ref.py oracles.  On TPU the same calls compile to Mosaic.  The model code
can route through these via ``use_pallas=True`` call sites.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import decode_attention as _da
from repro.kernels import flash_attention as _fa
from repro.kernels import rmsnorm as _rn
from repro.kernels import ssd as _ssd


def _on_cpu() -> bool:
    return jax.default_backend() == "cpu"


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k"))
def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 128,
                    block_k: int = 128):
    """q [B,H,S,D]; k,v [B,Hk,T,D] -> [B,H,S,D].  Forward-only kernel."""
    return _fa.flash_attention(q, k, v, causal=causal, block_q=block_q,
                               block_k=block_k, interpret=_on_cpu())


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention_diff(q, k, v, causal, block_q, block_k):
    """Differentiable flash attention: Pallas kernel forward, exact
    reference-math backward (recompute; a fused backward kernel is the
    natural TPU follow-up)."""
    return flash_attention(q, k, v, causal=causal, block_q=block_q,
                           block_k=block_k)


def _fad_fwd(q, k, v, causal, block_q, block_k):
    out = flash_attention(q, k, v, causal=causal, block_q=block_q,
                          block_k=block_k)
    return out, (q, k, v)


def _fad_bwd(causal, block_q, block_k, res, g):
    from repro.kernels.ref import attention_ref

    q, k, v = res
    _, vjp = jax.vjp(lambda q_, k_, v_: attention_ref(q_, k_, v_,
                                                      causal=causal),
                     q, k, v)
    return vjp(g)


flash_attention_diff.defvjp(_fad_fwd, _fad_bwd)


def flash_attention_bshd(q, k, v, *, causal: bool = True, block_q=128,
                         block_k=128):
    """Layout adapter for model code: q [B,S,H,D]; k,v [B,T,Hk,D]."""
    o = flash_attention_diff(q.swapaxes(1, 2), k.swapaxes(1, 2),
                             v.swapaxes(1, 2), causal, block_q, block_k)
    return o.swapaxes(1, 2)


def decode_block_rows(T: int, n_kv_heads: int, d_head: int, dtype) -> int:
    """Cache rows per KV block of :func:`decode_attention` for this cache."""
    itemsize = jnp.dtype(dtype).itemsize
    return _da.block_rows(T, n_kv_heads, d_head, itemsize,
                          _da.kv_order(n_kv_heads, d_head, itemsize))


def decode_attention(q, cache_k, cache_v, lens, layer=None, *, window=None,
                     softcap=None, scale=None):
    """Ragged decode attention for model code.  q [B,1,H,D]; cache_[kv] the
    layer stack [L,B,T,Hk,D] with ``layer`` its index, or one layer
    [B,T,Hk,D] without; lens [B]: each slot's query position.  Returns
    [B,1,H,D].

    The stack is transposed to the order of axes the TPU keeps it in
    (:func:`kernels.decode_attention.kv_order`), a bitcast there.  The
    kernel runs in interpret mode where the program is compiled for the CPU
    and as Mosaic elsewhere, chosen when the program is lowered."""
    if layer is None:
        cache_k, cache_v, layer = cache_k[None], cache_v[None], 0
    B, _, H, D = q.shape
    Hk = cache_k.shape[3]
    order = _da.kv_order(Hk, D, cache_k.dtype.itemsize)
    perm = (0, 1) + tuple(2 + "thd".index(a) for a in order)
    call = functools.partial(_da.decode_attention, order=order, window=window,
                             softcap=softcap, scale=scale)
    out = jax.lax.platform_dependent(
        q.reshape(B, Hk, H // Hk, D), jnp.transpose(cache_k, perm),
        jnp.transpose(cache_v, perm), lens, layer,
        cpu=functools.partial(call, interpret=True), default=call)
    return out.reshape(B, 1, H, D)


@jax.jit
def rmsnorm(x, scale):
    return _rn.rmsnorm(x, scale, interpret=_on_cpu())


@jax.jit
def gated_rmsnorm(y, z, scale):
    return _rn.gated_rmsnorm(y, z, scale, interpret=_on_cpu())


@jax.jit
def ssd_intra_chunk(x, dt, A, B, C):
    return _ssd.ssd_intra_chunk(x, dt, A, B, C, interpret=_on_cpu())
