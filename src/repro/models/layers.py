"""Shared layer primitives: norms, RoPE, MLPs, embeddings, initializers.

All modules are functional: ``init_*`` returns a pytree of arrays and
``apply``-style functions take ``(params, x, ...)``.  Compute dtype follows
the input; statistics (norm variance, softmax) accumulate in float32.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def truncated_normal(key, shape, std, dtype=jnp.float32):
    return (std * jax.random.truncated_normal(key, -2.0, 2.0, shape)).astype(dtype)


# --------------------------------------------------------------------------
# RMSNorm
# --------------------------------------------------------------------------


def init_rmsnorm(d: int, dtype=jnp.float32):
    return {"scale": jnp.ones((d,), dtype=dtype)}


def rmsnorm(params, x, eps: float = 1e-5):
    dtype = x.dtype
    xf = x.astype(F32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * params["scale"].astype(F32)).astype(dtype)


# --------------------------------------------------------------------------
# RoPE
# --------------------------------------------------------------------------


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's magnitude factor 0.1 * mscale * ln(factor) + 1 (1 where the
    context is not stretched)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rope_inv_freq(dim: int, theta: float, scaling=None) -> np.ndarray:
    """The dim/2 rotation frequencies; with a YaRN ``scaling``, pair i's is
    divided by ``factor`` in proportion to ramp(i), which rises from 0 to 1
    between the pairs that turn ``beta_fast`` and ``beta_slow`` times over
    ``original_max_position_embeddings`` positions."""
    inv_freq = 1.0 / (theta ** (np.arange(0, dim, 2, dtype=np.float32) / dim))
    if scaling is None:
        return inv_freq

    def pair(turns):     # the pair that turns ``turns`` times, fractional
        return dim * math.log(scaling.original_max_position_embeddings
                              / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(pair(scaling.beta_fast)), 0)
    high = min(math.ceil(pair(scaling.beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    return (inv_freq * (1 - ramp) + inv_freq / scaling.factor * ramp
            ).astype(np.float32)


def rope_sincos(positions, dim: int, theta: float, scaling=None):
    """positions [...,] int -> (sin, cos) each [..., dim/2] float32."""
    assert dim % 2 == 0
    inv_freq = rope_inv_freq(dim, theta, scaling)
    angles = positions.astype(F32)[..., None] * inv_freq  # [..., dim/2]
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    if scaling is not None:
        m = (yarn_mscale(scaling.factor, scaling.mscale)
             / yarn_mscale(scaling.factor, scaling.mscale_all_dim))
        if m != 1.0:
            sin, cos = sin * m, cos * m
    return sin, cos


def apply_rope_bshd(x, positions, theta: float, scaling=None):
    """Apply RoPE to x [B, S, H, D] at integer positions [S] or [B, S]."""
    sin, cos = rope_sincos(positions, x.shape[-1], theta,
                           scaling)  # [(B,)S, D/2]
    dtype = x.dtype
    d2 = x.shape[-1] // 2
    x1, x2 = x[..., :d2].astype(F32), x[..., d2:].astype(F32)
    if sin.ndim == 2:        # positions [S]
        sin, cos = sin[None, :, None, :], cos[None, :, None, :]
    else:                    # positions [B, S]
        sin, cos = sin[:, :, None, :], cos[:, :, None, :]
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    return jnp.concatenate([y1, y2], axis=-1).astype(dtype)


# --------------------------------------------------------------------------
# MLP (dense FFN)
# --------------------------------------------------------------------------


def init_mlp(key, d_model: int, d_ff: int, act: str, dtype=jnp.float32):
    k1, k2, k3 = jax.random.split(key, 3)
    std_in = d_model ** -0.5
    std_out = d_ff ** -0.5
    p = {
        "w1": truncated_normal(k1, (d_model, d_ff), std_in, dtype),
        "w2": truncated_normal(k2, (d_ff, d_model), std_out, dtype),
    }
    if act in ("swiglu", "geglu"):
        p["w3"] = truncated_normal(k3, (d_model, d_ff), std_in, dtype)
    return p


def mlp(params, x, act: str):
    h = x @ params["w1"]
    if act == "swiglu":
        h = jax.nn.silu(h) * (x @ params["w3"])
    elif act == "geglu":
        h = jax.nn.gelu(h) * (x @ params["w3"])
    elif act == "gelu":
        h = jax.nn.gelu(h)
    else:
        raise ValueError(act)
    return h @ params["w2"]


# --------------------------------------------------------------------------
# Embedding / logits
# --------------------------------------------------------------------------


def init_embed(key, vocab: int, d_model: int, dtype=jnp.float32):
    # d^-0.5 keeps tied-head logits O(1); gemma-style embed_scale=sqrt(d)
    # restores unit per-dim RMS on the residual stream.
    return truncated_normal(key, (vocab, d_model), d_model ** -0.5, dtype)


def embed(table, tokens, scale: float = 1.0):
    x = jnp.take(table, tokens, axis=0)
    if scale != 1.0:
        x = (x.astype(F32) * scale).astype(x.dtype)
    return x


def logits_from_hidden(x, table_or_head, transpose: bool):
    """x [B,S,D] @ head; transpose=True when using the tied embedding table."""
    w = table_or_head
    if transpose:
        return jnp.einsum("bsd,vd->bsv", x, w)
    return jnp.einsum("bsd,dv->bsv", x, w)
