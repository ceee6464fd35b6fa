"""Attention mixers: GQA (global / sliding-window), QKV-bias, MLA.

The core primitive is :func:`chunked_attention` — a ``lax.scan`` over query
chunks so the score tensor never exceeds ``[B, Hkv, G, chunk, Skv]``.  This is
"flash attention at the HLO level": exact softmax per chunk, bounded memory,
and the same loop structure the Pallas kernel (repro.kernels.flash_attention)
implements per-block in VMEM on TPU.

Local (sliding-window) layers have two code paths:
  * masked   — full-length scores with a band mask (baseline; wastes FLOPs)
  * banded   — per-chunk KV slice of width (chunk + window) (optimized; exact
               for window <= attn_window).  Selected by ``banded=True``;
               this is one of the §Perf hillclimb levers.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.ops import decode_attention
from repro.models.layers import (apply_rope_bshd, rmsnorm, truncated_normal,
                                 yarn_mscale)
from repro.models.scan_util import scan as _scan

F32 = jnp.float32
NEG_INF = -1e30


# --------------------------------------------------------------------------
# Parameter init
# --------------------------------------------------------------------------


def init_attention(key, d_model, n_heads, n_kv_heads, d_head, qkv_bias=False,
                   dtype=jnp.float32):
    ks = jax.random.split(key, 4)
    std = d_model ** -0.5
    std_o = (n_heads * d_head) ** -0.5
    p = {
        "wq": truncated_normal(ks[0], (d_model, n_heads * d_head), std, dtype),
        "wk": truncated_normal(ks[1], (d_model, n_kv_heads * d_head), std, dtype),
        "wv": truncated_normal(ks[2], (d_model, n_kv_heads * d_head), std, dtype),
        "wo": truncated_normal(ks[3], (n_heads * d_head, d_model), std_o, dtype),
    }
    if qkv_bias:
        p["bq"] = jnp.zeros((n_heads * d_head,), dtype)
        p["bk"] = jnp.zeros((n_kv_heads * d_head,), dtype)
        p["bv"] = jnp.zeros((n_kv_heads * d_head,), dtype)
    return p


def init_mla(key, d_model, n_heads, spec, dtype=jnp.float32):
    ks = jax.random.split(key, 5)
    std = d_model ** -0.5
    qk = spec.qk_head_dim
    if spec.q_lora_rank is None:
        p = {"wq": truncated_normal(ks[0], (d_model, n_heads * qk), std, dtype)}
    else:
        p = {"wq_a": truncated_normal(ks[0], (d_model, spec.q_lora_rank), std,
                                      dtype),
             "q_norm": jnp.ones((spec.q_lora_rank,), dtype),
             "wq_b": truncated_normal(ks[1], (spec.q_lora_rank, n_heads * qk),
                                      spec.q_lora_rank ** -0.5, dtype)}
    p.update({
        "wkv_a": truncated_normal(
            ks[2], (d_model, spec.kv_lora_rank + spec.qk_rope_head_dim), std, dtype),
        "kv_norm": jnp.ones((spec.kv_lora_rank,), dtype),
        "wkv_b": truncated_normal(
            ks[3], (spec.kv_lora_rank,
                    n_heads * (spec.qk_nope_head_dim + spec.v_head_dim)),
            spec.kv_lora_rank ** -0.5, dtype),
        "wo": truncated_normal(
            ks[4], (n_heads * spec.v_head_dim, d_model),
            (n_heads * spec.v_head_dim) ** -0.5, dtype),
    })
    return p


# --------------------------------------------------------------------------
# Core chunked attention
# --------------------------------------------------------------------------


def _attend_block(qc, k, v, q_pos, kv_pos, *, causal, window, kv_valid_len,
                  softcap, scale):
    """qc [B,C,Hk,G,D]; k,v [B,T,Hk,D]; q_pos [C] or [B,C]; kv_pos [T];
    kv_valid_len scalar or [B].  Returns [B,C,Hk,G,Dv]."""
    scores = jnp.einsum("bchgd,bthd->bhgct", qc, k,
                        preferred_element_type=F32) * scale
    if softcap is not None:
        scores = softcap * jnp.tanh(scores / softcap)
    q_pos = jnp.asarray(q_pos)
    if q_pos.ndim == 1:
        q_pos = q_pos[None]                       # [1, C]
    mask = (kv_pos >= 0)[None, None, :]           # banded path pads kv_pos<0
    mask = jnp.broadcast_to(mask,
                            (q_pos.shape[0], q_pos.shape[1], kv_pos.shape[0]))
    if causal:
        mask &= kv_pos[None, None, :] <= q_pos[:, :, None]
    if window is not None:
        mask &= (q_pos[:, :, None] - kv_pos[None, None, :]) < window
    if kv_valid_len is not None:
        kvl = jnp.asarray(kv_valid_len)
        if kvl.ndim == 0:
            kvl = kvl[None]
        mask &= kv_pos[None, None, :] < kvl[:, None, None]
    scores = jnp.where(mask[:, None, None, :, :], scores, NEG_INF)
    weights = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bhgct,bthd->bchgd", weights, v)


def chunked_attention(q, k, v, *, causal=True, window=None, q_offset=0,
                      kv_valid_len=None, softcap=None, chunk=1024,
                      banded=False, scale=None):
    """q [B,Sq,H,D]; k,v [B,Skv,Hkv,D] -> [B,Sq,H,D].

    ``q_offset``: position of q[0] within the kv sequence (decode: cache_len).
    ``kv_valid_len``: positions >= this are masked (ragged decode caches).
    ``banded``: for windowed layers, slice KV to the band instead of masking.
    ``scale``: of the scores, D^-0.5 unless given.
    """
    B, Sq, H, D = q.shape
    Hk = k.shape[2]
    G = H // Hk
    Dv = v.shape[-1]  # MLA: value head dim != qk head dim
    if scale is None:
        scale = D ** -0.5
    qg = q.reshape(B, Sq, Hk, G, D)
    Skv = k.shape[1]

    q_off = jnp.asarray(q_offset)
    if Sq <= chunk:
        q_pos = (q_off[:, None] + jnp.arange(Sq) if q_off.ndim == 1
                 else q_off + jnp.arange(Sq))
        kv_pos = jnp.arange(Skv)
        out = _attend_block(qg, k, v, q_pos, kv_pos, causal=causal,
                            window=window, kv_valid_len=kv_valid_len,
                            softcap=softcap, scale=scale)
        return out.reshape(B, Sq, H, Dv)

    pad = (-Sq) % chunk
    if pad:
        qg = jnp.pad(qg, ((0, 0), (0, pad), (0, 0), (0, 0), (0, 0)))
    nq = qg.shape[1] // chunk
    q_chunks = jnp.moveaxis(qg.reshape(B, nq, chunk, Hk, G, D), 1, 0)

    use_band = banded and window is not None and not (
        kv_valid_len is not None)
    if use_band:
        # Band width: a q chunk at offset c attends to kv in
        # [c - window + 1, c + chunk); slice width W = chunk + window rounded
        # to a multiple of chunk for static shapes.
        Wb = chunk + ((window + chunk - 1) // chunk) * chunk
        k_pad = jnp.pad(k, ((0, 0), (Wb - chunk, pad), (0, 0), (0, 0)))
        v_pad = jnp.pad(v, ((0, 0), (Wb - chunk, pad), (0, 0), (0, 0)))

        def body(_, inp):
            i, qc = inp
            start = i * chunk  # start of band in padded kv coords
            kc = jax.lax.dynamic_slice_in_dim(k_pad, start, Wb, axis=1)
            vc = jax.lax.dynamic_slice_in_dim(v_pad, start, Wb, axis=1)
            q_pos = q_offset + i * chunk + jnp.arange(chunk)
            # padded kv position of band element j is start + j - (Wb - chunk)
            kv_pos = start + jnp.arange(Wb) - (Wb - chunk)
            out = _attend_block(qc, kc, vc, q_pos, kv_pos, causal=causal,
                                window=window, kv_valid_len=None,
                                softcap=softcap, scale=scale)
            # kv_pos < 0 entries are padding; they are masked by the window
            # term only if window <= Wb-chunk; enforce via explicit mask:
            return None, out
    else:
        kv_pos_full = jnp.arange(Skv)

        def body(_, inp):
            i, qc = inp
            q_pos = q_offset + i * chunk + jnp.arange(chunk)
            out = _attend_block(qc, k, v, q_pos, kv_pos_full, causal=causal,
                                window=window, kv_valid_len=kv_valid_len,
                                softcap=softcap, scale=scale)
            return None, out

    _, outs = _scan(body, None, (jnp.arange(nq), q_chunks))
    out = jnp.moveaxis(outs, 0, 1).reshape(B, nq * chunk, Hk, G, Dv)
    if pad:
        out = out[:, :Sq]
    return out.reshape(B, Sq, H, Dv)


# --------------------------------------------------------------------------
# GQA mixer (train/prefill and decode)
# --------------------------------------------------------------------------


def gqa_project_qkv(params, x, n_heads, n_kv_heads, d_head):
    B, S, _ = x.shape
    q = x @ params["wq"]
    k = x @ params["wk"]
    v = x @ params["wv"]
    if "bq" in params:
        q = q + params["bq"]
        k = k + params["bk"]
        v = v + params["bv"]
    return (q.reshape(B, S, n_heads, d_head),
            k.reshape(B, S, n_kv_heads, d_head),
            v.reshape(B, S, n_kv_heads, d_head))


def _flash_applicable(cfg, local: bool, S: int) -> bool:
    from repro.models.perf_flags import current as _perf

    if not _perf().flash_kernel or local or cfg.attn_logit_softcap:
        return False
    block = min(128, S)
    return S % block == 0


def gqa_attention(params, x, cfg, *, local: bool, positions, chunk=None,
                  banded=False):
    """Full-sequence (train / prefill) GQA attention. x [B,S,D] -> [B,S,D]."""
    q, k, v = gqa_project_qkv(params, x, cfg.n_heads, cfg.n_kv_heads, cfg.d_head)
    theta = cfg.rope_theta_local if (local and cfg.rope_theta_local) \
        else cfg.rope_theta
    q = apply_rope_bshd(q, positions, theta)
    k = apply_rope_bshd(k, positions, theta)
    window = cfg.attn_window if local else None
    B, S, _, _ = q.shape
    if _flash_applicable(cfg, local, S):
        from repro.kernels.ops import flash_attention_bshd

        block = min(128, S)
        out = flash_attention_bshd(q, k, v, causal=True, block_q=block,
                                   block_k=block)
    else:
        out = chunked_attention(
            q, k, v, causal=True, window=window,
            softcap=cfg.attn_logit_softcap, chunk=chunk or cfg.attn_chunk,
            banded=banded)
    return out.reshape(B, S, -1) @ params["wo"], (k, v)


def cache_write(cache, new, lens, layer=None):
    """Write row b of ``new`` [B,1,...] at (layer, b, lens[b]) of ``cache``.

    ``cache`` is a layer stack [L,B,T,...] when ``layer`` is given and one
    layer [B,T,...] when it is None.  Each slot is one
    ``dynamic_update_slice`` of a two-row window (the new row, and its
    neighbour written back unchanged), so a donated cache is updated in
    place.  A scatter, a vmap over rows, or a one-row window lets XLA's TPU
    layout assignment copy and relayout the whole stack (a one-row window
    does so where the cache's layout puts T minor-most, as for d_head 64).
    A row at or past T writes nothing.
    """
    lead = () if layer is None else (layer,)
    T = cache.shape[len(lead) + 1]
    W = min(2, T)
    win = (1,) * len(lead) + (1, W) + new.shape[2:]
    hit_shape = (1,) * len(lead) + (1, W) + (1,) * (new.ndim - 2)
    rest = (0,) * (new.ndim - 2)
    new = new.astype(cache.dtype)
    for b in range(new.shape[0]):
        start = jnp.minimum(lens[b], T - W)
        at = lead + (b, start) + rest
        hit = (jnp.arange(W) == lens[b] - start).reshape(hit_shape)
        row = new[b:b + 1] if layer is None else new[None, b:b + 1]
        window = jnp.where(hit, row, jax.lax.dynamic_slice(cache, at, win))
        cache = jax.lax.dynamic_update_slice(cache, window, at)
    return cache


def cache_layer(cache, layer=None):
    """One layer of a cache stack [L,...] (the cache itself without a
    ``layer``).  Read after the write, so the compiler fuses the slice into
    the attention dot instead of materialising the layer."""
    if layer is None:
        return cache
    return jax.lax.dynamic_index_in_dim(cache, layer, 0, keepdims=False)


def gqa_decode(params, x, cfg, cache_k, cache_v, lens, *, local: bool,
               layer=None):
    """Single-token decode. x [B,1,D]; lens [B]: each row's cache length.

    ``cache_[kv]`` is the layer stack [L,B,T,Hk,D] with ``layer`` its index,
    or one layer [B,T,Hk,D] without; returns (out, cache_k, cache_v) with
    the new K/V rows written at ``lens``.  The query reads only its slot's
    live, in-window rows, through the ragged kernel
    (:func:`repro.kernels.ops.decode_attention`)."""
    q, k, v = gqa_project_qkv(params, x, cfg.n_heads, cfg.n_kv_heads, cfg.d_head)
    theta = cfg.rope_theta_local if (local and cfg.rope_theta_local) \
        else cfg.rope_theta
    pos = lens[:, None]
    q = apply_rope_bshd(q, pos, theta)
    k = apply_rope_bshd(k, pos, theta)
    cache_k = cache_write(cache_k, k, lens, layer)
    cache_v = cache_write(cache_v, v, lens, layer)
    out = decode_attention(
        q, cache_k, cache_v, lens, layer,
        window=cfg.attn_window if local else None,
        softcap=cfg.attn_logit_softcap)
    B = x.shape[0]
    return out.reshape(B, 1, -1) @ params["wo"], cache_k, cache_v


# --------------------------------------------------------------------------
# Cross attention (enc-dec)
# --------------------------------------------------------------------------


def cross_attention(params, x, enc_k, enc_v, cfg):
    """x [B,S,D] attends (non-causal) over precomputed encoder K/V."""
    B, S, _ = x.shape
    q = (x @ params["wq"]).reshape(B, S, cfg.n_heads, cfg.d_head)
    out = chunked_attention(q, enc_k, enc_v, causal=False, chunk=cfg.attn_chunk)
    return out.reshape(B, S, -1) @ params["wo"]


def cross_kv(params, enc_out, n_kv_heads, d_head):
    B, S, _ = enc_out.shape
    k = (enc_out @ params["wk"]).reshape(B, S, n_kv_heads, d_head)
    v = (enc_out @ params["wv"]).reshape(B, S, n_kv_heads, d_head)
    return k, v


# --------------------------------------------------------------------------
# MLA (multi-head latent attention)
# --------------------------------------------------------------------------


def mla_scale(cfg) -> float:
    """The scores' scale: qk_head_dim^-0.5, times m^2 under YaRN, where
    m = yarn_mscale(factor, mscale_all_dim)."""
    scale = cfg.mla.qk_head_dim ** -0.5
    ys = cfg.rope_scaling
    if ys is not None and ys.mscale_all_dim:
        scale *= yarn_mscale(ys.factor, ys.mscale_all_dim) ** 2
    return scale


def _mla_q(params, x, cfg):
    """Queries [B,S,H,qk] split into (nope, rope): directly from x, or
    through the compressed query where the spec has a q_lora_rank."""
    spec = cfg.mla
    B, S, _ = x.shape
    if spec.q_lora_rank is None:
        q = x @ params["wq"]
    else:
        cq = rmsnorm({"scale": params["q_norm"]}, x @ params["wq_a"],
                     cfg.norm_eps)
        q = cq @ params["wq_b"]
    q = q.reshape(B, S, cfg.n_heads, spec.qk_head_dim)
    return jnp.split(q, [spec.qk_nope_head_dim], axis=-1)


def _mla_latent(params, x, cfg, positions):
    """The cache rows of x: the normed latent c_kv [B,S,rank] and the
    rotated rope key [B,S,rope] that every head shares."""
    spec = cfg.mla
    ckv, k_rope = jnp.split(x @ params["wkv_a"], [spec.kv_lora_rank], axis=-1)
    ckv = rmsnorm({"scale": params["kv_norm"]}, ckv, cfg.norm_eps)
    k_rope = apply_rope_bshd(k_rope[:, :, None, :], positions, cfg.rope_theta,
                             cfg.rope_scaling)[:, :, 0, :]
    return ckv, k_rope


def mla_attention(params, x, cfg, *, positions):
    """Naive MLA for train/prefill: per-head K and V materialized from the
    latent.  Returns (out, (ckv, k_rope)) for the cache."""
    spec = cfg.mla
    B, S, _ = x.shape
    H = cfg.n_heads
    with jax.named_scope("llload.mla"):
        q_nope, q_rope = _mla_q(params, x, cfg)
        q_rope = apply_rope_bshd(q_rope, positions, cfg.rope_theta,
                                 cfg.rope_scaling)
        ckv, k_rope = _mla_latent(params, x, cfg, positions)
        kv = (ckv @ params["wkv_b"]).reshape(
            B, S, H, spec.qk_nope_head_dim + spec.v_head_dim)
        k_nope, v = jnp.split(kv, [spec.qk_nope_head_dim], axis=-1)
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                      k_nope.shape[:-1] + k_rope.shape[-1:])],
            axis=-1)
        out = chunked_attention(q, k, v, causal=True, chunk=cfg.attn_chunk,
                                scale=mla_scale(cfg))
        out = out.reshape(B, S, -1) @ params["wo"]
    return out, (ckv, k_rope)


def mla_decode(params, x, cfg, cache_ckv, cache_krope, lens, layer=None):
    """Absorbed MLA decode: attend in the latent space (DeepSeek-V2 trick).

    cache_ckv [(L,)B,T,rank]; cache_krope [(L,)B,T,rope_dim]; lens [B].  As
    :func:`gqa_decode`: the new rows are written at ``lens`` of ``layer``.
    """
    spec = cfg.mla
    B = x.shape[0]
    H = cfg.n_heads
    with jax.named_scope("llload.mla"):
        q_nope, q_rope = _mla_q(params, x, cfg)
        pos = lens[:, None]
        q_rope = apply_rope_bshd(q_rope, pos, cfg.rope_theta, cfg.rope_scaling)
        ckv_new, krope_new = _mla_latent(params, x, cfg, pos)
        cache_ckv = cache_write(cache_ckv, ckv_new, lens, layer)
        cache_krope = cache_write(cache_krope, krope_new, lens, layer)
        ckv = cache_layer(cache_ckv, layer)
        krope = cache_layer(cache_krope, layer)

        # Absorb W_uk into q: wkv_b [rank, H*(nope+v)]
        wkv_b = params["wkv_b"].reshape(
            spec.kv_lora_rank, H, spec.qk_nope_head_dim + spec.v_head_dim)
        w_uk = wkv_b[:, :, : spec.qk_nope_head_dim]   # [rank, H, nope]
        w_uv = wkv_b[:, :, spec.qk_nope_head_dim:]    # [rank, H, v]
        q_lat = jnp.einsum("bqhn,rhn->bqhr", q_nope, w_uk)

        scores = (jnp.einsum("bqhr,btr->bhqt", q_lat, ckv,
                             preferred_element_type=F32)
                  + jnp.einsum("bqhe,bte->bhqt", q_rope, krope,
                               preferred_element_type=F32)) * mla_scale(cfg)
        kv_pos = jnp.arange(ckv.shape[1])
        valid = (kv_pos[None, :] <= lens[:, None])[:, None, None, :]
        scores = jnp.where(valid, scores, NEG_INF)
        weights = jax.nn.softmax(scores, axis=-1).astype(ckv.dtype)
        out_lat = jnp.einsum("bhqt,btr->bqhr", weights, ckv)
        out = jnp.einsum("bqhr,rhv->bqhv", out_lat, w_uv)
        out = out.reshape(B, 1, -1) @ params["wo"]
    return out, cache_ckv, cache_krope
