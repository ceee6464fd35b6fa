"""Model assembly: periodic layer stacks, scans, prefill/decode, loss.

A model is ``n_periods`` copies of a *period* (``cfg.layer_pattern`` /
``cfg.mlp_pattern``) scanned with ``lax.scan`` (small HLO, fast compiles,
native remat), plus an unrolled remainder of ``n_layers % period`` layers.
Hybrid (jamba 1:7 attn:ssm), local:global (gemma3 5:1) and MoE-every-k
patterns all reduce to this scheme.  Leading dense layers
(``cfg.n_dense_layers``, DeepSeek's first_k_dense_replace) are unrolled
before the scanned stack, under ``params["dense"]``.

Caches: a dict ``{"dense": {str(i): tree}, "blocks": {str(pos):
tree[n_periods, ...]}, "rem": {str(i): tree}}`` — scan-compatible because
every leaf of ``blocks`` carries the period axis in front.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models import attention as attn_mod
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import (embed, init_embed, init_mlp, init_rmsnorm,
                                 mlp, rmsnorm, truncated_normal)
from repro.models.scan_util import scan as _scan
from repro.models.sharding_hints import shard_hint

F32 = jnp.float32


def _dtype(cfg):
    return jnp.dtype(cfg.dtype)


# ==========================================================================
# Init
# ==========================================================================


def init_block(key, cfg, mixer_kind: str, mlp_kind: str, *, cross: bool,
               dtype):
    ks = jax.random.split(key, 4)
    p = {"ln1": init_rmsnorm(cfg.d_model, dtype),
         "ln2": init_rmsnorm(cfg.d_model, dtype)}
    if mixer_kind == "ssm":
        p["mixer"] = ssm_mod.init_mamba2(ks[0], cfg.d_model, cfg.ssm, dtype)
    elif cfg.mla is not None:
        p["mixer"] = attn_mod.init_mla(ks[0], cfg.d_model, cfg.n_heads,
                                       cfg.mla, dtype)
    else:
        p["mixer"] = attn_mod.init_attention(
            ks[0], cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_head,
            cfg.qkv_bias, dtype)
    if cross:
        p["ln_x"] = init_rmsnorm(cfg.d_model, dtype)
        p["xattn"] = attn_mod.init_attention(
            ks[1], cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_head,
            False, dtype)
    if mlp_kind == "moe":
        p["mlp"] = moe_mod.init_moe(ks[2], cfg.d_model, cfg.moe, dtype)
    elif cfg.d_ff > 0:
        p["mlp"] = init_mlp(ks[2], cfg.d_model, cfg.d_ff, cfg.act, dtype)
    else:
        p["mlp"] = {}  # attention-free SSM blocks (mamba2) have no FFN
    return p


def _init_enc_block(key, cfg, dtype):
    enc = cfg.encoder
    ks = jax.random.split(key, 2)
    return {
        "ln1": init_rmsnorm(cfg.d_model, dtype),
        "ln2": init_rmsnorm(cfg.d_model, dtype),
        "mixer": attn_mod.init_attention(
            ks[0], cfg.d_model, enc.n_heads, enc.n_kv_heads, cfg.d_model // enc.n_heads,
            False, dtype),
        "mlp": init_mlp(ks[1], cfg.d_model, enc.d_ff, "gelu", dtype),
    }


def init_params(cfg, key, dtype=None):
    """Full parameter tree.  Works under jax.eval_shape (no allocation)."""
    dtype = dtype or _dtype(cfg)
    keys = jax.random.split(key, 8)
    cross = cfg.is_encdec
    params = {"embed": init_embed(keys[0], cfg.vocab_size, cfg.d_model, dtype)}

    blocks = {}
    for p_idx in range(cfg.period):
        mixer_kind = cfg.layer_pattern[p_idx]
        mlp_kind = cfg.mlp_pattern[p_idx]
        pkeys = jax.random.split(jax.random.fold_in(keys[1], p_idx),
                                 cfg.n_periods)
        blocks[str(p_idx)] = jax.vmap(
            lambda k: init_block(k, cfg, mixer_kind, mlp_kind, cross=cross,
                                 dtype=dtype))(pkeys)
    params["blocks"] = blocks
    if cfg.n_dense_layers:
        params["dense"] = {
            str(i): init_block(jax.random.fold_in(keys[5], i), cfg,
                               cfg.layer_pattern[0], "mlp", cross=cross,
                               dtype=dtype)
            for i in range(cfg.n_dense_layers)}

    rem = {}
    for i in range(cfg.n_remainder):
        mixer_kind = cfg.layer_pattern[i]
        mlp_kind = cfg.mlp_pattern[i]
        rem[str(i)] = init_block(jax.random.fold_in(keys[2], i), cfg,
                                 mixer_kind, mlp_kind, cross=cross,
                                 dtype=dtype)
    params["rem"] = rem
    params["final_norm"] = init_rmsnorm(cfg.d_model, dtype)
    if not cfg.tie_embeddings:
        params["lm_head"] = truncated_normal(
            keys[3], (cfg.d_model, cfg.vocab_size), cfg.d_model ** -0.5, dtype)
    if cfg.is_encdec:
        enc = cfg.encoder
        ekeys = jax.random.split(keys[4], enc.n_layers)
        params["enc_blocks"] = jax.vmap(
            lambda k: _init_enc_block(k, cfg, dtype))(ekeys)
        params["enc_norm"] = init_rmsnorm(cfg.d_model, dtype)
    return params


# ==========================================================================
# Block application
# ==========================================================================


def _apply_mixer_full(bp, x, cfg, kind, positions, *, want_cache, banded):
    h = rmsnorm(bp["ln1"], x, cfg.norm_eps)
    cache = {}
    if kind == "ssm":
        y, (conv_tail, state) = ssm_mod.mamba2_forward(bp["mixer"], h, cfg)
        if want_cache:
            cache = {"conv": conv_tail.astype(_dtype(cfg)),
                     "ssd": state.astype(F32)}
    elif cfg.mla is not None:
        y, (ckv, krope) = attn_mod.mla_attention(bp["mixer"], h, cfg,
                                                 positions=positions)
        if want_cache:
            cache = {"ckv": ckv.astype(_dtype(cfg)),
                     "krope": krope.astype(_dtype(cfg))}
    else:
        local = kind == "attn_local"
        from repro.models.perf_flags import current as _perf
        banded = banded or (_perf().banded_local and local)
        y, (k, v) = attn_mod.gqa_attention(bp["mixer"], h, cfg, local=local,
                                           positions=positions, banded=banded)
        if want_cache:
            cache = {"k": k.astype(_dtype(cfg)), "v": v.astype(_dtype(cfg))}
    return x + y, cache


def _apply_cross_full(bp, x, cfg, enc_out, *, want_cache):
    h = rmsnorm(bp["ln_x"], x, cfg.norm_eps)
    enc = cfg.encoder
    d_head = cfg.d_model // enc.n_heads
    k, v = attn_mod.cross_kv(bp["xattn"], enc_out, enc.n_kv_heads, d_head)
    y = attn_mod.cross_attention(bp["xattn"], h, k, v, cfg)
    cache = {"xk": k, "xv": v} if want_cache else {}
    return x + y, cache


def _apply_mlp(bp, x, cfg, mlp_kind, *, want_aux=False, serving=False):
    """Returns (x, aux, hits): aux = [load_balance, z] router losses; hits
    [B,S,n_held] marks the held experts each token was routed to, where the
    layer runs :func:`moe.moe_share_ffn` (None elsewhere).  Serving, and a
    layer the capacity path cannot run, take that no-drop layer."""
    zero = jnp.zeros((2,), F32)
    if mlp_kind != "moe" and not bp["mlp"]:
        return x, zero, None  # no FFN (mamba2)
    h = rmsnorm(bp["ln2"], x, cfg.norm_eps)
    if mlp_kind == "moe":
        hits = None
        if serving or cfg.moe.holds_share:
            y, hits = moe_mod.moe_share_ffn(bp["mlp"], h, cfg.moe, cfg.act)
        else:
            y = moe_mod.moe_ffn(bp["mlp"], h, cfg.moe, cfg.act)
        aux = zero
        if want_aux:
            lb, z = moe_mod.moe_aux_losses(bp["mlp"], h, cfg.moe)
            aux = jnp.stack([lb, z])
        return x + y, aux, hits
    return x + mlp(bp["mlp"], h, cfg.act), zero, None


@jax.custom_vjp
def _bf16_cotangent(x):
    return x


def _bf16_ct_fwd(x):
    return x, None


def _bf16_ct_bwd(_, g):
    # compress the activation gradient crossing this boundary: the TP/FSDP
    # backward collectives then move bf16 instead of f32 (§Perf lever)
    return (g.astype(jnp.bfloat16).astype(g.dtype),)


_bf16_cotangent.defvjp(_bf16_ct_fwd, _bf16_ct_bwd)


def apply_block_full(bp, x, cfg, mixer_kind, mlp_kind, positions,
                     enc_out=None, *, want_cache=False, banded=False,
                     want_aux=False):
    x, cache = _apply_mixer_full(bp, x, cfg, mixer_kind, positions,
                                 want_cache=want_cache, banded=banded)
    if cfg.is_encdec:
        x, xcache = _apply_cross_full(bp, x, cfg, enc_out,
                                      want_cache=want_cache)
        cache.update(xcache)
    # a forward that keeps its cache is a prefill: serving
    x, aux, _ = _apply_mlp(bp, x, cfg, mlp_kind, want_aux=want_aux,
                           serving=want_cache)
    x = shard_hint(x, "activation")
    from repro.models.perf_flags import current as _perf
    if _perf().bf16_grads:
        x = _bf16_cotangent(x)
    return x, cache, aux


def apply_block_decode(bp, x, cfg, mixer_kind, mlp_kind, cache, lens,
                       layer=None):
    """One block's decode.  ``cache`` holds this block kind's leaves for
    every layer of the stack [L,...] with ``layer`` the index, or for one
    layer without.  K/V leaves get one new row per slot at ``lens`` [B];
    SSM states are replaced whole at ``layer``; cross-attention K/V are
    read only.  Returns (x, cache, hits): hits [B,1,n_held] for an expert
    layer, else None."""
    h = rmsnorm(bp["ln1"], x, cfg.norm_eps)
    new_cache = dict(cache)
    if mixer_kind == "ssm":
        y, conv_state, ssd_state = ssm_mod.mamba2_decode(
            bp["mixer"], h, cfg, attn_mod.cache_layer(cache["conv"], layer),
            attn_mod.cache_layer(cache["ssd"], layer))
        for name, state in (("conv", conv_state), ("ssd", ssd_state)):
            state = state.astype(cache[name].dtype)
            new_cache[name] = state if layer is None else \
                jax.lax.dynamic_update_index_in_dim(cache[name], state,
                                                    layer, 0)
    elif cfg.mla is not None:
        y, new_cache["ckv"], new_cache["krope"] = attn_mod.mla_decode(
            bp["mixer"], h, cfg, cache["ckv"], cache["krope"], lens, layer)
    else:
        y, new_cache["k"], new_cache["v"] = attn_mod.gqa_decode(
            bp["mixer"], h, cfg, cache["k"], cache["v"], lens,
            local=mixer_kind == "attn_local", layer=layer)
    x = x + y
    if cfg.is_encdec:
        hx = rmsnorm(bp["ln_x"], x, cfg.norm_eps)
        y = attn_mod.cross_attention(
            bp["xattn"], hx, attn_mod.cache_layer(cache["xk"], layer),
            attn_mod.cache_layer(cache["xv"], layer), cfg)
        x = x + y
    x, _, hits = _apply_mlp(bp, x, cfg, mlp_kind, serving=True)
    return x, new_cache, hits


# ==========================================================================
# Encoder (enc-dec models)
# ==========================================================================


def encode(params, cfg, enc_embeds):
    """enc_embeds [B, S_enc, d] (stub frontend output) -> encoder hidden."""
    enc = cfg.encoder
    positions = jnp.arange(enc_embeds.shape[1])

    def body(x, bp):
        h = rmsnorm(bp["ln1"], x, cfg.norm_eps)
        d_head = cfg.d_model // enc.n_heads
        q, k, v = attn_mod.gqa_project_qkv(bp["mixer"], h, enc.n_heads,
                                           enc.n_kv_heads, d_head)
        from repro.models.attention import chunked_attention
        o = chunked_attention(q, k, v, causal=False, chunk=cfg.attn_chunk)
        x = x + o.reshape(x.shape[0], x.shape[1], -1) @ bp["mixer"]["wo"]
        h = rmsnorm(bp["ln2"], x, cfg.norm_eps)
        x = x + mlp(bp["mlp"], h, "gelu")
        return x, None

    x, _ = _scan(body, enc_embeds, params["enc_blocks"])
    return rmsnorm(params["enc_norm"], x, cfg.norm_eps)


# ==========================================================================
# Full-sequence forward (train / prefill)
# ==========================================================================


def _remat(fn, cfg):
    from repro.models.perf_flags import current as _perf

    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots" or _perf().remat_dots:
        policy = jax.checkpoint_policies.checkpoint_dots
        return jax.checkpoint(fn, policy=policy)
    return jax.checkpoint(fn)


def input_embeddings(params, cfg, tokens, frontend_embeds=None):
    x = embed(params["embed"], tokens, cfg.embed_scale)
    if cfg.frontend == "patch_stub" and frontend_embeds is not None:
        x = jnp.concatenate([frontend_embeds.astype(x.dtype), x], axis=1)
    return x


def forward_hidden(params, cfg, tokens, frontend_embeds=None, *,
                   want_cache=False, banded=False, want_aux=False):
    """Returns (hidden [B,S,d], caches-or-None) — or, with ``want_aux``,
    (hidden, caches, aux [2]) where aux sums MoE (load-balance, z) losses.

    For encdec models ``frontend_embeds`` is the encoder (stub) input; for
    vlm it is prepended patch embeddings.
    """
    enc_out = None
    if cfg.is_encdec:
        enc_out = encode(params, cfg, frontend_embeds)
        x = embed(params["embed"], tokens, cfg.embed_scale)
    else:
        x = input_embeddings(params, cfg, tokens, frontend_embeds)
    x = shard_hint(x, "activation")
    S = x.shape[1]
    positions = jnp.arange(S)

    aux0 = jnp.zeros((2,), F32)
    dense_caches = {}
    for i in range(cfg.n_dense_layers):
        x, dense_caches[str(i)], a = apply_block_full(
            params["dense"][str(i)], x, cfg, cfg.layer_pattern[0], "mlp",
            positions, enc_out, want_cache=want_cache, banded=banded,
            want_aux=want_aux)
        aux0 = aux0 + a

    def period_fn(carry, pparams):
        x, aux = carry
        caches = {}
        for p_idx in range(cfg.period):
            x, c, a = apply_block_full(
                pparams[str(p_idx)], x, cfg, cfg.layer_pattern[p_idx],
                cfg.mlp_pattern[p_idx], positions, enc_out,
                want_cache=want_cache, banded=banded, want_aux=want_aux)
            caches[str(p_idx)] = c
            aux = aux + a
        return (x, aux), caches

    (x, aux), block_caches = _scan(_remat(period_fn, cfg), (x, aux0),
                                   params["blocks"])

    rem_caches = {}
    for i in range(cfg.n_remainder):
        x, c, a = apply_block_full(
            params["rem"][str(i)], x, cfg, cfg.layer_pattern[i],
            cfg.mlp_pattern[i], positions, enc_out,
            want_cache=want_cache, banded=banded, want_aux=want_aux)
        rem_caches[str(i)] = c
        aux = aux + a

    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    caches = None
    if want_cache:
        caches = {"dense": dense_caches, "blocks": block_caches,
                  "rem": rem_caches}
    if want_aux:
        return x, caches, aux / max(cfg.n_layers, 1)
    return x, caches


# ==========================================================================
# Logits / loss
# ==========================================================================


def _head(params, cfg):
    if cfg.tie_embeddings:
        return params["embed"], True
    return params["lm_head"], False


def logits_last(params, cfg, hidden):
    """Logits for the final position only. hidden [B,S,d] -> [B,V] fp32."""
    h = hidden[:, -1]
    w, tied = _head(params, cfg)
    if tied:
        return jnp.einsum("bd,vd->bv", h, w, preferred_element_type=F32)
    return jnp.einsum("bd,dv->bv", h, w, preferred_element_type=F32)


def chunked_ce_loss(params, cfg, hidden, labels):
    """Mean CE over labels >= 0 without materializing [B,S,V] logits.

    hidden [B,S,d]; labels [B,S] int32 (-1 = ignore).  Computed in sequence
    chunks of cfg.loss_chunk; each chunk is rematerialized in backward.
    """
    B, S, d = hidden.shape
    w, tied = _head(params, cfg)
    C = min(cfg.loss_chunk, S)
    pad = (-S) % C
    if pad:
        hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)), constant_values=-1)
    nch = hidden.shape[1] // C
    h_chunks = jnp.moveaxis(hidden.reshape(B, nch, C, d), 1, 0)
    l_chunks = jnp.moveaxis(labels.reshape(B, nch, C), 1, 0)

    from repro.models.perf_flags import current as _perf

    if _perf().loss_weight_gather:
        # Replicate the head weight's d_model shards before the loss einsum:
        # GSPMD then gathers the (small) weight over the FSDP axis instead of
        # all-reducing [B, C, V]-sized partial logits (§Perf lever).
        w = shard_hint(w, "loss_head_tied" if tied else "loss_head")

    @jax.checkpoint
    def chunk_fn(carry, xs):
        hc, lc = xs
        if tied:
            logits = jnp.einsum("bcd,vd->bcv", hc, w,
                                preferred_element_type=F32)
        else:
            logits = jnp.einsum("bcd,dv->bcv", hc, w,
                                preferred_element_type=F32)
        logits = shard_hint(logits, "logits")
        lse = jax.nn.logsumexp(logits, axis=-1)
        # gold logit via masked reduction (shards cleanly over a split vocab,
        # unlike take_along_axis)
        vocab_idx = jnp.arange(logits.shape[-1])
        sel = vocab_idx[None, None, :] == jnp.clip(lc, 0)[..., None]
        gold = jnp.sum(jnp.where(sel, logits, 0.0), axis=-1)
        valid = lc >= 0
        loss_sum, count = carry
        loss_sum = loss_sum + jnp.sum(jnp.where(valid, lse - gold, 0.0))
        count = count + jnp.sum(valid)
        return (loss_sum, count), None

    (loss_sum, count), _ = _scan(
        chunk_fn, (jnp.zeros((), F32), jnp.zeros((), jnp.int32)),
        (h_chunks, l_chunks))
    return loss_sum / jnp.maximum(count, 1)


def lm_loss(params, cfg, tokens, labels, frontend_embeds=None, *,
            banded=False, aux_weights=None):
    """CE loss (+ optional MoE auxiliary losses).

    ``aux_weights=(lb_w, z_w)``: adds lb_w * load_balance + z_w * z_loss
    (per-MoE-layer means).  Ignored for non-MoE configs.
    """
    want_aux = aux_weights is not None and cfg.moe is not None
    if want_aux:
        hidden, _, aux = forward_hidden(params, cfg, tokens, frontend_embeds,
                                        banded=banded, want_aux=True)
    else:
        hidden, _ = forward_hidden(params, cfg, tokens, frontend_embeds,
                                   banded=banded)
    if cfg.frontend == "patch_stub" and frontend_embeds is not None:
        P = frontend_embeds.shape[1]
        pad_labels = jnp.full(
            (labels.shape[0], P), -1, labels.dtype)
        labels = jnp.concatenate([pad_labels, labels], axis=1)
    loss = chunked_ce_loss(params, cfg, hidden, labels)
    if want_aux:
        loss = loss + aux_weights[0] * aux[0] + aux_weights[1] * aux[1]
    return loss


# ==========================================================================
# Prefill / decode (serving)
# ==========================================================================


def prefill(params, cfg, tokens, frontend_embeds=None):
    """Returns (last-token logits [B,V], caches)."""
    hidden, caches = forward_hidden(params, cfg, tokens, frontend_embeds,
                                    want_cache=True)
    return logits_last(params, cfg, hidden), caches


def decode_step(params, cfg, token, caches, cache_len):
    """One decode step.  token [B,1] int32; cache_len: each row's current
    length, [B] or a scalar for all rows.

    The block caches ride in the layer loop's carry and each layer writes
    only its new row per slot, so a donated cache is updated in place.
    Returns (logits [B,V] fp32, new caches, routed): for a model with
    expert layers routed [B, n_held] counts each slot's token routed to
    each held expert, summed over the layers; None for a model without.
    """
    x = embed(params["embed"], token, cfg.embed_scale)
    B = token.shape[0]
    lens = jnp.broadcast_to(jnp.asarray(cache_len, jnp.int32), (B,))

    def tally(routed, hits):
        return routed if hits is None else routed + hits.reshape(B, -1)

    routed = (None if cfg.moe is None
              else jnp.zeros((B, cfg.moe.n_held), F32))
    new_dense = {}
    for i in range(cfg.n_dense_layers):
        x, new_dense[str(i)], _ = apply_block_decode(
            params["dense"][str(i)], x, cfg, cfg.layer_pattern[0], "mlp",
            caches["dense"][str(i)], lens)

    def period_fn(carry, xs):
        x, blocks, routed = carry
        layer, pparams = xs
        blocks = dict(blocks)
        for p_idx in range(cfg.period):
            key = str(p_idx)
            x, blocks[key], hits = apply_block_decode(
                pparams[key], x, cfg, cfg.layer_pattern[p_idx],
                cfg.mlp_pattern[p_idx], blocks[key], lens, layer)
            routed = tally(routed, hits)
        return (x, blocks, routed), None

    (x, new_blocks, routed), _ = _scan(
        period_fn, (x, caches["blocks"], routed),
        (jnp.arange(cfg.n_periods), params["blocks"]))

    new_rem = {}
    for i in range(cfg.n_remainder):
        x, new_rem[str(i)], hits = apply_block_decode(
            params["rem"][str(i)], x, cfg, cfg.layer_pattern[i],
            cfg.mlp_pattern[i], caches["rem"][str(i)], lens)
        routed = tally(routed, hits)

    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = logits_last(params, cfg, x)
    new = {"dense": new_dense, "blocks": new_blocks, "rem": new_rem}
    return logits, new, routed


# ==========================================================================
# Cache allocation (for serving and for decode dry-run cells)
# ==========================================================================


def _block_cache_struct(cfg, mixer_kind, B, T):
    dt = _dtype(cfg)
    c = {}
    if mixer_kind == "ssm":
        spec = cfg.ssm
        ch = spec.d_inner(cfg.d_model) + 2 * spec.n_groups * spec.d_state
        H = spec.n_heads(cfg.d_model)
        c["conv"] = jnp.zeros((B, spec.d_conv - 1, ch), dt)
        c["ssd"] = jnp.zeros((B, spec.n_groups, H // spec.n_groups,
                              spec.head_dim, spec.d_state), F32)
    elif cfg.mla is not None:
        c["ckv"] = jnp.zeros((B, T, cfg.mla.kv_lora_rank), dt)
        c["krope"] = jnp.zeros((B, T, cfg.mla.qk_rope_head_dim), dt)
    else:
        c["k"] = jnp.zeros((B, T, cfg.n_kv_heads, cfg.d_head), dt)
        c["v"] = jnp.zeros((B, T, cfg.n_kv_heads, cfg.d_head), dt)
    if cfg.is_encdec:
        enc = cfg.encoder
        d_head = cfg.d_model // enc.n_heads
        c["xk"] = jnp.zeros((B, enc.source_len, enc.n_kv_heads, d_head), dt)
        c["xv"] = jnp.zeros((B, enc.source_len, enc.n_kv_heads, d_head), dt)
    return c


def init_cache(cfg, B: int, T: int):
    """Zero caches with capacity T (use under eval_shape for specs)."""
    blocks = {}
    for p_idx in range(cfg.period):
        kind = cfg.layer_pattern[p_idx]
        one = _block_cache_struct(cfg, kind, B, T)
        blocks[str(p_idx)] = jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (cfg.n_periods,) + a.shape),
            one)
    rem = {str(i): _block_cache_struct(cfg, cfg.layer_pattern[i], B, T)
           for i in range(cfg.n_remainder)}
    dense = {str(i): _block_cache_struct(cfg, cfg.layer_pattern[0], B, T)
             for i in range(cfg.n_dense_layers)}
    return {"dense": dense, "blocks": blocks, "rem": rem}
