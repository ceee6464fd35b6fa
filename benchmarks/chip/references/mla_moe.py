"""Plain float32 reference of a DeepSeek-V2 decoder holding a share of its
routed experts (one chip of an expert-parallel deployment).

Written from the published description (arXiv:2405.04434 and the model's
config.json), not from the program:

- pre-norm blocks; RMSNorm, ``x * rsqrt(mean(x^2) + eps) * scale``;
- multi-head latent attention without query compression, computed naively:
  q = x W_q, [H, nope + rope] a head; [c | k_pe] = x W_kva; c_kv =
  RMSNorm(c); [k_nope | v] = c_kv W_kvb, [H, nope + v] a head; k_pe is
  rotated once and shared by every head; scores (q_nope . k_nope + q_pe .
  k_pe) * qk_head^-0.5 * m^2, with m = 0.1 * mscale_all_dim * ln(factor) +
  1; causal softmax; the values' heads through W_o;
- rope with YaRN (arXiv:2309.00071): pair i's frequency theta^(-2i/d) is
  divided by ``factor`` by ramp(i) = clamp((i - lo) / (hi - lo), 0, 1), lo
  and hi the pairs that turn ``beta_fast`` and ``beta_slow`` times over the
  original context (floored and ceiled); sines and cosines times
  m(mscale) / m(mscale_all_dim);
- the first ``n_dense_layers`` layers with a SwiGLU MLP,
  ``(silu(x W1) * (x W3)) W2``; every later layer an expert layer: a
  softmax over all ``n_experts`` router logits, the greedy top-k of it (not
  renormalised where ``norm_topk_prob`` is false), and y = sum over the
  chosen experts that are held here (0 .. n_held - 1) of weight * E_e(x),
  plus the shared experts' SwiGLU once.  The absent experts' part is left
  out.  The held experts run densely over every token, weighted 0 where the
  token did not pick them.
- a final RMSNorm and an untied head, computed a block of positions at a
  time (the logits of 4096 positions over 102400 ids are 1.68 GB).

Departure from the published code: rope rotates the two halves of the rope
dimensions (rotate-half), where the published code rotates interleaved
pairs; the two agree under a fixed permutation of the rope columns of W_q
and W_kva, which random weights cannot tell apart.

No kernel, cache or batching: attention takes blocks of query rows and the
layers run one at a time, only so that it fits beside the weights.  Every
matmul runs at HIGHEST precision on operands that ``quant`` has rounded
first: the identity for the reference, :func:`fp8` for its control.  There
is no loss or optimizer: no cell trains this architecture.

It also makes the weights that both the program and the reference are
given, in the program's tree layout, from a key, in one jitted call.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 1024
HEAD_BLOCK = 512
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def identity(x):
    return x


def _round_fp8(x):
    """Round to float8 e4m3 with one scale for the whole tensor."""
    s = jnp.max(jnp.abs(x)) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(F8).astype(F32) * s


@jax.custom_vjp
def fp8(x):
    """The control's rounding: fp8 operands, both ways through a matmul."""
    return _round_fp8(x)


fp8.defvjp(lambda x: (_round_fp8(x), None), lambda _, g: (_round_fp8(g),))


def make_params(dims, key, dtype):
    """Random weights in the program's layout: the dense layers under
    ``dense``, the expert layers stacked on axis 0 under ``blocks``."""
    d, H = dims.d_model, dims.n_heads
    qk = dims.qk_nope_head_dim + dims.qk_rope_head_dim
    r, rope = dims.kv_lora_rank, dims.qk_rope_head_dim
    nv = dims.qk_nope_head_dim + dims.v_head_dim
    E, f, fs = dims.n_held, dims.d_ff_expert, dims.d_ff_shared
    k_embed, k_head, k_dense, k_layers, k_norm = jax.random.split(key, 5)

    def normal(k, shape, fan_in, dt=dtype):
        return (jax.random.normal(k, shape, F32) * fan_in ** -0.5).astype(dt)

    def scale(k, shape):
        # not all ones, so that a norm that drops its scale is seen
        return (1.0 + 0.1 * jax.random.uniform(k, shape, F32, -1.0, 1.0)
                ).astype(dtype)

    def swiglu(k, width):
        ks = jax.random.split(k, 3)
        return {"w1": normal(ks[0], (d, width), d),
                "w3": normal(ks[1], (d, width), d),
                "w2": normal(ks[2], (width, d), width)}

    def layer(k, moe: bool):
        ks = jax.random.split(k, 12)
        out = {
            "ln1": {"scale": scale(ks[0], (d,))},
            "ln2": {"scale": scale(ks[1], (d,))},
            "mixer": {"wq": normal(ks[2], (d, H * qk), d),
                      "wkv_a": normal(ks[3], (d, r + rope), d),
                      "kv_norm": scale(ks[4], (r,)),
                      "wkv_b": normal(ks[5], (r, H * nv), r),
                      "wo": normal(ks[6], (H * dims.v_head_dim, d),
                                   H * dims.v_head_dim)},
        }
        if not moe:
            out["mlp"] = swiglu(ks[7], dims.d_ff)
            return out
        out["mlp"] = {"router": normal(ks[8], (d, dims.n_experts), d, F32),
                      "w1": normal(ks[9], (E, d, f), d),
                      "w3": normal(ks[10], (E, d, f), d),
                      "w2": normal(ks[11], (E, f, d), f),
                      "shared": swiglu(ks[7], fs)}
        return out

    n_moe = dims.n_layers - dims.n_dense_layers
    params = {"embed": normal(k_embed, (dims.vocab_size, d), d),
              "dense": {str(i): layer(jax.random.fold_in(k_dense, i), False)
                        for i in range(dims.n_dense_layers)},
              "blocks": {"0": jax.lax.map(lambda k: layer(k, True),
                                          jax.random.split(k_layers, n_moe))},
              "rem": {},
              "final_norm": {"scale": scale(k_norm, (d,))},
              "lm_head": normal(k_head, (d, dims.vocab_size), d)}
    return params


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _yarn_m(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_frequencies(dims):
    """(the rope pairs' frequencies, the sines' and cosines' factor, the
    scores' factor m^2)."""
    D, theta = dims.qk_rope_head_dim, dims.rope_theta
    base = theta ** (-jnp.arange(0, D, 2, dtype=F32) / D)

    def turns_to_pair(turns):
        return (D * math.log(dims.yarn_original_max / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    lo = max(math.floor(turns_to_pair(dims.yarn_beta_fast)), 0)
    hi = min(math.ceil(turns_to_pair(dims.yarn_beta_slow)), D - 1)
    if hi == lo:
        hi += 0.001
    ramp = jnp.clip((jnp.arange(D // 2, dtype=F32) - lo) / (hi - lo), 0, 1)
    freq = base * (1 - ramp) + base / dims.yarn_factor * ramp
    sc = (_yarn_m(dims.yarn_factor, dims.yarn_mscale)
          / _yarn_m(dims.yarn_factor, dims.yarn_mscale_all_dim))
    m = _yarn_m(dims.yarn_factor, dims.yarn_mscale_all_dim)
    return freq, sc, m * m


def _rope(x, dims):
    """x [B, S, H, D] at positions 0..S-1, rotate-half form, YaRN."""
    S, D = x.shape[1], x.shape[-1]
    freq, sc, _ = yarn_frequencies(dims)
    ang = jnp.arange(S, dtype=F32)[:, None] * freq
    sin = (jnp.sin(ang) * sc)[None, :, None, :]
    cos = (jnp.cos(ang) * sc)[None, :, None, :]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, quant, scale):
    """Causal attention. q, k [B,S,H,qk], v [B,S,H,dv] -> [B,S,H*dv]."""
    B, S, H, _ = q.shape
    k, v = quant(k), quant(v)
    outs = []
    for start in range(0, S, QUERY_BLOCK):
        qb = q[:, start:start + QUERY_BLOCK]
        s = jnp.einsum("bqhd,bthd->bhqt", quant(qb), k,
                       precision=HIGHEST) * scale
        back = (start + jnp.arange(qb.shape[1]))[:, None] \
            - jnp.arange(S)[None, :]
        s = jnp.where(back >= 0, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        outs.append(jnp.einsum("bhqt,bthd->bqhd", quant(p), v,
                               precision=HIGHEST))
    return jnp.concatenate(outs, 1).reshape(B, S, -1)


def _mla(dims, quant, mm, h, at):
    B, S, _ = h.shape
    H, nope = dims.n_heads, dims.qk_nope_head_dim
    q = mm(h, at["wq"]).reshape(B, S, H, nope + dims.qk_rope_head_dim)
    q_nope, q_pe = q[..., :nope], q[..., nope:]
    ckr = mm(h, at["wkv_a"])
    c, k_pe = ckr[..., :dims.kv_lora_rank], ckr[..., dims.kv_lora_rank:]
    c = _rms(c, at["kv_norm"], dims.norm_eps)
    kv = mm(c, at["wkv_b"]).reshape(B, S, H, nope + dims.v_head_dim)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    q_pe = _rope(q_pe, dims)
    k_pe = _rope(k_pe[:, :, None, :], dims)
    q = jnp.concatenate([q_nope, q_pe], -1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, k_nope.shape[:-1]
                                                  + k_pe.shape[-1:])], -1)
    scale = (nope + dims.qk_rope_head_dim) ** -0.5 * yarn_frequencies(dims)[2]
    return mm(_attention(q, k, v, quant, scale), at["wo"])


def _swiglu(mm, h, m):
    return mm(jax.nn.silu(mm(h, m["w1"])) * mm(h, m["w3"]), m["w2"])


def _experts(dims, quant, mm, h, m):
    """The held experts' weighted part and the shared experts."""
    probs = jax.nn.softmax(jnp.matmul(h, m["router"], precision=HIGHEST), -1)
    top, idx = jax.lax.top_k(probs, dims.top_k)
    if dims.norm_topk_prob:
        top = top / jnp.sum(top, -1, keepdims=True)
    y = _swiglu(mm, h, m["shared"])
    for e in range(dims.n_held):
        w = jnp.sum(jnp.where(idx == e, top, 0.0), -1, keepdims=True)
        ex = {k: m[k][e] for k in ("w1", "w3", "w2")}
        y = y + w * _swiglu(mm, h, ex)
    return y


def _layer(dims, quant, x, lp, moe: bool):
    lp = jax.tree.map(lambda a: a.astype(F32), lp)

    def mm(a, w):
        return jnp.matmul(quant(a), quant(w), precision=HIGHEST)

    h = _rms(x, lp["ln1"]["scale"], dims.norm_eps)
    x = x + _mla(dims, quant, mm, h, lp["mixer"])
    h = _rms(x, lp["ln2"]["scale"], dims.norm_eps)
    if moe:
        return x + _experts(dims, quant, mm, h, lp["mlp"])
    return x + _swiglu(mm, h, lp["mlp"])


def hidden(params, tokens, dims, quant=identity):
    """tokens [B, S] -> the final normed hidden states [B, S, d], float32."""
    x = params["embed"].astype(F32)[tokens]
    for i in range(dims.n_dense_layers):
        x = _layer(dims, quant, x, params["dense"][str(i)], False)
    x, _ = jax.lax.scan(lambda x, lp: (_layer(dims, quant, x, lp, True), None),
                        x, params["blocks"]["0"])
    return _rms(x, params["final_norm"]["scale"].astype(F32), dims.norm_eps)


def _head_blocks(params, h, fn, quant=identity):
    """fn(logits [B, b, V], block index) over blocks of HEAD_BLOCK
    positions, concatenated along positions."""
    head = params["lm_head"].astype(F32)
    B, S, d = h.shape
    n = -(-S // HEAD_BLOCK)
    hb = jnp.pad(h, ((0, 0), (0, n * HEAD_BLOCK - S), (0, 0)))
    hb = jnp.moveaxis(hb.reshape(B, n, HEAD_BLOCK, d), 1, 0)

    def one(args):
        i, x = args
        z = jnp.matmul(quant(x), quant(head), precision=HIGHEST)
        return fn(z, i)

    out = jax.lax.map(one, (jnp.arange(n), hb))           # [n, B, b, ...]
    out = jnp.moveaxis(out, 0, 1)
    return out.reshape((B, n * HEAD_BLOCK) + out.shape[3:])[:, :S]


# --------------------------------------------------------------------------
# Serving: how far below the reference's best each served token lies
# --------------------------------------------------------------------------


def served_gaps(params, tokens, targets, dims):
    """tokens [b, T]: each prompt with its served tokens; targets [b, T]: the
    token served at each position (-1: none).  Per row, the widest gap by
    which a served token's logit lies below the reference's best."""
    h = hidden(params, tokens, dims)
    tgt = jnp.pad(jnp.clip(targets, 0), ((0, 0), (0, -tokens.shape[1]
                                                  % HEAD_BLOCK)))

    def gap(z, i):
        t = jax.lax.dynamic_slice_in_dim(tgt, i * HEAD_BLOCK, HEAD_BLOCK, 1)
        got = jnp.take_along_axis(z, t[..., None], -1)[..., 0]
        return jnp.max(z, -1) - got

    g = _head_blocks(params, h, gap)
    return jnp.max(jnp.where(targets >= 0, g, 0.0), -1)


def control_gaps(params, tokens, targets, dims, quant):
    """The control: at the same positions, the gap of the token that the
    reference computed under ``quant`` puts first."""
    h = hidden(params, tokens, dims)
    pick = _head_blocks(params, hidden(params, tokens, dims, quant),
                        lambda z, i: jnp.argmax(z, -1), quant)
    pick = jnp.pad(pick, ((0, 0), (0, -tokens.shape[1] % HEAD_BLOCK)))

    def gap(z, i):
        p = jax.lax.dynamic_slice_in_dim(pick, i * HEAD_BLOCK, HEAD_BLOCK, 1)
        return jnp.max(z, -1) - jnp.take_along_axis(z, p[..., None], -1)[..., 0]

    g = _head_blocks(params, h, gap)
    return jnp.max(jnp.where(targets >= 0, g, 0.0), -1)
