"""Plain float32 reference of a dense grouped-query-attention decoder.

Written from the published description of the Phi-3 blocks (arXiv:2404.14219
and the models' config.json), not from the program: pre-norm blocks;
RMSNorm, ``x * rsqrt(mean(x^2) + eps) * scale``; rotary position embedding
in the rotate-half form on every query and key head; causal grouped-query
attention with ``n_heads / n_kv_heads`` query heads per key/value head, over
a sliding window where the config states one (query i sees keys j with
i - window < j <= i, as the transformers mask for ``sliding_window`` has
it); a SwiGLU MLP, ``(silu(x W1) * (x W3)) W2``; a final RMSNorm; and a
head that is the embedding table (tied) or a matrix of its own.  No kernel, cache or batching: attention takes blocks of query rows
and the layers run one at a time, only so that it fits beside the weights.

Every matmul runs at HIGHEST precision on operands that ``quant`` has rounded
first: the identity for the reference, :func:`fp8` for its control.

It also makes the weights that both the program and the reference are given,
in the program's tree layout, from a key, in one jitted call, and holds the
AdamW update the trainer's configuration states.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 1024
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
    """Random weights in the program's layout: blocks stacked on axis 0."""
    d, H, Hk, dh, ff, V = (dims.d_model, dims.n_heads, dims.n_kv_heads,
                           dims.d_head, dims.d_ff, dims.vocab_size)
    k_embed, k_head, k_layers, k_norm = jax.random.split(key, 4)

    def normal(k, shape, fan_in):
        return (jax.random.normal(k, shape, F32) * fan_in ** -0.5).astype(dtype)

    def scale(k, shape):
        # not all ones, so that a norm that drops its scale is seen
        return (1.0 + 0.1 * jax.random.uniform(k, shape, F32, -1.0, 1.0)
                ).astype(dtype)

    def layer(k):
        ks = jax.random.split(k, 9)
        return {
            "ln1": {"scale": scale(ks[0], (d,))},
            "ln2": {"scale": scale(ks[1], (d,))},
            "mixer": {"wq": normal(ks[2], (d, H * dh), d),
                      "wk": normal(ks[3], (d, Hk * dh), d),
                      "wv": normal(ks[4], (d, Hk * dh), d),
                      "wo": normal(ks[5], (H * dh, d), H * dh)},
            "mlp": {"w1": normal(ks[6], (d, ff), d),
                    "w3": normal(ks[7], (d, ff), d),
                    "w2": normal(ks[8], (ff, d), ff)},
        }

    params = {"embed": normal(k_embed, (V, d), d),
              "blocks": {"0": jax.lax.map(layer, jax.random.split(
                  k_layers, dims.n_layers))},
              "rem": {},
              "final_norm": {"scale": scale(k_norm, (d,))}}
    if not dims.tie_embeddings:
        params["lm_head"] = normal(k_head, (d, V), d)
    return params


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x [B, S, H, D] at positions 0..S-1, rotate-half form."""
    S, D = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, D, 2, dtype=F32) / D)
    ang = jnp.arange(S, dtype=F32)[:, None] * inv
    sin, cos = jnp.sin(ang)[None, :, None, :], jnp.cos(ang)[None, :, None, :]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, quant, window=None):
    """Causal GQA, windowed if ``window``. q [B,S,H,D], k and v [B,S,Hk,D]
    -> [B,S,H*D]."""
    B, S, H, D = q.shape
    Hk = k.shape[2]
    qg = q.reshape(B, S, Hk, H // Hk, D)
    k, v = quant(k), quant(v)
    outs = []
    for start in range(0, S, QUERY_BLOCK):
        qb = qg[:, start:start + QUERY_BLOCK]
        s = jnp.einsum("bqkgd,btkd->bkgqt", quant(qb), k,
                       precision=HIGHEST) * D ** -0.5
        back = (start + jnp.arange(qb.shape[1]))[:, None] \
            - jnp.arange(S)[None, :]
        seen = back >= 0
        if window:
            seen = seen & (back < window)
        s = jnp.where(seen, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        outs.append(jnp.einsum("bkgqt,btkd->bqkgd", quant(p), v,
                               precision=HIGHEST))
    return jnp.concatenate(outs, 1).reshape(B, S, H * D)


def _layer(dims, quant, x, lp):
    B, S, _ = x.shape
    lp = jax.tree.map(lambda a: a.astype(F32), lp)

    def mm(a, w):
        return jnp.matmul(quant(a), quant(w), precision=HIGHEST)

    h = _rms(x, lp["ln1"]["scale"], dims.norm_eps)
    at = lp["mixer"]
    q = mm(h, at["wq"]).reshape(B, S, dims.n_heads, dims.d_head)
    k = mm(h, at["wk"]).reshape(B, S, dims.n_kv_heads, dims.d_head)
    v = mm(h, at["wv"]).reshape(B, S, dims.n_kv_heads, dims.d_head)
    q, k = _rope(q, dims.rope_theta), _rope(k, dims.rope_theta)
    x = x + mm(_attention(q, k, v, quant, dims.window), at["wo"])
    h = _rms(x, lp["ln2"]["scale"], dims.norm_eps)
    m = lp["mlp"]
    return x + mm(jax.nn.silu(mm(h, m["w1"])) * mm(h, m["w3"]), m["w2"])


def logits(params, tokens, dims, quant=identity, remat=False):
    """tokens [B, S] -> logits [B, S, V] in float32."""
    x = params["embed"].astype(F32)[tokens]
    body = lambda x, lp: (_layer(dims, quant, x, lp), None)  # noqa: E731
    if remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, params["blocks"]["0"])
    x = _rms(x, params["final_norm"]["scale"].astype(F32), dims.norm_eps)
    if dims.tie_embeddings:
        head = params["embed"].astype(F32).T
    else:
        head = params["lm_head"].astype(F32)
    return jnp.matmul(quant(x), quant(head), precision=HIGHEST)


# --------------------------------------------------------------------------
# Serving: how far below the reference's best each served token lies
# --------------------------------------------------------------------------


def served_gaps(params, tokens, targets, dims):
    """tokens [b, T]: each prompt with its served tokens; targets [b, T]: the
    token served at each position (-1: none).  Per row, the widest gap by
    which a served token's logit lies below the reference's best."""
    z = logits(params, tokens, dims)
    tgt = jnp.take_along_axis(z, jnp.clip(targets, 0)[..., None], -1)[..., 0]
    gap = jnp.where(targets >= 0, jnp.max(z, -1) - tgt, 0.0)
    return jnp.max(gap, -1)


def control_gaps(params, tokens, targets, dims, quant):
    """The control: at the same positions, the gap of the token that the
    reference computed under ``quant`` puts first."""
    z = logits(params, tokens, dims)
    pick = jnp.argmax(logits(params, tokens, dims, quant), -1)
    got = jnp.take_along_axis(z, pick[..., None], -1)[..., 0]
    gap = jnp.where(targets >= 0, jnp.max(z, -1) - got, 0.0)
    return jnp.max(gap, -1)


# --------------------------------------------------------------------------
# Training: the mean next-token loss, its gradient, and AdamW
# --------------------------------------------------------------------------


def loss_sum(params, tokens, labels, dims, quant=identity):
    """Summed next-token cross-entropy over every position of the rows."""
    z = logits(params, tokens, dims, quant, remat=True)
    gold = jnp.take_along_axis(z, labels[..., None], -1)[..., 0]
    return jnp.sum(jax.nn.logsumexp(z, -1) - gold)


def adamw(params, grads, m, v, step: int, opt: dict):
    """One AdamW step as the trainer's configuration states it: the
    gradient clipped to a global norm, bias-corrected moments, decoupled
    weight decay on every leaf but the norms' scales, and a learning rate
    that warms up linearly and then decays on a cosine to ``min_lr_ratio``.
    ``step`` counts from 1.  Returns (params, m, v, the clipped gradient)."""
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    clip = jnp.minimum(1.0, opt["clip_norm"] / (gnorm + 1e-9))
    warm = min(step / max(opt["warmup_steps"], 1), 1.0)
    prog = min(max((step - opt["warmup_steps"])
                   / max(opt["total_steps"] - opt["warmup_steps"], 1), 0.0), 1.0)
    lr = opt["lr"] * warm * (opt["min_lr_ratio"] + (1 - opt["min_lr_ratio"])
                             * 0.5 * (1 + math.cos(math.pi * prog)))
    b1, b2 = opt["b1"], opt["b2"]
    bc1, bc2 = 1 - b1 ** step, 1 - b2 ** step

    def one(path, p, g, mi, vi):
        g = g * clip
        mi = b1 * mi + (1 - b1) * g
        vi = b2 * vi + (1 - b2) * g * g
        upd = (mi / bc1) / (jnp.sqrt(vi / bc2) + opt["eps"])
        if "scale" not in jax.tree_util.keystr(path):
            upd = upd + opt["weight_decay"] * p
        return p - lr * upd, mi, vi, g

    out = jax.tree_util.tree_map_with_path(one, params, grads, m, v)
    pick = [jax.tree.map(lambda t, i=i: t[i], out,
                         is_leaf=lambda t: isinstance(t, tuple))
            for i in range(4)]
    return tuple(pick)
