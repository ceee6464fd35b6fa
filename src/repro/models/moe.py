"""Mixture-of-experts FFN with sort/scatter dispatch.

Design notes (TPU adaptation):
  * No ``[T, E, C]`` one-hot dispatch einsum (GShard style) — at 1M tokens,
    128 experts and capacity ~5k that tensor is ~10^13 elements.  Instead we
    argsort token-expert assignments and *scatter* rows into per-expert
    capacity buffers ``[E, C, d]``, then run a grouped einsum over experts.
  * Tokens are processed in groups (leading ``G`` axis) so the dispatch is
    local to a data shard; the ``[G, E, C, d]`` buffer carries a sharding
    hint (G -> data, E -> model) so GSPMD lowers expert parallelism to an
    all-to-all instead of replicating expert weights.
  * Over-capacity tokens are dropped (standard capacity-factor semantics);
    with a large enough factor the output equals the dense reference
    (property-tested in tests/test_moe.py).

Serving, and any layer that holds only a share of the experts or has shared
experts, runs :func:`moe_share_ffn` instead: it drops no token.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.models.layers import init_mlp, mlp, truncated_normal
from repro.models.sharding_hints import shard_hint

F32 = jnp.float32


def init_moe(key, d_model: int, spec, dtype=jnp.float32):
    """The router over all ``n_experts``, the held experts' weights
    [n_held, ...], and the shared experts' MLP where the spec has one."""
    ks = jax.random.split(key, 4)
    E, f = spec.n_held, spec.d_ff_expert
    std_in = d_model ** -0.5
    p = {
        "router": truncated_normal(ks[0], (d_model, spec.n_experts), std_in,
                                   F32),
        "w1": truncated_normal(ks[1], (E, d_model, f), std_in, dtype),
        "w3": truncated_normal(ks[2], (E, d_model, f), std_in, dtype),
        "w2": truncated_normal(ks[3], (E, f, d_model), f ** -0.5, dtype),
    }
    if spec.d_ff_shared:
        p["shared"] = init_mlp(jax.random.fold_in(key, 4), d_model,
                               spec.d_ff_shared, "swiglu", dtype)
    return p


def _route(logits, spec):
    """logits [T, E] fp32 -> (weights [T,k], idx [T,k])."""
    if spec.norm_topk_prob:
        vals, idx = jax.lax.top_k(logits, spec.top_k)
        weights = jax.nn.softmax(vals, axis=-1)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        weights, idx = jax.lax.top_k(probs, spec.top_k)
    return weights, idx


def _dispatch_group(x, idx, weights, E: int, C: int):
    """One token group. x [T,d]; idx/weights [T,k].

    Returns (buf [E,C,d], combine info) where combine info lets the caller
    scatter expert outputs back to tokens.
    """
    T, k = idx.shape
    e_flat = idx.reshape(-1)                       # [T*k]
    t_flat = jnp.repeat(jnp.arange(T), k)          # token id per assignment
    w_flat = weights.reshape(-1)

    order = jnp.argsort(e_flat, stable=True)
    e_sorted = e_flat[order]
    t_sorted = t_flat[order]
    w_sorted = w_flat[order]

    counts = jnp.zeros((E,), jnp.int32).at[e_flat].add(1)
    starts = jnp.cumsum(counts) - counts
    pos = jnp.arange(T * k, dtype=jnp.int32) - starts[e_sorted]

    # Out-of-capacity writes fall outside [0, C) and are dropped by XLA
    # scatter semantics (mode="drop").
    buf = jnp.zeros((E, C) + x.shape[1:], x.dtype)
    buf = buf.at[e_sorted, pos].set(x[t_sorted], mode="drop")
    return buf, (e_sorted, pos, t_sorted, w_sorted)


def _combine_group(out_buf, combine, T: int, C: int):
    e_sorted, pos, t_sorted, w_sorted = combine
    keep = (pos < C).astype(out_buf.dtype)
    rows = out_buf[e_sorted, jnp.clip(pos, 0, C - 1)]  # [T*k, d]
    rows = rows * (keep * w_sorted.astype(out_buf.dtype))[:, None]
    y = jnp.zeros((T,) + out_buf.shape[2:], out_buf.dtype)
    return y.at[t_sorted].add(rows)


def capacity(tokens_per_group: int, spec) -> int:
    return max(1, math.ceil(tokens_per_group * spec.top_k
                            * spec.capacity_factor / spec.n_experts))


def _pick_groups(B: int, S: int) -> int:
    if S > 1:
        return B  # one group per batch row (shards over the data axis)
    # decode: group tokens so the group axis still shards over data
    for g in (16, 8, 4, 2, 1):
        if B % g == 0 and B // g >= 1:
            return min(g, B)
    return 1


def moe_aux_losses(params, x, spec):
    """(load_balance, z) router losses for x [B,S,d] (fp32 scalars)."""
    xf = x.reshape(-1, x.shape[-1]).astype(F32)
    logits = xf @ params["router"].astype(F32)
    _, idx = _route(logits, spec)
    return load_balance_loss(logits, idx, spec), router_z_loss(logits)


def moe_ffn(params, x, spec, act: str = "swiglu", n_groups=None):
    """x [B, S, d] -> [B, S, d]."""
    from repro.models.perf_flags import current as _perf

    if _perf().moe_a2a:
        from repro.models.moe_a2a import a2a_applicable, moe_ffn_a2a
        from repro.models.sharding_hints import current_hints

        state = current_hints()
        mesh = state[0] if state else None
        if mesh is not None and a2a_applicable(x.shape, spec, mesh):
            fsdp = (("pod", "data") if "pod" in mesh.axis_names
                    else ("data",))
            return moe_ffn_a2a(params, x, spec, act, mesh, fsdp_axes=fsdp)

    B, S, d = x.shape
    G = n_groups or _pick_groups(B, S)
    T = (B * S) // G
    E = spec.n_experts
    C = capacity(T, spec)
    xg = x.reshape(G, T, d)

    logits = jnp.einsum("gtd,de->gte", xg.astype(F32),
                        params["router"].astype(F32))
    weights, idx = jax.vmap(lambda l: _route(l, spec))(logits)

    buf, combine = jax.vmap(lambda xs, i, w: _dispatch_group(xs, i, w, E, C))(
        xg, idx, weights)
    buf = shard_hint(buf, "moe_dispatch")          # [G, E, C, d]

    h1 = jnp.einsum("gecd,edf->gecf", buf, params["w1"])
    if act == "swiglu":
        h = jax.nn.silu(h1) * jnp.einsum("gecd,edf->gecf", buf, params["w3"])
    else:
        h = jax.nn.gelu(h1)
    out_buf = jnp.einsum("gecf,efd->gecd", h, params["w2"])
    out_buf = shard_hint(out_buf, "moe_dispatch")

    y = jax.vmap(lambda ob, cmb: _combine_group(ob, cmb, T, C))(out_buf, combine)
    y = shard_hint(y, "moe_out")                   # [G, T, d]
    return y.reshape(B, S, d)


# Up to this many tokens the no-drop layer is bound by reading the held
# experts' weights (a v5e does ~240 flops a byte of HBM), so every held
# expert runs on every token at no cost and each layer's slice of the
# scanned weights stays fused into its dot.  Past it only the routed rows
# are multiplied (``jax.lax.ragged_dot``).
DENSE_TOKENS = 256


def moe_share_ffn(params, x, spec, act: str = "swiglu"):
    """The expert layer as one chip of an expert-parallel deployment holds
    it, dropping no token.  x [B, S, d] -> (y [B, S, d], hits [B, S, n_held]).

    Each token is routed over all ``spec.n_experts``; the experts held here
    (the first ``n_held`` of the router's) give their weighted part, y = sum
    over the token's top-k experts e that are held of weight_e * E_e(x), and
    the shared experts, where the spec has them, are added once.  What the
    absent experts would add is left out.  Up to ``DENSE_TOKENS`` tokens
    every held expert has a row for every token, its weight 0 where the
    token did not pick it; past it the (token, expert) pairs are sorted by
    expert and each held expert multiplies its own rows.  Either way no
    grouping can drop a token.  ``hits`` marks the held experts each token
    was routed to."""
    B, S, d = x.shape
    E = spec.n_held
    with jax.named_scope("llload.moe"):
        xf = x.reshape(-1, d)
        logits = xf.astype(F32) @ params["router"].astype(F32)
        weights, idx = _route(logits, spec)                       # [T, k]
        # an expert held elsewhere has no column: its row is all 0
        picked = jax.nn.one_hot(idx, E, dtype=F32)                # [T,k,E]
        if xf.shape[0] <= DENSE_TOKENS:
            y = _held_dense(params, xf, jnp.einsum("tk,tke->et", weights,
                                                   picked), act)
        else:
            y = _held_ragged(params, xf, weights, idx, E, act)
        if "shared" in params:
            y = y + mlp(params["shared"], xf, "swiglu").astype(F32)
        hits = jnp.sum(picked, axis=1)
    return y.astype(x.dtype).reshape(B, S, d), hits.reshape(B, S, -1)


def _hidden(up, params, act):
    """The held experts' hidden activations; ``up(w)`` multiplies the rows
    by the stacked weights w."""
    h = up(params["w1"])
    return jax.nn.silu(h) * up(params["w3"]) if act == "swiglu" \
        else jax.nn.gelu(h)


def _held_dense(params, xf, gate, act):
    """Every held expert on every token, weighted by gate [E, T]."""
    h = _hidden(lambda w: jnp.einsum("td,edf->etf", xf, w), params, act)
    h = h * gate[..., None].astype(h.dtype)
    return jnp.einsum("etf,efd->td", h, params["w2"],
                      preferred_element_type=F32)


def _held_ragged(params, xf, weights, idx, E, act):
    """The routed (token, held expert) rows only, sorted by expert."""
    k = idx.shape[1]
    e = idx.reshape(-1)                                           # [T*k]
    held = e < E
    # pairs whose expert is held elsewhere sort last, past every group
    key = jnp.where(held, e, E)
    order = jnp.argsort(key, stable=True)
    sizes = jnp.sum(jax.nn.one_hot(key, E, dtype=jnp.int32), axis=0)
    rows = xf[order // k]
    h = _hidden(lambda w: jax.lax.ragged_dot(rows, w, sizes), params, act)
    out = jax.lax.ragged_dot(h, params["w2"], sizes,
                             preferred_element_type=F32)
    w = jnp.where(held, weights.reshape(-1), 0.0)[order]
    out = jnp.where(held[order][:, None], out * w[:, None], 0.0)
    return out[jnp.argsort(order)].reshape(-1, k, out.shape[-1]).sum(axis=1)


def moe_ffn_dense_reference(params, x, spec, act: str = "swiglu"):
    """Oracle: every token through its top-k experts, no capacity drops."""
    B, S, d = x.shape
    xf = x.reshape(-1, d)
    logits = xf.astype(F32) @ params["router"].astype(F32)
    weights, idx = _route(logits, spec)
    # all experts densely: [T, E, d_out]
    h1 = jnp.einsum("td,edf->tef", xf, params["w1"])
    if act == "swiglu":
        h = jax.nn.silu(h1) * jnp.einsum("td,edf->tef", xf, params["w3"])
    else:
        h = jax.nn.gelu(h1)
    all_out = jnp.einsum("tef,efd->ted", h, params["w2"])
    sel = jnp.take_along_axis(all_out, idx[..., None], axis=1)  # [T,k,d]
    y = jnp.sum(sel * weights[..., None].astype(sel.dtype), axis=1)
    return y.reshape(B, S, d)


def load_balance_loss(logits, idx, spec):
    """Switch-style auxiliary load-balancing loss (fraction * probability)."""
    E = spec.n_experts
    probs = jax.nn.softmax(logits.astype(F32), axis=-1)  # [T, E]
    me = jnp.mean(probs, axis=0)
    one_hot = jax.nn.one_hot(idx[..., 0], E)             # top-1 assignment
    ce = jnp.mean(one_hot, axis=0)
    return E * jnp.sum(me * ce)


def router_z_loss(logits):
    """ST-MoE router z-loss: penalizes large router logits (stability)."""
    z = jax.nn.logsumexp(logits.astype(F32), axis=-1)
    return jnp.mean(jnp.square(z))
