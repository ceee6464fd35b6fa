"""Expert-parallel MoE with explicit all-to-all (shard_map) — §Perf lever.

Why: under GSPMD, the sort/scatter combine gathers rows from the
expert-sharded capacity buffer ``[E -> model, C, d]``; the partitioner
lowers that cross-shard gather to token-buffer-sized all-reduce /
all-gather pairs per layer (measured: ~17 GB/layer/device for
qwen3-moe train_4k — the dominant collective of the whole step).

Fix (MegaBlocks/DeepSpeed-MoE schedule, TPU-native): shard tokens over the
model axis too, route locally, and move *only the routed token rows* to the
shard that owns their expert with ``lax.all_to_all``, compute the expert
GEMMs locally, and all-to-all the outputs back.  Comm per device per layer
drops to ~2 * T_local * k * d bytes (~134 MB for qwen3) instead of ~17 GB.

Semantics: capacity-dropped tokens (two capacity stages: per-destination
send buffers and per-expert receive buffers) contribute zero, matching the
GSPMD path's capacity semantics.  With ample capacity the result equals
``moe_ffn_dense_reference`` (subprocess-tested on an 8-device host mesh).
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.moe import _route, capacity

F32 = jnp.float32


def _sortable_dispatch(ids, n_buckets: int, cap: int):
    """Bucket row indices by `ids` (invalid = negative -> dropped).

    Returns (bucket, pos, order) so rows can be scattered into
    ``[n_buckets, cap, ...]`` buffers with mode='drop'.
    """
    n = ids.shape[0]
    order = jnp.argsort(ids, stable=True)
    ids_sorted = ids[order]
    valid = ids_sorted >= 0
    safe = jnp.where(valid, ids_sorted, 0)
    counts = jnp.zeros((n_buckets,), jnp.int32).at[safe].add(
        valid.astype(jnp.int32))
    starts = jnp.cumsum(counts) - counts
    # invalid ids sort first; valid entry j's bucket-relative position is its
    # sorted index minus the invalid prefix minus its bucket's start offset
    n_invalid = jnp.sum(~valid).astype(jnp.int32)
    pos = jnp.arange(n, dtype=jnp.int32) - n_invalid - starts[safe]
    pos = jnp.where(valid, pos, cap)  # out of bounds -> dropped
    return ids_sorted, pos, order


def _moe_block(x_blk, router, w1, w3, w2, *, spec, act, tp_size, e_loc,
               axis_name):
    """Per-device block under shard_map.

    x_blk [B_loc, S_loc, d]; router [d, E]; w1/w3 [E_loc, d, f];
    w2 [E_loc, f, d].
    """
    B_loc, S_loc, d = x_blk.shape
    T = B_loc * S_loc
    k = spec.top_k
    E = spec.n_experts
    xf = x_blk.reshape(T, d)
    shard = jax.lax.axis_index(axis_name)

    # ---- local routing ---------------------------------------------------
    logits = xf.astype(F32) @ router.astype(F32)          # [T, E]
    weights, idx = _route(logits, spec)                   # [T, k]
    e_flat = idx.reshape(-1)                              # [T*k]
    t_flat = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
    w_flat = weights.reshape(-1)

    # ---- pack per destination shard --------------------------------------
    cs = max(1, math.ceil(T * k * spec.capacity_factor / tp_size))
    dest = e_flat // e_loc
    dest_sorted, pos, order = _sortable_dispatch(dest, tp_size, cs)
    send_x = jnp.zeros((tp_size, cs, d), x_blk.dtype)
    send_e = jnp.full((tp_size, cs), -1, jnp.int32)
    send_x = send_x.at[dest_sorted, pos].set(xf[t_flat[order]], mode="drop")
    send_e = send_e.at[dest_sorted, pos].set(e_flat[order], mode="drop")

    # ---- all-to-all: rows travel to their expert's shard ------------------
    recv_x = jax.lax.all_to_all(send_x, axis_name, 0, 0, tiled=True)
    recv_e = jax.lax.all_to_all(send_e, axis_name, 0, 0, tiled=True)

    # ---- local dispatch to experts ----------------------------------------
    n_recv = tp_size * cs
    rx = recv_x.reshape(n_recv, d)
    re = recv_e.reshape(n_recv)
    le = jnp.where(re >= 0, re - shard * e_loc, -1)       # local expert id
    c2 = max(1, math.ceil(n_recv / e_loc))
    le_sorted, pos2, order2 = _sortable_dispatch(le, e_loc, c2)
    buf = jnp.zeros((e_loc, c2, d), x_blk.dtype)
    buf = buf.at[le_sorted, pos2].set(rx[order2], mode="drop")

    # ---- expert FFN --------------------------------------------------------
    h1 = jnp.einsum("ecd,edf->ecf", buf, w1)
    if act == "swiglu":
        h = jax.nn.silu(h1) * jnp.einsum("ecd,edf->ecf", buf, w3)
    elif act == "geglu":
        h = jax.nn.gelu(h1) * jnp.einsum("ecd,edf->ecf", buf, w3)
    else:
        h = jax.nn.gelu(h1)
    out_buf = jnp.einsum("ecf,efd->ecd", h, w2)

    # ---- local combine back into recv slot order --------------------------
    keep2 = (pos2 < c2) & (le_sorted >= 0)
    rows2 = out_buf[jnp.clip(le_sorted, 0, e_loc - 1),
                    jnp.clip(pos2, 0, c2 - 1)]
    rows2 = rows2 * keep2[:, None].astype(rows2.dtype)
    back = jnp.zeros((n_recv, d), x_blk.dtype).at[order2].set(rows2)
    back = back.reshape(tp_size, cs, d)

    # ---- all-to-all return trip + weighted combine ------------------------
    ret = jax.lax.all_to_all(back, axis_name, 0, 0, tiled=True)
    keep = pos < cs
    rows = ret[jnp.clip(dest_sorted, 0, tp_size - 1), jnp.clip(pos, 0, cs - 1)]
    scale = jnp.where(keep, w_flat[order], 0.0).astype(rows.dtype)
    rows = rows * scale[:, None]
    y = jnp.zeros((T, d), x_blk.dtype).at[t_flat[order]].add(rows)
    return y.reshape(B_loc, S_loc, d)


def moe_ffn_a2a(params, x, spec, act, mesh, *, fsdp_axes, tp_axis="model"):
    """x [B, S, d] -> [B, S, d] with explicit expert-parallel all-to-all.

    Requires S % tp == 0, E % tp == 0, B % fsdp == 0; the caller falls back
    to the GSPMD path otherwise.
    """
    tp_size = mesh.shape[tp_axis]
    e_loc = spec.n_experts // tp_size
    blk = partial(_moe_block, spec=spec, act=act, tp_size=tp_size,
                  e_loc=e_loc, axis_name=tp_axis)
    fn = jax.shard_map(
        blk, mesh=mesh,
        in_specs=(P(fsdp_axes, tp_axis, None),   # x: tokens over fsdp x tp
                  P(None, None),                 # router (replicated)
                  P(tp_axis, None, None),        # w1 [E->tp, d, f]
                  P(tp_axis, None, None),        # w3
                  P(tp_axis, None, None)),       # w2
        out_specs=P(fsdp_axes, tp_axis, None),
        check_vma=False)
    return fn(x, params["router"].astype(x.dtype), params["w1"],
              params["w3"], params["w2"])


def a2a_applicable(x_shape, spec, mesh, tp_axis="model") -> bool:
    if mesh is None:
        return False
    tp = mesh.shape.get(tp_axis, 1) if hasattr(mesh.shape, "get") else \
        dict(mesh.shape).get(tp_axis, 1)
    if tp <= 1:
        return False
    B, S, _ = x_shape
    return (S % tp == 0 and spec.n_experts % tp == 0
            and spec.n_experts >= tp)
