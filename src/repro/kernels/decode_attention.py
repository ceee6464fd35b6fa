"""Ragged, window-bounded decode attention as a Pallas TPU kernel.

One query token per slot attends to its own slot's cache: the keys at
positions [lo, hi) with hi = lens[b] + 1 and lo = max(0, hi - window).  The
grid is (slot b, KV block j), and each slot's j is mapped into the blocks
that hold [lo, hi) (:func:`kv_block_range`).  A step past the slot's last
block repeats that block's index, so Pallas fetches nothing for it, and
``pl.when`` skips its compute: the cache rows outside a slot's blocks are
never read.  Softmax is online, with f32 running max, sum and accumulator in
VMEM scratch; the edge blocks are masked as ``attention._attend_block``
masks (causal, window, valid below lens + 1), softcap included.

The cache goes in as the whole layer stack [L, B, ...] with the layer index
prefetched beside ``lens`` and chosen in the index map, so no layer is
sliced (and copied) in front of the call.  A custom call takes its operands
in the default tiled layout, so the stack is given in the order of its three
minor axes that the device keeps (``order``, e.g. "hdt" for [L, B, Hk, D, T])
and the caller transposes to it, which the compiler makes a bitcast.  The
order only picks the dot's dimension numbers: the algorithm is the same.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
NEG_INF = -1e30
BLOCK_ROWS = (512, 256, 128)
# Bytes of VMEM the double-buffered K and V blocks may take: half of the
# 16 MiB that a v5e kernel may scope, the rest left to the scores, the
# accumulator and Mosaic's own scratch.
KV_VMEM_BYTES = 8 * 1024 * 1024


def _padded(n: int, m: int) -> int:
    return -(-n // m) * m


def kv_order(n_kv_heads: int, d_head: int, itemsize: int) -> str:
    """The order of a [..., T, Hk, D] cache's three minor axes in the layout
    a TPU keeps it in, as XLA's default layouts for a v5e have it (the
    tests compile for a described v5e and check it): a head width that
    fills no 128 lanes puts T minor-most ("hdt"); a full one keeps [T, Hk,
    D] where Hk fills the tiles' rows (a multiple of 8, or a power of two
    no smaller than a 32-bit word's share of elements), else moves T in
    front of D ("htd")."""
    if d_head % 128:
        return "hdt"
    if n_kv_heads % 8 == 0 or (n_kv_heads in (1, 2, 4)
                               and n_kv_heads * itemsize >= 4):
        return "thd"
    return "htd"


def block_rows(T: int, n_kv_heads: int, d_head: int, itemsize: int,
               order: str) -> int:
    """Cache rows per KV block, from the shapes alone: the largest of
    ``BLOCK_ROWS`` that divides T and whose double-buffered K and V blocks,
    padded to the (8 * packing, 128) tiles of their two minor axes, fit
    ``KV_VMEM_BYTES``; T itself where none does.  A block whose head axis
    is not its leading one is also relaid out in VMEM for the dot, so it
    counts twice over."""
    sub = 8 * max(1, 4 // itemsize)
    n_buf = 4 if order[0] == "h" else 8
    dims = {"h": n_kv_heads, "d": d_head}
    for blk in BLOCK_ROWS:
        if T % blk:
            continue
        dims["t"] = blk
        a, b, c = (dims[ax] for ax in order)
        if n_buf * a * _padded(b, sub) * _padded(c, 128) * itemsize \
                <= KV_VMEM_BYTES:
            return blk
    return T


def kv_block_range(lens, T: int, blk: int, window, xp=np):
    """First and last KV block (inclusive) that hold the keys a query at
    position ``lens`` attends: [lo, hi) with hi = min(lens + 1, T) and
    lo = max(0, hi - window).  Works on host arrays (``xp=np``) and on
    traced scalars (``xp=jnp``) alike: the kernel's index map and the
    engine's counter both read it."""
    hi = xp.minimum(lens + 1, T)
    lo = 0 * hi if window is None else xp.maximum(hi - window, 0)
    return lo // blk, (hi - 1) // blk


def max_blocks(T: int, blk: int, window) -> int:
    """The most blocks any slot's range can span: the grid's KV axis."""
    n = T // blk
    if window is None:
        return n
    return min(n, (window + blk - 2) // blk + 1)


def _kernel(lens_ref, layer_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
            acc_scr, *, T, blk, n_j, window, softcap, scale, order):
    del layer_ref
    b, j = pl.program_id(0), pl.program_id(1)
    lens = lens_ref[b]
    first, last = kv_block_range(lens, T, blk, window, xp=jnp)
    idx = first + j
    h_ax, t_ax, d_ax = (order.index(a) for a in "htd")

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(idx <= last)
    def _attend():
        q = q_ref[...]                                    # [Hk, G, D]
        s = jax.lax.dot_general(
            q, k_ref[...], (((2,), (d_ax,)), ((0,), (h_ax,))),
            preferred_element_type=F32) * scale           # [Hk, G, blk]
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        pos = idx * blk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        keep = pos <= lens
        if window is not None:
            keep &= lens - pos < window
        s = jnp.where(keep, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=2, keepdims=True)
        v = v_ref[...]
        acc_scr[...] = alpha * acc_scr[...] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((2,), (t_ax,)), ((0,), (h_ax,))),
            preferred_element_type=F32)
        m_scr[...] = m_new

    @pl.when(j == n_j - 1)
    def _flush():
        o_ref[...] = (acc_scr[...] / l_scr[...]).astype(o_ref.dtype)


def decode_attention(q, cache_k, cache_v, lens, layer, *, order: str,
                     window=None, softcap=None, scale=None,
                     interpret: bool = False):
    """q [B, Hk, G, D]; cache_[kv] [L, B, a, b, c], the three minor axes
    ordered as ``order`` names them ("h" heads, "t" positions, "d" head
    width); lens [B] int32, each slot's query position (its keys are
    [0, lens]); layer: the stack's index.  Returns [B, Hk, G, D]."""
    B, Hk, G, D = q.shape
    axes = dict(zip(order, cache_k.shape[2:]))
    T = axes["t"]
    assert (axes["h"], axes["d"]) == (Hk, D), (cache_k.shape, q.shape, order)
    blk = block_rows(T, Hk, D, cache_k.dtype.itemsize, order)
    n_j = max_blocks(T, blk, window)
    scale = D ** -0.5 if scale is None else scale
    t_pos = 2 + order.index("t")

    def kv_map(b, j, lens_ref, layer_ref):
        first, last = kv_block_range(lens_ref[b], T, blk, window, xp=jnp)
        at = [layer_ref[0], b, 0, 0, 0]
        at[t_pos] = jnp.minimum(first + j, last)
        return tuple(at)

    kv_block = [None, None] + [axes[a] for a in order]
    kv_block[t_pos] = blk
    kv_spec = pl.BlockSpec(tuple(kv_block), kv_map)
    q_spec = pl.BlockSpec((None, Hk, G, D), lambda b, j, *_: (b, 0, 0, 0))
    kernel = functools.partial(
        _kernel, T=T, blk=blk, n_j=n_j, window=window, softcap=softcap,
        scale=scale, order=order)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, n_j),
            in_specs=[q_spec, kv_spec, kv_spec],
            out_specs=q_spec,
            scratch_shapes=[pltpu.VMEM((Hk, G, 1), F32),
                            pltpu.VMEM((Hk, G, 1), F32),
                            pltpu.VMEM((Hk, G, D), F32)]),
        out_shape=jax.ShapeDtypeStruct((B, Hk, G, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(lens.astype(jnp.int32), jnp.reshape(layer, (1,)).astype(jnp.int32),
      q, cache_k, cache_v)
