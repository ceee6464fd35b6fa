"""Fused RMSNorm (plain + Mamba-2 gated) Pallas kernels.

Row-tiled: each grid step normalizes a [block_rows, D] tile in VMEM with
fp32 statistics.  The gated variant fuses ``silu(z) * y`` into the same
pass (one HBM read of y and z instead of materializing the product).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

F32 = jnp.float32


def _rmsnorm_kernel(x_ref, s_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(F32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps) * s_ref[...].astype(F32)[None, :]
    o_ref[...] = y.astype(o_ref.dtype)


def _gated_kernel(y_ref, z_ref, s_ref, o_ref, *, eps: float):
    y = y_ref[...].astype(F32)
    z = z_ref[...].astype(F32)
    h = y * (z * jax.nn.sigmoid(z))          # silu
    var = jnp.mean(jnp.square(h), axis=-1, keepdims=True)
    o = h * jax.lax.rsqrt(var + eps) * s_ref[...].astype(F32)[None, :]
    o_ref[...] = o.astype(o_ref.dtype)


def _row_tiles(rows: int, block_rows: int):
    """Rows per tile and the padded row count.

    Mosaic tiles the row axis in sublanes of 8, so a tile is a multiple of 8
    rows; the rows are padded up to whole tiles instead of shrinking the tile
    to a divisor of ``rows`` (300 rows would otherwise give 150-row tiles).
    """
    n_tiles = -(-rows // block_rows)
    tile = -(-rows // n_tiles)
    tile = -(-tile // 8) * 8
    return tile, n_tiles * tile


def _rows_call(kernel, arrays, scale, block_rows, interpret):
    """Apply a row-wise kernel to ``arrays`` [..., D] (all the same shape)."""
    shape = arrays[0].shape
    d = shape[-1]
    rows = math.prod(shape[:-1])
    tile, padded = _row_tiles(rows, block_rows)
    args = [jnp.pad(a.reshape(rows, d), ((0, padded - rows), (0, 0)))
            for a in arrays]
    row_spec = pl.BlockSpec((tile, d), lambda i: (i, 0))
    out = pl.pallas_call(
        kernel,
        grid=(padded // tile,),
        in_specs=[row_spec] * len(args) + [pl.BlockSpec((d,), lambda i: (0,))],
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct((padded, d), arrays[0].dtype),
        interpret=interpret,
    )(*args, scale)
    return out[:rows].reshape(shape)


def rmsnorm(x, scale, *, eps: float = 1e-5, block_rows: int = 256,
            interpret: bool = False):
    """x [..., D]; scale [D]."""
    return _rows_call(functools.partial(_rmsnorm_kernel, eps=eps), (x,),
                      scale, block_rows, interpret)


def gated_rmsnorm(y, z, scale, *, eps: float = 1e-5, block_rows: int = 256,
                  interpret: bool = False):
    """RMSNorm(y * silu(z)); y,z [..., D]; scale [D]."""
    return _rows_call(functools.partial(_gated_kernel, eps=eps), (y, z),
                      scale, block_rows, interpret)
