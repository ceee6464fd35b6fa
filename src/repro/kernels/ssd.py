"""Mamba-2 SSD intra-chunk Pallas kernel.

Computes the diagonal (within-chunk) SSD contribution for one chunk tile:

    y[i] = C_i . ( sum_{j<=i} exp(segsum dtA)_{ij} * B_j * dt_j * x_j )

per (batch, chunk, head-group) grid cell, entirely in VMEM:
the [l, l] decay matrix is formed from a cumulative-sum difference (no HBM
round-trip for segsum), then two MXU matmuls produce the output tile.
Head-grouped B/C (G groups of HG heads) are indexed in the BlockSpec maps,
mirroring the grouped layout the pure-jnp path uses.

The inter-chunk recurrence stays in jnp (tiny, bandwidth-trivial scan);
this kernel covers the FLOP-dominant quadratic term.

It runs in interpret mode only: it does not compile for TPU v5e, since
Mosaic has no lowering for ``cumsum`` (tests/test_tpu_compile.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

F32 = jnp.float32


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, o_ref, *, chunk: int):
    # block refs: x [1,l,1,hg,p], dt [1,l,1,hg], a [1,hg], b/c [1,l,1,n]
    x = x_ref[0, :, 0].astype(F32)             # [l, hg, p]
    dt = dt_ref[0, :, 0].astype(F32)           # [l, hg]
    A = a_ref[0].astype(F32)                   # [hg]
    Bm = b_ref[0, :, 0].astype(F32)            # [l, n]
    Cm = c_ref[0, :, 0].astype(F32)            # [l, n]

    dtA = dt * A[None, :]                      # [l, hg]
    cs = jnp.cumsum(dtA, axis=0)               # [l, hg]
    diff = cs[:, None, :] - cs[None, :, :]     # [i, j, hg]
    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    L = jnp.where((ii >= jj)[:, :, None], jnp.exp(diff), 0.0)  # [i,j,hg]

    cb = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=F32)        # [i, j]
    w = cb[:, :, None] * L                                      # [i,j,hg]
    xdt = x * dt[:, :, None]                                    # [j,hg,p]
    # y[i,h,p] = sum_j w[i,j,h] * xdt[j,h,p]
    y = jnp.einsum("ijh,jhp->ihp", w, xdt)
    o_ref[0, :, 0] = y.astype(o_ref.dtype)


def ssd_intra_chunk(x, dt, A, B, C, *, interpret: bool = False):
    """x [b,l,h,p]; dt [b,l,h]; A [h]; B,C [b,l,g,n] -> y_diag [b,l,h,p].

    One chunk per call (l = chunk length); vectorized over batch and head
    groups via the grid.
    """
    b, l, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    hg = h // g
    xg = x.reshape(b, l, g, hg, p)

    grid = (b, g)
    out = pl.pallas_call(
        functools.partial(_ssd_kernel, chunk=l),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, l, 1, hg, p), lambda i, j: (i, 0, j, 0, 0)),
            pl.BlockSpec((1, l, 1, hg), lambda i, j: (i, 0, j, 0)),
            pl.BlockSpec((1, hg), lambda i, j: (j, 0)),
            pl.BlockSpec((1, l, 1, n), lambda i, j: (i, 0, j, 0)),
            pl.BlockSpec((1, l, 1, n), lambda i, j: (i, 0, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, l, 1, hg, p), lambda i, j: (i, 0, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, l, g, hg, p), x.dtype),
        interpret=interpret,
    )(xg, dt.reshape(b, l, g, hg), A.reshape(g, hg), B, C)
    return out.reshape(b, l, h, p)
