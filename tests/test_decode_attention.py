"""The ragged decode-attention kernel (interpret mode on the CPU) against the
masked whole-cache read it replaces, ``chunked_attention``, and its block
range against a count of the rows each slot needs."""
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import decode_attention as da
from repro.kernels import ops
from repro.models.attention import cache_write, chunked_attention

T = 4096
WINDOW = 700
# a new slot, the last row of a block and the first of the next, a query
# past the window, the cache's last row
LENS = np.array([0, 511, 512, 2000, T - 1], np.int32)


def _inputs(G, D, Hk=2, L=3, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    B = len(LENS)
    q = jax.random.normal(ks[0], (B, 1, Hk * G, D)).astype(dtype)
    k = jax.random.normal(ks[1], (L, B, T, Hk, D)).astype(dtype)
    v = jax.random.normal(ks[2], (L, B, T, Hk, D)).astype(dtype)
    return q, k, v


def _masked(q, k, v, lens, window, softcap):
    return chunked_attention(q, k, v, causal=True, window=window,
                             q_offset=lens, kv_valid_len=lens + 1,
                             softcap=softcap)


@pytest.mark.parametrize("G,D,window,softcap", list(itertools.product(
    (1, 4), (64, 96, 128), (None, WINDOW), (None, 30.0))))
def test_kernel_matches_masked_read(G, D, window, softcap):
    """Stacked at layer 2 and one layer alone, each against the masked
    read of that layer."""
    q, k, v = _inputs(G, D)
    lens = jnp.asarray(LENS)
    run = jax.jit(lambda q, k, v, n, layer=None: ops.decode_attention(
        q, k, v, n, layer, window=window, softcap=softcap))
    want = _masked(q, k[2], v[2], lens, window, softcap)
    for got in (run(q, k, v, lens, 2), run(q, k[2], v[2], lens)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("Hk,D,dtype", [
    (3, 128, jnp.float32),       # heads that fill no tile: T before D
    (4, 128, jnp.bfloat16),      # [T, Hk, D] kept
    (8, 64, jnp.bfloat16),       # T minor-most
], ids=["htd", "thd", "hdt-bf16"])
def test_kernel_in_each_cache_order(Hk, D, dtype):
    order = da.kv_order(Hk, D, jnp.dtype(dtype).itemsize)
    assert order == {3: "htd", 4: "thd", 8: "hdt"}[Hk]
    q, k, v = _inputs(2, D, Hk=Hk, dtype=dtype)
    lens = jnp.asarray(LENS)
    got = jax.jit(lambda q, k, v, n: ops.decode_attention(
        q, k, v, n, 1, window=WINDOW))(q, k, v, lens)
    want = _masked(q, k[1], v[1], lens, WINDOW, None)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=tol, atol=tol)


def test_kernel_in_a_donated_layer_scan():
    """As the decode step runs it: a jitted scan over the layer stack, the
    cache donated, each layer writing its new rows and then attending."""
    L, G, D = 3, 2, 96
    q, k, v = _inputs(G, D, L=L)
    Hk = k.shape[3]
    ks = jax.random.split(jax.random.PRNGKey(7), 2)
    new_k = jax.random.normal(ks[0], (L, len(LENS), 1, Hk, D))
    new_v = jax.random.normal(ks[1], (L, len(LENS), 1, Hk, D))
    lens = jnp.asarray(LENS)

    def step(attend, k, v, lens):
        def body(carry, xs):
            k, v = carry
            layer, nk, nv = xs
            k = cache_write(k, nk, lens, layer)
            v = cache_write(v, nv, lens, layer)
            return (k, v), attend(q, k, v, lens, layer)
        (k, v), outs = jax.lax.scan(body, (k, v),
                                    (jnp.arange(L), new_k, new_v))
        return outs, k, v

    def masked(q, k, v, lens, layer):
        return _masked(q, k[layer], v[layer], lens, WINDOW, None)

    def ragged(q, k, v, lens, layer):
        return ops.decode_attention(q, k, v, lens, layer, window=WINDOW)

    want, want_k, _ = jax.jit(lambda *a: step(masked, *a))(k, v, lens)
    got, got_k, _ = jax.jit(lambda *a: step(ragged, *a),
                            donate_argnums=(0, 1))(k + 0, v + 0, lens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(np.asarray(got_k), np.asarray(want_k))


@pytest.mark.parametrize("blk,window", [(128, None), (256, 2047),
                                        (512, 700), (128, 1)])
def test_block_range_holds_exactly_the_needed_blocks(blk, window):
    """For every query position: the blocks [first, last] are the ones that
    hold a key of [lo, hi), and they fit the grid's KV axis."""
    lens = np.arange(T)
    first, last = da.kv_block_range(lens, T, blk, window)
    pos = np.arange(T)
    for n in lens[::7]:
        keep = (pos <= n) & ((n - pos < window) if window else True)
        blocks = np.unique(pos[keep] // blk)
        assert (first[n], last[n]) == (blocks[0], blocks[-1])
        assert len(blocks) == last[n] - first[n] + 1
    assert np.max(last - first + 1) == da.max_blocks(T, blk, window)
    traced = jax.jit(lambda n: da.kv_block_range(n, T, blk, window, xp=jnp))
    np.testing.assert_array_equal(np.asarray(traced(lens)[0]), first)
    np.testing.assert_array_equal(np.asarray(traced(lens)[1]), last)


@pytest.mark.parametrize("T_,Hk,D,itemsize,blk", [
    (4096, 32, 96, 2, 256),      # Phi-3-mini: [Hk, D, T]
    (4096, 10, 128, 2, 512),     # Phi-3-medium: [Hk, T, D]
    (2048, 12, 64, 2, 512),      # llsc-100m
    (64, 4, 16, 4, 64),          # a cache shorter than a block: one block
    (640, 2, 64, 4, 128),        # 128 the only size that divides T
], ids=["phi3-mini", "phi3-medium", "llsc-100m", "short", "odd"])
def test_block_rows_from_shapes(T_, Hk, D, itemsize, blk):
    order = da.kv_order(Hk, D, itemsize)
    assert da.block_rows(T_, Hk, D, itemsize, order) == blk
