"""Main-path kernels and the serving decode step compiled for a TPU v5e.

Nothing runs: each program is compiled for a described (not attached) v5e
chip, which finds what interpret mode cannot, such as block shapes Mosaic
refuses.  The kernels are called with ``interpret=False`` because
``kernels.ops`` picks interpret mode from the process's backend, which is the
CPU here.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import flash_attention as fa
from repro.kernels import rmsnorm as rn
from repro.kernels import ssd
from repro.models import model as model_lib

BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(one_chip, shape, dtype=BF16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("q_shape,kv_shape", [
    ((8, 12, 1024, 64), (8, 12, 1024, 64)),     # llsc-100m heads
    ((1, 16, 1024, 128), (1, 8, 1024, 128)),    # GQA, 2 query heads per KV
], ids=["d64", "gqa_d128"])
def test_flash_attention_compiles(one_chip, q_shape, kv_shape):
    q = _spec(one_chip, q_shape)
    kv = _spec(one_chip, kv_shape)
    compiled = _compile(
        lambda q, k, v: fa.flash_attention(q, k, v, interpret=False),
        q, kv, kv)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("shape", [(8, 1024, 768), (300, 768)],
                         ids=["aligned", "rows300"])
def test_rmsnorm_compiles(one_chip, shape):
    compiled = _compile(lambda x, s: rn.rmsnorm(x, s, interpret=False),
                        _spec(one_chip, shape), _spec(one_chip, (768,)))
    assert "tpu_custom_call" in compiled.as_text()


def test_gated_rmsnorm_compiles(one_chip):
    y = _spec(one_chip, (8, 1024, 768))
    compiled = _compile(
        lambda y, z, s: rn.gated_rmsnorm(y, z, s, interpret=False),
        y, y, _spec(one_chip, (768,)))
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.xfail(strict=True, reason=(
    "ssd_intra_chunk does not compile for v5e: Mosaic has no lowering for "
    "cumsum (kernels/ssd.py), nor for the 3-D mask reshape after it; the "
    "kernel has no caller in models/"))
def test_ssd_intra_chunk_compiles(one_chip):
    b, l, h, p, g, n = 1, 128, 8, 64, 1, 128
    compiled = _compile(
        lambda *a: ssd.ssd_intra_chunk(*a, interpret=False),
        _spec(one_chip, (b, l, h, p)), _spec(one_chip, (b, l, h)),
        _spec(one_chip, (h,), jnp.float32), _spec(one_chip, (b, l, g, n)),
        _spec(one_chip, (b, l, g, n)))
    assert "tpu_custom_call" in compiled.as_text()


def test_llsc_100m_decode_step_compiles(one_chip):
    """The serving decode step at full width: 8 slots x 2048 positions."""
    cfg = get_config("llsc-100m")
    B, T = 8, 2048
    on_chip = lambda s: _spec(one_chip, s.shape, s.dtype)  # noqa: E731
    params = jax.tree.map(on_chip, model_lib.init_params_shape(cfg))
    caches = jax.tree.map(on_chip, model_lib.cache_struct(cfg, B, T))
    compiled = _compile(
        lambda p, t, c, l: model_lib.decode_step(p, cfg, t, c, l),
        params, _spec(one_chip, (B, 1), jnp.int32), caches,
        _spec(one_chip, (B,), jnp.int32))
    mem = compiled.memory_analysis()
    # bf16 weights (~0.22 GB) and KV cache (~0.6 GB) fit one 16 GiB chip
    assert 0.5e9 < mem.argument_size_in_bytes < 2e9


def _phi3_medium_windowed_l2():
    """GQA 4:1 with d_head 128, every layer windowed, two layers."""
    return dataclasses.replace(
        get_config("phi3-medium-14b"), n_layers=2,
        layer_pattern=("attn_local",), mlp_pattern=("mlp",), attn_window=2047)


@pytest.mark.parametrize("make_cfg,B,T", [
    (lambda: get_config("llsc-100m"), 8, 2048),
    (_phi3_medium_windowed_l2, 16, 4096),
], ids=["llsc-100m-8x2048", "phi3-medium-windowed-l2-16x4096"])
def test_decode_step_updates_cache_in_place(one_chip, make_cfg, B, T):
    """With the cache donated, as the engine donates it, the decode step
    writes its new rows into the cache's own buffer: the whole cache is
    aliased to the output and the step's scratch stays under one layer's
    K cache, so no copy of a layer or of the stack is made."""
    cfg = make_cfg()
    on_chip = lambda s: _spec(one_chip, s.shape, s.dtype)  # noqa: E731
    params = jax.tree.map(on_chip, model_lib.init_params_shape(cfg))
    caches = jax.tree.map(on_chip, model_lib.cache_struct(cfg, B, T))
    compiled = jax.jit(
        lambda p, t, c, l: model_lib.decode_step(p, cfg, t, c, l),
        donate_argnums=(2,)).lower(
            params, _spec(one_chip, (B, 1), jnp.int32), caches,
            _spec(one_chip, (B,), jnp.int32)).compile()
    mem = compiled.memory_analysis()
    cache_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree.leaves(caches))
    one_layer_k = B * T * cfg.n_kv_heads * cfg.d_head * 2
    assert mem.alias_size_in_bytes == cache_bytes
    assert mem.temp_size_in_bytes < one_layer_k, (
        f"decode temp {mem.temp_size_in_bytes} B >= one layer's K "
        f"{one_layer_k} B: the step copies the cache")
