"""The benchmark's largest programs, compiled for a described TPU v5e (no
chip): the Phi-3-medium one-stage prefill at 4096 tokens and its decode step
at 16 slots x 4096, the Phi-3-mini one-stage decode step at 32 slots x 4096
(the decode cell) and at 64 slots x 2048, and its train step at 8 x 1024.
Each must compile and fit one chip's 16 GiB; ``memory_analysis`` gives the
bytes.

The topology is described inside a module fixture, never at import, so that
every test worker collects the same tests and only the one given this file
loads the TPU compiler.
"""
import functools
import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from chipbench import spec  # noqa: E402

HBM = 16 * 1024 ** 3


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@functools.cache
def _bench():
    return spec.Bench()


def _cfg(name):
    conf = json.loads((HERE / "configs" / f"{name}.json").read_text())
    arch, _ = _bench().architecture(conf)
    return arch.program_config(conf)


def _on(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _bytes(compiled) -> dict:
    m = compiled.memory_analysis()
    out = {k: int(getattr(m, f"{k}_size_in_bytes")) for k in
           ("argument", "output", "temp", "alias")}
    print(f"memory_analysis {out}")
    assert out["argument"] + out["output"] + out["temp"] - out["alias"] < HBM
    return out


def test_phi3_l10_prefill_4096(one_chip):
    from repro.models import model as model_lib

    cfg = _cfg("phi3-medium-4k-l10")
    params = _on(model_lib.init_params_shape(cfg), one_chip)
    tokens = jax.ShapeDtypeStruct((1, 4096), jnp.int32, sharding=one_chip)
    compiled = jax.jit(lambda p, t: model_lib.prefill(p, cfg, t)).lower(
        params, tokens).compile()
    _bytes(compiled)


def _decode(name, slots, T, sharding):
    """The decode step of configuration ``name`` at ``slots`` x ``T``, the
    cache donated."""
    from repro.models import model as model_lib

    cfg = _cfg(name)
    params = _on(model_lib.init_params_shape(cfg), sharding)
    caches = _on(model_lib.cache_struct(cfg, slots, T), sharding)
    tok = jax.ShapeDtypeStruct((slots, 1), jnp.int32, sharding=sharding)
    lens = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=sharding)
    compiled = jax.jit(
        lambda p, t, c, n: model_lib.decode_step(p, cfg, t, c, n),
        donate_argnums=(2,)).lower(params, tok, caches, lens).compile()
    _bytes(compiled)


def test_phi3_l10_decode_16x4096(one_chip):
    _decode("phi3-medium-4k-l10", 16, 4096, one_chip)


def test_phi3_mini_l4_decode_32x4096(one_chip):
    """The decode cell's step."""
    _decode("phi3-mini-4k-l4", 32, 4096, one_chip)


def test_phi3_mini_l4_decode_64x2048(one_chip):
    """The same cache bytes over twice the slots: twice the per-slot row
    writes of each step."""
    _decode("phi3-mini-4k-l4", 64, 2048, one_chip)


def test_phi3_mini_l4_train_step_8x1024(one_chip):
    from repro.train.train_step import (default_opt_cfg,
                                        init_train_state_shape,
                                        make_train_step)

    cfg = _cfg("phi3-mini-4k-l4")
    opt = default_opt_cfg(cfg, 10_000)
    state = _on(init_train_state_shape(cfg, opt), one_chip)
    batch = {k: jax.ShapeDtypeStruct((8, 1024), jnp.int32, sharding=one_chip)
             for k in ("tokens", "labels")}
    compiled = jax.jit(make_train_step(cfg, opt), donate_argnums=(0,)).lower(
        state, batch).compile()
    _bytes(compiled)
