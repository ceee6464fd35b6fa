"""DeepSeek-V2-Lite's mechanisms at a small size: the expert layer that holds
a share of the experts (no token dropped, the shares adding up to the uncut
layer), YaRN's frequencies and attention scale, and the routed-token counter
of ``ServeEngine.run``."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced_config
from repro.configs.base import MoESpec, YaRNSpec
from repro.models import init_params
from repro.models.attention import mla_scale
from repro.models.layers import (mlp, rope_inv_freq, rope_sincos,
                                 yarn_mscale)
from repro.models.moe import (DENSE_TOKENS, init_moe, moe_ffn,
                              moe_ffn_dense_reference, moe_share_ffn)
from repro.serve.engine import EngineConfig, Request, ServeEngine

KEY = jax.random.PRNGKey(0)
# DeepSeek-V2-Lite's routing (softmax over all experts, greedy top-6, not
# renormalised) at a small width, its 64 experts over 8 shares of 8
E, HELD, K, D, F = 64, 8, 6, 32, 16
FULL = MoESpec(n_experts=E, top_k=K, d_ff_expert=F, norm_topk_prob=False)
# float32 throughout; the sums differ only in their order
TOL = dict(rtol=2e-5, atol=2e-6)


def _share(params, first, shared=None):
    """Share ``first // HELD`` of the uncut layer's experts, and its spec.
    A share holds the router's first experts: the router's columns are
    rolled so that experts ``first ..`` come first, which moves no token's
    choice among the experts."""
    spec = dataclasses.replace(FULL, n_local_experts=HELD,
                               d_ff_shared=0 if shared is None else 64)
    p = {"router": jnp.roll(params["router"], -first, axis=1)}
    p.update({k: params[k][first:first + HELD] for k in ("w1", "w3", "w2")})
    if shared is not None:
        p["shared"] = shared
    return spec, p


def _x(B, S, seed=1):
    return jax.random.normal(jax.random.PRNGKey(seed), (B, S, D))


@pytest.mark.parametrize("B,S", [(32, 1), (1, 64), (1, DENSE_TOKENS + 64)])
def test_share_drops_no_token(B, S):
    """Each share equals the dense reference with every other expert's
    output left out, at decode's (32, 1), at a prefill's (1, 64), and at a
    prefill long enough to take the sorted rows.  At (32, 1) the capacity
    path at factor 1.25 drops tokens (16 groups of two, one slot an
    expert), so it would fail this."""
    params = init_moe(KEY, D, FULL)
    x = _x(B, S)
    for first in (0, 24, 56):
        spec, p = _share(params, first)
        keep = (jnp.arange(E) >= first) & (jnp.arange(E) < first + HELD)
        masked = dict(params, w2=params["w2"] * keep[:, None, None])
        want = moe_ffn_dense_reference(masked, x, FULL)
        got, hits = moe_share_ffn(p, x, spec)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)
        assert hits.shape == (B, S, HELD)
        assert float(jnp.max(jnp.sum(hits, -1))) <= K
    if (B, S) == (32, 1):
        capped = moe_ffn(params, x, dataclasses.replace(FULL,
                                                        capacity_factor=1.25))
        uncut = moe_ffn_dense_reference(params, x, FULL)
        assert float(jnp.max(jnp.abs(capped - uncut))) > 1e-3


@pytest.mark.parametrize("B,S", [(4, 16), (2, DENSE_TOKENS // 2 + 32)])
def test_shares_add_up_to_the_uncut_layer(B, S):
    """The eight shares' outputs, the shared experts counted once, sum to
    the whole layer: all 64 experts and the shared experts, with every held
    expert on every token and with the sorted rows."""
    params = init_moe(KEY, D, FULL)
    shared = init_moe(KEY, D, dataclasses.replace(FULL, d_ff_shared=64))[
        "shared"]
    x = _x(B, S, seed=2)
    total = 0.0
    for i in range(E // HELD):
        spec, p = _share(params, i * HELD, shared if i == 0 else None)
        total = total + moe_share_ffn(p, x, spec)[0]
    whole = dataclasses.replace(FULL, d_ff_shared=64)
    want, _ = moe_share_ffn(dict(params, shared=shared), x, whole)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), **TOL)
    plain = moe_ffn_dense_reference(params, x, FULL) + mlp(shared, x, "swiglu")
    np.testing.assert_allclose(np.asarray(want), np.asarray(plain), **TOL)


def test_yarn_frequencies_and_scale():
    """DeepSeek-V2-Lite's rope: theta 1e4 over 64 dims, factor 40 over 4096
    positions, beta 32 and 1: ramp(i) = clamp((i - 10) / 13, 0, 1); the
    scores' scale 192^-0.5 * m^2 with m = 0.1 * 0.707 * ln 40 + 1."""
    cfg = get_config("deepseek-v2-lite")
    ys = cfg.rope_scaling
    base = 1.0 / 1e4 ** (np.arange(0, 64, 2) / 64)
    ramp = np.clip((np.arange(32) - 10) / 13, 0, 1)
    want = base * (1 - ramp) + base / 40 * ramp
    got = rope_inv_freq(64, cfg.rope_theta, ys)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    np.testing.assert_array_equal(got[:11], rope_inv_freq(64, 1e4)[:11])
    np.testing.assert_allclose(got[23:], base[23:] / 40, rtol=1e-6)
    m = yarn_mscale(40, 0.707)
    assert m == pytest.approx(1.26080, abs=1e-5)
    assert m * m == pytest.approx(1.58963, abs=1e-5)
    assert mla_scale(cfg) == pytest.approx(192 ** -0.5 * m * m, rel=1e-12)
    assert mla_scale(cfg) == pytest.approx(0.114721, abs=1e-6)
    # mscale equals mscale_all_dim: the sines and cosines keep their size
    pos = jnp.arange(5)
    sin, cos = rope_sincos(pos, 64, 1e4, ys)
    np.testing.assert_allclose(np.asarray(sin ** 2 + cos ** 2), 1.0,
                               rtol=1e-6)
    # where they differ, both are scaled by m(mscale) / m(mscale_all_dim)
    other = YaRNSpec(factor=40.0, original_max_position_embeddings=4096,
                     mscale=1.0, mscale_all_dim=0.707)
    s2, c2 = rope_sincos(pos, 64, 1e4, other)
    k = yarn_mscale(40, 1.0) / m
    np.testing.assert_allclose(np.asarray(s2 ** 2 + c2 ** 2), k * k,
                               rtol=1e-5)


def test_engine_counts_routed_tokens():
    """``ServeEngine.run`` reports the live tokens routed to each held
    expert over its decode steps: top-k a token a layer over all experts,
    so the held experts' total is at most k a decoded token a layer."""
    base = reduced_config("deepseek-v2-lite")
    cfg = dataclasses.replace(base, moe=dataclasses.replace(
        base.moe, n_local_experts=4))
    params = init_params(cfg, KEY)
    assert params["blocks"]["0"]["mlp"]["w1"].shape[1] == 4
    eng = ServeEngine(cfg, params, EngineConfig(slots=4, max_seq_len=48,
                                                monitor=False))
    rng = np.random.default_rng(0)
    for i in range(6):
        eng.submit(Request(i, rng.integers(0, 512, 8 + i).astype(np.int32),
                           max_new_tokens=5))
    stats = eng.run()
    per = np.asarray(stats["routed_per_expert"])
    decoded = stats["tokens"] - stats["admitted"]
    n_moe = cfg.n_layers - cfg.n_dense_layers
    assert per.shape == (4,) and per.sum() > 0
    assert per.sum() <= decoded * n_moe * cfg.moe.top_k
    assert stats["routed_mean"] == pytest.approx(per.mean())
    assert stats["routed_max_over_mean"] == pytest.approx(
        per.max() / per.mean())
