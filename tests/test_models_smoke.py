"""Per-arch smoke tests (assignment requirement): reduced config of the same
family -> one forward + one train step on CPU, assert shapes + no NaNs;
plus the decode==prefill consistency invariant."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced_config
from repro.configs.archs import ASSIGNED
from repro.models import (decode_step, init_cache, init_params, lm_loss,
                          prefill)
from repro.train.train_step import default_opt_cfg, init_train_state, make_train_step

KEY = jax.random.PRNGKey(0)
K1, K2, K3, K4 = jax.random.split(KEY, 4)


def _inputs(cfg, B=2, S=24):
    tokens = jax.random.randint(K2, (B, S), 0, cfg.vocab_size)
    labels = jax.random.randint(K3, (B, S), 0, cfg.vocab_size)
    fe = None
    if cfg.frontend == "patch_stub":
        fe = jax.random.normal(K4, (B, cfg.frontend_len, cfg.d_model),
                               jnp.float32)
    elif cfg.frontend == "audio_stub":
        fe = jax.random.normal(K4, (B, cfg.encoder.source_len, cfg.d_model),
                               jnp.float32)
    return tokens, labels, fe


@pytest.mark.parametrize("arch", ASSIGNED)
def test_smoke_forward_and_train_step(arch):
    cfg = reduced_config(arch)
    B, S = 2, 24
    tokens, labels, fe = _inputs(cfg, B, S)

    # forward (loss) — finite
    params = init_params(cfg, K1)
    loss = lm_loss(params, cfg, tokens, labels, fe)
    assert loss.shape == ()
    assert np.isfinite(float(loss)), f"{arch}: NaN loss"

    # one full train step — params update, loss finite, no NaN grads
    opt_cfg = default_opt_cfg(cfg, total_steps=10)
    state = init_train_state(cfg, K1, opt_cfg)
    batch = {"tokens": tokens, "labels": labels}
    if fe is not None:
        batch["frontend"] = fe
    step = make_train_step(cfg, opt_cfg)
    new_state, metrics = step(state, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert np.isfinite(float(metrics["grad_norm"]))
    # params actually changed
    delta = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))),
                         state.params, new_state.params)
    assert max(jax.tree.leaves(delta)) > 0


@pytest.mark.parametrize("arch", ASSIGNED)
def test_smoke_prefill_decode_consistency(arch):
    cfg = reduced_config(arch)
    B, S = 2, 24
    tokens, _, fe = _inputs(cfg, B, S)
    params = init_params(cfg, K1)

    logits_full, _ = prefill(params, cfg, tokens, fe)
    assert logits_full.shape == (B, cfg.vocab_size)
    _, caches = prefill(params, cfg, tokens[:, : S - 1], fe)

    T = S + (cfg.frontend_len if cfg.frontend == "patch_stub" else 0)
    cap = init_cache(cfg, B, T)

    def grow(c, full):
        if c.shape == full.shape:
            return c
        pad = [(0, 0)] * c.ndim
        for ax, (a, b) in enumerate(zip(c.shape, full.shape)):
            if a != b:
                pad[ax] = (0, b - a)
        return jnp.pad(c, pad)

    caches = jax.tree.map(grow, caches, cap)
    pos = S - 1 + (cfg.frontend_len if cfg.frontend == "patch_stub" else 0)
    logits_dec, new_caches, _ = decode_step(params, cfg, tokens[:, S - 1:],
                                         caches, jnp.int32(pos))
    err = float(jnp.max(jnp.abs(logits_dec - logits_full)))
    assert err < 2e-4, f"{arch}: decode/prefill mismatch {err}"
    # cache structure preserved
    assert jax.tree.structure(new_caches) == jax.tree.structure(caches)


ROWS = ("k", "v", "ckv", "krope")     # cache leaves with a T axis


def _leaf_name(path):
    keys = [str(getattr(k, "key", k)) for k in path]
    return keys[-1], "blocks" in keys


@pytest.mark.parametrize("arch", ASSIGNED)
def test_ragged_decode_writes_one_row_per_slot(arch):
    """One decode step with a different length in each slot: each row's
    logits match its own prefill, each K/V leaf changes only at
    (layer, b, lens[b]), SSM states become the prefill's states, and the
    cross-attention caches stay as they were."""
    cfg = reduced_config(arch)
    B, S = 3, 24
    n = (5, 11, 17)                              # prompt tokens of each slot
    tokens, _, fe = _inputs(cfg, B, S)
    params = init_params(cfg, K1)
    P = cfg.frontend_len if cfg.frontend == "patch_stub" else 0
    lens = np.array(n) + P                       # cache positions filled

    run_prefill = jax.jit(prefill, static_argnums=1)

    def row(b, m):
        return run_prefill(params, cfg, tokens[b:b + 1, :m],
                           None if fe is None else fe[b:b + 1])

    def fill(path, dst, *srcs):
        b_ax = 1 if _leaf_name(path)[1] else 0
        srcs = [jnp.pad(s, [(0, 0) if ax == b_ax else (0, d - a)
                            for ax, (a, d) in enumerate(zip(s.shape,
                                                            dst.shape))])
                for s in srcs]
        return jnp.concatenate(srcs, axis=b_ax).astype(dst.dtype)

    caches = jax.tree_util.tree_map_with_path(
        fill, init_cache(cfg, B, S + P), *[row(b, n[b])[1] for b in range(B)])
    refs = [row(b, n[b] + 1) for b in range(B)]
    logits, new, _ = jax.jit(decode_step, static_argnums=1)(
        params, cfg, tokens[np.arange(B), n][:, None], caches,
        jnp.asarray(lens, jnp.int32))

    for b in range(B):
        err = float(jnp.max(jnp.abs(logits[b] - refs[b][0][0])))
        assert err < 2e-4, f"{arch}: slot {b} decode/prefill mismatch {err}"
    assert jax.tree.structure(new) == jax.tree.structure(caches)

    def check(path, old, got, *ref):
        name, stacked = _leaf_name(path)
        old, got = np.asarray(old), np.asarray(got)
        assert got.shape == old.shape and got.dtype == old.dtype
        lead = (slice(None),) if stacked else ()
        if name in ROWS:
            written = np.zeros(old.shape, bool)
            for b in range(B):
                written[lead + (b, lens[b])] = True
            assert got[~written].tobytes() == old[~written].tobytes(), (
                f"{arch}: {name} changed outside the new rows")
            for b in range(B):
                np.testing.assert_allclose(
                    got[lead + (b, lens[b])],
                    np.asarray(ref[b])[lead + (0, lens[b])],
                    rtol=1e-4, atol=2e-4)
        elif name in ("conv", "ssd"):
            for b in range(B):
                np.testing.assert_allclose(
                    got[lead + (b,)], np.asarray(ref[b])[lead + (0,)],
                    rtol=1e-4, atol=2e-4)
        else:
            assert name in ("xk", "xv"), name
            assert got.tobytes() == old.tobytes(), f"{arch}: {name} written"

    jax.tree_util.tree_map_with_path(check, caches, new,
                                     *[r[1] for r in refs])


def test_param_counts_sane():
    # full configs: analytic counts in the right ballpark (catches config typos)
    expect = {
        "phi3-medium-14b": (12e9, 16e9),
        "qwen1.5-4b": (3e9, 5e9),
        "jamba-1.5-large-398b": (330e9, 430e9),
        "qwen3-moe-30b-a3b": (28e9, 33e9),
        "granite-moe-1b-a400m": (1.0e9, 1.7e9),
        "mamba2-370m": (0.30e9, 0.45e9),
        "gemma3-1b": (0.8e9, 1.3e9),
        "whisper-base": (0.05e9, 0.11e9),
        "deepseek-v2-lite": (15.5e9, 15.9e9),
    }
    from repro.models import count_params, count_params_analytic
    for arch, (lo, hi) in expect.items():
        n = count_params(get_config(arch))
        assert lo <= n <= hi, f"{arch}: {n / 1e9:.2f}B not in [{lo/1e9}, {hi/1e9}]"
    # MoE active < total
    for arch in ("qwen3-moe-30b-a3b", "granite-moe-1b-a400m",
                 "jamba-1.5-large-398b", "deepseek-v2-lite"):
        cfg = get_config(arch)
        assert count_params_analytic(cfg, True) < count_params(cfg) * 0.6
