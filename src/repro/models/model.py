"""Top-level model API: init / apply / counting, dispatched on ModelConfig."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import transformer


def init_params(cfg, key, dtype=None):
    return transformer.init_params(cfg, key, dtype)


def init_params_shape(cfg, dtype=None):
    """Parameter ShapeDtypeStructs without allocating (for dry-run)."""
    key = jax.random.PRNGKey(0)
    return jax.eval_shape(functools.partial(transformer.init_params, cfg,
                                            dtype=dtype), key)


forward_hidden = transformer.forward_hidden
lm_loss = transformer.lm_loss
prefill = transformer.prefill
decode_step = transformer.decode_step
init_cache = transformer.init_cache


def cache_struct(cfg, B: int, T: int):
    """ShapeDtypeStructs for a decode cache (for dry-run input specs)."""
    return jax.eval_shape(functools.partial(transformer.init_cache, cfg, B, T))


def count_params(cfg) -> int:
    tree = init_params_shape(cfg)
    return int(sum(np.prod(x.shape) for x in jax.tree.leaves(tree)))


def _moe_block_count(cfg) -> int:
    n = cfg.n_periods * sum(1 for m in cfg.mlp_pattern if m == "moe")
    n += sum(1 for m in cfg.mlp_pattern[: cfg.n_remainder] if m == "moe")
    return n


def count_params_analytic(cfg, active_only: bool = False) -> int:
    """Total params (leading dense layers and shared experts included);
    with active_only, the held experts count only the top_k/E share of them
    that a token is expected to use."""
    total = count_params(cfg)
    if not active_only or cfg.moe is None:
        return total
    spec = cfg.moe
    per_block_expert = 3 * cfg.d_model * spec.d_ff_expert  # w1,w3,w2
    if cfg.act != "swiglu":
        per_block_expert = 2 * cfg.d_model * spec.d_ff_expert
    n_moe = _moe_block_count(cfg)
    idle = spec.n_held - spec.top_k * spec.n_held / spec.n_experts
    return total - round(n_moe * idle * per_block_expert)


def model_flops(cfg, n_tokens: int, *, training: bool) -> float:
    """MODEL_FLOPS: 6·N·D (train) or 2·N·D (inference), N = active params."""
    n = count_params_analytic(cfg, active_only=True)
    # embeddings participate once per token; keep the standard 6ND convention
    return (6.0 if training else 2.0) * n * n_tokens
