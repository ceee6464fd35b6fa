"""A dense grouped-query-attention decoder (the Phi-3 blocks): its sizes in
the program's terms, the program's ``ModelConfig`` for a configuration file,
and the operations and bytes its work requires.

The counts follow ``chipbench.flops``'s rules, from the shapes alone.
"""
from __future__ import annotations

import dataclasses

from chipbench.flops import attended_keys


@dataclasses.dataclass(frozen=True)
class Dims:
    """The sizes of a dense GQA decoder, in the program's terms."""
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_head: int
    d_ff: int
    vocab_size: int
    tie_embeddings: bool
    norm_eps: float
    rope_theta: float
    dtype: str
    window: int | None = None     # a query attends to keys q - k < window


# configuration file key (as in a Hugging Face config.json) -> Dims field
_KEYS = {"num_hidden_layers": "n_layers", "hidden_size": "d_model",
         "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
         "intermediate_size": "d_ff", "vocab_size": "vocab_size",
         "tie_word_embeddings": "tie_embeddings", "rms_norm_eps": "norm_eps",
         "rope_theta": "rope_theta", "torch_dtype": "dtype"}


def dims(conf: dict) -> Dims:
    vals = {field: conf[key] for key, field in _KEYS.items()}
    vals["d_head"] = conf.get("head_dim",
                              conf["hidden_size"] // conf["num_attention_heads"])
    vals["window"] = conf.get("sliding_window")
    return Dims(**vals)


def program_config(conf: dict):
    """The program's ``ModelConfig`` for a configuration file: the program's
    registered architecture named by ``program_base``, with every size the
    file states put in.  A ``sliding_window`` makes every layer a windowed
    one (the program's ``attn_local``), as the config states it."""
    from repro.configs import get_config

    if conf.get("hidden_act") != "silu":
        raise SystemExit("chipbench: only SwiGLU (hidden_act silu) blocks")
    d = dims(conf)
    kind = "attn_local" if d.window else "attn"
    return dataclasses.replace(
        get_config(conf["program_base"]), name=conf["name"],
        n_layers=d.n_layers, d_model=d.d_model, n_heads=d.n_heads,
        n_kv_heads=d.n_kv_heads, d_head=d.d_head, d_ff=d.d_ff,
        vocab_size=d.vocab_size, tie_embeddings=d.tie_embeddings,
        norm_eps=d.norm_eps, rope_theta=d.rope_theta, act="swiglu",
        dtype=d.dtype, layer_pattern=(kind,), mlp_pattern=("mlp",),
        qkv_bias=False, embed_scale=1.0, attn_window=d.window,
        rope_theta_local=None, attn_logit_softcap=None)


def layer_matmul_params(d) -> int:
    """Weights one token multiplies through in one block."""
    q = d.n_heads * d.d_head
    kv = d.n_kv_heads * d.d_head
    return d.d_model * (q + 2 * kv) + q * d.d_model + 3 * d.d_model * d.d_ff


def head_params(d) -> int:
    return d.d_model * d.vocab_size


def param_count(d) -> int:
    """Every parameter, the embedding table and the norms' scales included."""
    embed = d.vocab_size * d.d_model
    head = 0 if d.tie_embeddings else head_params(d)
    norms = (2 * d.n_layers + 1) * d.d_model
    return d.n_layers * layer_matmul_params(d) + embed + head + norms


def attention_flops(d, n_queries: int, first_pos: int) -> float:
    """Scores and weighted values for queries at positions first_pos ..
    first_pos + n_queries - 1, each over the keys it attends to."""
    keys = attended_keys(n_queries, first_pos, d.window)
    return 4.0 * d.n_layers * d.n_heads * d.d_head * keys


def train_flops_per_token(d, seq_len: int) -> float:
    """Forward and backward (3x forward) per trained token."""
    fwd = 2.0 * (d.n_layers * layer_matmul_params(d) + head_params(d))
    fwd += attention_flops(d, seq_len, 0) / seq_len
    return 3.0 * fwd


def prefill_flops(d, prompt_len: int) -> float:
    """A prefill: every prompt token through the blocks, the head once
    (the program asks for the last position's logits only)."""
    return (2.0 * prompt_len * d.n_layers * layer_matmul_params(d)
            + attention_flops(d, prompt_len, 0) + 2.0 * head_params(d))


def decode_flops(d, context: int, n_tokens: int) -> float:
    """n_tokens decoded one by one, the first at position ``context``."""
    per = 2.0 * (d.n_layers * layer_matmul_params(d) + head_params(d))
    return n_tokens * per + attention_flops(d, n_tokens, context)


def weight_bytes(d, itemsize: int = 2) -> int:
    return param_count(d) * itemsize


def cache_bytes_per_token(d, itemsize: int = 2) -> int:
    """Keys and values of one position, over every layer."""
    return 2 * d.n_layers * d.n_kv_heads * d.d_head * itemsize
