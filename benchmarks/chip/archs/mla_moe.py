"""A DeepSeek-V2 decoder: multi-head latent attention (MLA) with YaRN rope,
leading dense layers, then expert layers of routed and shared SwiGLU
experts, of which this chip holds a share.  Its sizes in the program's
terms, the program's ``ModelConfig`` for a configuration file, and the
operations and bytes its work requires.

The configuration file uses the public config.json's keys.  Its
``n_routed_experts`` counts the experts held here (experts 0 .. n - 1);
``published.n_routed_experts`` is the router's full width.

The counts follow ``chipbench.flops``'s rules, from the shapes alone:

- a token multiplies through every projection once: W_q, W_kva, W_kvb and
  W_o (in decode W_kvb's two halves are absorbed into the query and the
  output, the same multiply-adds), its layer's MLP or shared experts, the
  router, and the expected share of its routed experts that lies here,
  top_k * n_held / n_routed of them (0.75 for 6 of 64 with 8 held);
- attention counts each key a query attends to: in prefill, as the program
  computes it, per-head keys and values, 2 * H * (qk_head + v_head); in
  decode, absorbed into the latent, 2 * H * (kv_rank + rope + kv_rank);
- the head once per prefill and once per decoded token.

There is no training count: no cell trains this architecture, and the
reference (``references/mla_moe.py``) has no loss.
"""
from __future__ import annotations

import dataclasses

from chipbench.flops import attended_keys


@dataclasses.dataclass(frozen=True)
class Dims:
    """The sizes of a DeepSeek-V2 decoder, in the program's terms."""
    n_layers: int                 # every layer, the dense ones included
    n_dense_layers: int
    d_model: int
    n_heads: int
    q_lora_rank: int | None       # None: queries projected directly
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    d_ff: int                     # the dense layers' MLP
    d_ff_expert: int
    d_ff_shared: int              # the shared experts, as one MLP
    n_experts: int                # the router's width
    n_held: int                   # experts 0 .. n_held - 1 live here
    top_k: int
    norm_topk_prob: bool
    vocab_size: int
    tie_embeddings: bool
    norm_eps: float
    rope_theta: float
    yarn_factor: float
    yarn_original_max: int
    yarn_beta_fast: float
    yarn_beta_slow: float
    yarn_mscale: float
    yarn_mscale_all_dim: float
    dtype: str

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim


def _require(conf: dict, key: str, value):
    if conf.get(key, value) != value:
        raise SystemExit(f"chipbench: mla_moe runs {key} {value!r} only, "
                         f"not {conf[key]!r}")


def dims(conf: dict) -> Dims:
    for key, value in (("hidden_act", "silu"), ("scoring_func", "softmax"),
                       ("topk_method", "greedy"), ("moe_layer_freq", 1),
                       ("routed_scaling_factor", 1), ("n_group", 1),
                       ("topk_group", 1)):
        _require(conf, key, value)
    ys = conf["rope_scaling"]
    if ys.get("type") != "yarn":
        raise SystemExit("chipbench: mla_moe runs YaRN rope scaling only")
    return Dims(
        n_layers=conf["num_hidden_layers"],
        n_dense_layers=conf["first_k_dense_replace"],
        d_model=conf["hidden_size"], n_heads=conf["num_attention_heads"],
        q_lora_rank=conf["q_lora_rank"], kv_lora_rank=conf["kv_lora_rank"],
        qk_nope_head_dim=conf["qk_nope_head_dim"],
        qk_rope_head_dim=conf["qk_rope_head_dim"],
        v_head_dim=conf["v_head_dim"], d_ff=conf["intermediate_size"],
        d_ff_expert=conf["moe_intermediate_size"],
        d_ff_shared=conf["n_shared_experts"] * conf["moe_intermediate_size"],
        n_experts=conf.get("published", {}).get("n_routed_experts",
                                                conf["n_routed_experts"]),
        n_held=conf["n_routed_experts"], top_k=conf["num_experts_per_tok"],
        norm_topk_prob=conf["norm_topk_prob"], vocab_size=conf["vocab_size"],
        tie_embeddings=conf["tie_word_embeddings"],
        norm_eps=conf["rms_norm_eps"], rope_theta=float(conf["rope_theta"]),
        yarn_factor=float(ys["factor"]),
        yarn_original_max=ys["original_max_position_embeddings"],
        yarn_beta_fast=float(ys["beta_fast"]),
        yarn_beta_slow=float(ys["beta_slow"]),
        yarn_mscale=float(ys["mscale"]),
        yarn_mscale_all_dim=float(ys["mscale_all_dim"]),
        dtype=conf["torch_dtype"])


def program_config(conf: dict):
    """The program's ``ModelConfig``: its registered architecture named by
    ``program_base``, with every size the file states put in, holding
    experts 0 .. n_routed_experts - 1 of the router's width."""
    from repro.configs import MLASpec, MoESpec, YaRNSpec, get_config

    d = dims(conf)
    return dataclasses.replace(
        get_config(conf["program_base"]), name=conf["name"],
        n_layers=d.n_layers, n_dense_layers=d.n_dense_layers,
        d_model=d.d_model, n_heads=d.n_heads, n_kv_heads=d.n_heads,
        d_head=d.qk_head_dim, d_ff=d.d_ff, vocab_size=d.vocab_size,
        tie_embeddings=d.tie_embeddings, norm_eps=d.norm_eps,
        rope_theta=d.rope_theta, act="swiglu", dtype=d.dtype,
        layer_pattern=("attn",), mlp_pattern=("moe",),
        rope_scaling=YaRNSpec(
            factor=d.yarn_factor,
            original_max_position_embeddings=d.yarn_original_max,
            beta_fast=d.yarn_beta_fast, beta_slow=d.yarn_beta_slow,
            mscale=d.yarn_mscale, mscale_all_dim=d.yarn_mscale_all_dim),
        mla=MLASpec(q_lora_rank=d.q_lora_rank, kv_lora_rank=d.kv_lora_rank,
                    qk_nope_head_dim=d.qk_nope_head_dim,
                    qk_rope_head_dim=d.qk_rope_head_dim,
                    v_head_dim=d.v_head_dim),
        moe=MoESpec(n_experts=d.n_experts, top_k=d.top_k,
                    d_ff_expert=d.d_ff_expert,
                    norm_topk_prob=d.norm_topk_prob,
                    n_local_experts=d.n_held,
                    d_ff_shared=d.d_ff_shared),
        qkv_bias=False, embed_scale=1.0, attn_window=None,
        rope_theta_local=None, attn_logit_softcap=None)


def attention_matmul_params(d) -> int:
    """W_q (or its two low-rank factors), W_kva, W_kvb and W_o."""
    H = d.n_heads
    if d.q_lora_rank is None:
        q = d.d_model * H * d.qk_head_dim
    else:
        q = (d.d_model + H * d.qk_head_dim) * d.q_lora_rank
    return (q + d.d_model * (d.kv_lora_rank + d.qk_rope_head_dim)
            + d.kv_lora_rank * H * (d.qk_nope_head_dim + d.v_head_dim)
            + H * d.v_head_dim * d.d_model)


def expert_params(d) -> int:
    """One routed expert's SwiGLU."""
    return 3 * d.d_model * d.d_ff_expert


def moe_layer_params(d) -> int:
    """An expert layer as held here: the router, the held experts and the
    shared experts."""
    return (d.d_model * d.n_experts + d.n_held * expert_params(d)
            + 3 * d.d_model * d.d_ff_shared)


def token_matmul_params(d) -> float:
    """Weights one token multiplies through in all layers, the routed
    experts at their expected share here."""
    routed = d.top_k * d.n_held / d.n_experts * expert_params(d)
    moe = (d.d_model * d.n_experts + routed + 3 * d.d_model * d.d_ff_shared)
    return (d.n_layers * attention_matmul_params(d)
            + d.n_dense_layers * 3 * d.d_model * d.d_ff
            + (d.n_layers - d.n_dense_layers) * moe)


def head_params(d) -> int:
    return d.d_model * d.vocab_size


def param_count(d) -> int:
    """Every parameter held here: the embedding, the head, the norms' scales
    (two a layer, the latent's, the final one), and the layers."""
    norms = d.n_layers * (2 * d.d_model + d.kv_lora_rank) + d.d_model
    if d.q_lora_rank is not None:
        norms += d.n_layers * d.q_lora_rank
    head = 0 if d.tie_embeddings else head_params(d)
    return (d.vocab_size * d.d_model + head + norms
            + d.n_layers * attention_matmul_params(d)
            + d.n_dense_layers * 3 * d.d_model * d.d_ff
            + (d.n_layers - d.n_dense_layers) * moe_layer_params(d))


def weight_bytes(d, itemsize: int = 2) -> int:
    """The router is kept in float32, everything else in ``itemsize``."""
    router = (d.n_layers - d.n_dense_layers) * d.d_model * d.n_experts
    return (param_count(d) - router) * itemsize + router * 4


def prefill_flops(d, prompt_len: int) -> float:
    """Every prompt token through the layers, per-head attention over the
    causal keys, the head once."""
    att = 2.0 * d.n_heads * (d.qk_head_dim + d.v_head_dim)
    return (2.0 * prompt_len * token_matmul_params(d)
            + d.n_layers * att * attended_keys(prompt_len, 0)
            + 2.0 * head_params(d))


def decode_flops(d, context: int, n_tokens: int) -> float:
    """n_tokens decoded one by one, the first at position ``context``, each
    attending in the latent space over its live keys."""
    att = 2.0 * d.n_heads * (2 * d.kv_lora_rank + d.qk_rope_head_dim)
    per = 2.0 * (token_matmul_params(d) + head_params(d))
    return (n_tokens * per
            + d.n_layers * att * attended_keys(n_tokens, context))


def cache_bytes_per_token(d, itemsize: int = 2) -> int:
    """The latent and the rope key of one position, over every layer."""
    return d.n_layers * (d.kv_lora_rank + d.qk_rope_head_dim) * itemsize
