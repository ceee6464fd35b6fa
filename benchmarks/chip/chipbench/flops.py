"""Operations and bytes that a dense GQA decoder's work requires.

Counted from the shapes alone, as the benchmark's yardstick: a multiply-add
is 2 operations; the embedding lookup is a gather and counts nothing;
recomputation under remat counts nothing; causal attention counts the
keys each query attends to only (keys 0..i, or the last ``window`` of
them), whatever the program computes of the masked part.
"""
from __future__ import annotations


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


def attended_keys(n_queries: int, first_pos: int, window=None) -> int:
    """Keys seen, summed over queries at positions first_pos ..
    first_pos + n_queries - 1: the query at i sees i + 1 keys, or
    ``window`` once i + 1 exceeds it."""
    def upto(n):                      # queries at positions 0 .. n - 1
        if window is None or n <= window:
            return n * (n + 1) // 2
        return window * (window + 1) // 2 + (n - window) * window
    return upto(first_pos + n_queries) - upto(first_pos)


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


def kv_bytes_per_token(d, itemsize: int = 2) -> int:
    return 2 * d.n_layers * d.n_kv_heads * d.d_head * itemsize
