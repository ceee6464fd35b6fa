"""How the benchmark counts the operations a model's work requires.

Each architecture's counts (``archs/<name>.py``) are taken from the shapes
alone, as the benchmark's yardstick: a multiply-add is 2 operations; the
embedding lookup is a gather and counts nothing; recomputation under remat
counts nothing; causal attention counts the keys each query attends to only
(keys 0..i, or the last ``window`` of them), whatever the program computes
of the masked part.
"""
from __future__ import annotations


def attended_keys(n_queries: int, first_pos: int, window=None) -> int:
    """Keys seen, summed over queries at positions first_pos ..
    first_pos + n_queries - 1: the query at i sees i + 1 keys, or
    ``window`` once i + 1 exceeds it."""
    def upto(n):                      # queries at positions 0 .. n - 1
        if window is None or n <= window:
            return n * (n + 1) // 2
        return window * (window + 1) // 2 + (n - window) * window
    return upto(first_pos + n_queries) - upto(first_pos)
