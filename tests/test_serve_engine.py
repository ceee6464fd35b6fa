"""Serving engine: completion, continuous batching, overload integration."""
import jax
import numpy as np
import pytest

from repro.configs import reduced_config
from repro.models import init_params
from repro.serve.engine import EngineConfig, Request, ServeEngine

KEY = jax.random.PRNGKey(0)
# The CPU has no published peak, so monitored engines are given one.
CPU_PEAK_FLOPS = 5e10


def _engine(arch="llsc-100m", slots=2, max_seq=64):
    cfg = reduced_config(arch)
    params = init_params(cfg, KEY)
    return cfg, ServeEngine(cfg, params,
                            EngineConfig(slots=slots, max_seq_len=max_seq,
                                         monitor=True,
                                         peak_flops=CPU_PEAK_FLOPS))


def _req(i, n=6, prompt_len=8, vocab=512):
    rng = np.random.default_rng(i)
    return Request(i, rng.integers(0, vocab, prompt_len).astype(np.int32),
                   max_new_tokens=n)


def test_completes_all_requests():
    cfg, eng = _engine(slots=2)
    for i in range(5):
        eng.submit(_req(i))
    stats = eng.run()
    assert stats["requests"] == 5
    ids = sorted(c.request_id for c in eng.completions)
    assert ids == list(range(5))
    for c in eng.completions:
        assert len(c.tokens) == 6
        assert all(0 <= t < cfg.vocab_size for t in c.tokens)


def test_deterministic_across_slot_counts():
    """Greedy generations are identical with 1 slot vs 4 slots."""
    _, e1 = _engine(slots=1)
    _, e4 = _engine(slots=4)
    for i in range(4):
        e1.submit(_req(i))
        e4.submit(_req(i))
    e1.run()
    e4.run()
    out1 = {c.request_id: c.tokens for c in e1.completions}
    out4 = {c.request_id: c.tokens for c in e4.completions}
    assert out1 == out4


def test_ssm_arch_serving():
    """State-carrying arch (mamba2) must decode correctly after prefill."""
    _, eng = _engine(arch="mamba2-370m", slots=2)
    for i in range(3):
        eng.submit(_req(i, n=4))
    stats = eng.run()
    assert stats["requests"] == 3


def test_overload_controller_sees_duty():
    _, eng = _engine(slots=2)
    for i in range(4):
        eng.submit(_req(i))
    stats = eng.run()
    assert stats["decision"].nppn in (1, 2, 4, 8)
    assert eng.controller.history, "controller should have observations"


def test_throughput_reported():
    _, eng = _engine(slots=2)
    eng.submit(_req(0))
    stats = eng.run()
    assert stats["tokens_per_s"] > 0
    assert stats["tokens"] >= stats["requests"]


def test_monitored_engine_needs_a_known_peak():
    """A device missing from roofline.hw's table has no default peak."""
    cfg = reduced_config("llsc-100m")
    params = init_params(cfg, KEY)
    with pytest.raises(ValueError, match="no published peak"):
        ServeEngine(cfg, params, EngineConfig(monitor=True))
    eng = ServeEngine(cfg, params, EngineConfig(monitor=False))
    assert eng.peak_flops is None


@pytest.mark.parametrize("arch,prompt_len,share", [
    ("llsc-100m", 8, 0.5),        # every key in the first of two blocks
    ("llsc-100m", 600, 1.0),      # every slot's keys span both blocks
    ("gemma3-1b", 8, 0.5),        # local (window 8) and global layers
])
def test_kv_rows_read_share_is_the_kernels_blocks(arch, prompt_len, share):
    """One slot, one request: the decode steps run at lens = P .. P + n - 2,
    and the counter is the kernel's block range over those lens, a block of
    512 rows in a cache of 1024."""
    from repro.kernels.decode_attention import kv_block_range
    from repro.serve.engine import kv_read_groups

    T, n = 1024, 6
    cfg, eng = _engine(arch, slots=1, max_seq=T)
    eng.submit(_req(0, n=n, prompt_len=prompt_len))
    stats = eng.run()
    groups = kv_read_groups(cfg, T)
    lens = np.arange(prompt_len, prompt_len + n - 1)
    rows = 0
    for (blk, window), n_layers in groups.items():
        assert blk == 512
        first, last = kv_block_range(lens, T, blk, window)
        rows += n_layers * blk * int(np.sum(last - first + 1))
    want = rows / (len(lens) * sum(groups.values()) * T)
    assert stats["steps"] == len(lens)
    assert stats["kv_rows_read_share"] == pytest.approx(want)
    assert want == pytest.approx(share)


def test_kv_rows_read_share_needs_gqa_layers():
    """Latent attention reads its cache without the kernel: no share."""
    _, eng = _engine("deepseek-v2-lite", slots=1)
    eng.submit(_req(0, n=3))
    assert eng.run()["kv_rows_read_share"] is None
