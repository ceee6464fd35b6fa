"""Operation and byte counts against hand arithmetic, read through the
architecture each configuration file resolves to (``archs/dense_gqa.py``)."""
import functools
import json
from pathlib import Path

import pytest
from chipbench_tiny import BENCH

from chipbench import flops, spec


@functools.cache
def _bench():
    return spec.Bench()


def _arch(name):
    """(the architecture module, its dims) of a configuration file."""
    conf = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    arch, _ = _bench().architecture(conf)
    assert Path(arch.__file__) == BENCH / "archs" / "dense_gqa.py"
    return arch, arch.dims(conf)


def test_phi3_mini_l4_counts():
    a, d = _arch("phi3-mini-4k-l4")
    assert (d.d_head, d.n_kv_heads, d.window) == (96, 32, 2047)
    # wq, wk, wv, wo: 4 x 3072 x 3072; w1, w3, w2: 3 x 3072 x 8192
    layer = 4 * 3072 * 3072 + 3 * 3072 * 8192
    assert a.layer_matmul_params(d) == layer == 113_246_208
    assert a.param_count(d) == 4 * layer + 2 * 32064 * 3072 + 9 * 3072
    # per trained token: 3 x (2 x (4 layers + head) + attention over the
    # causal half of 1024 positions, all inside the window:
    # 4 x 4 x 32 x 96 x 1025 / 2 x 2)
    fwd = 2 * (4 * layer + 3072 * 32064) + 2 * 4 * 32 * 96 * 1025
    assert a.train_flops_per_token(d, 1024) == pytest.approx(3 * fwd)
    assert a.train_flops_per_token(d, 1024) == pytest.approx(3.3845e9,
                                                             rel=1e-4)
    assert a.cache_bytes_per_token(d) == 2 * 4 * 32 * 96 * 2 == 49152
    assert a.weight_bytes(d) == 2 * a.param_count(d)


def test_phi3_l10_counts():
    a, d = _arch("phi3-medium-4k-l10")
    assert (d.d_head, d.n_kv_heads, d.vocab_size) == (128, 10, 32064)
    # q 5120 x 5120, k and v 5120 x 1280 each, o 5120 x 5120, MLP 3 x 5120 x 17920
    layer = 5120 * 5120 * 2 + 2 * 5120 * 1280 + 3 * 5120 * 17920
    assert a.layer_matmul_params(d) == layer == 340_787_200
    assert a.param_count(d) == 10 * layer + 2 * 32064 * 5120 + 21 * 5120
    assert a.weight_bytes(d) == 7_472_629_760
    assert a.cache_bytes_per_token(d) == 2 * 10 * 10 * 128 * 2 == 51200
    # a 2048-token prefill: matmuls, causal attention in the window of 2047
    # (the last query sees 2047 keys, not 2048), the head once
    want = (2 * 2048 * 10 * layer
            + 4 * 10 * 40 * 128 * (2048 * 2049 / 2 - 1) + 2 * 5120 * 32064)
    assert a.prefill_flops(d, 2048) == pytest.approx(want)


def test_decode_attention_counts_the_live_context():
    a, d = _arch("phi3-medium-4k-l10")
    per = 2 * (10 * a.layer_matmul_params(d) + 5120 * 32064)
    att = 4 * 10 * 40 * 128
    assert a.decode_flops(d, 100, 1) == pytest.approx(per + att * 101)
    # two tokens from 100: queries at 100 and 101 see 101 and 102 keys
    assert a.decode_flops(d, 100, 2) == pytest.approx(
        2 * per + att * (101 + 102))
    # past the window of 2047 a query sees 2047 keys
    assert a.decode_flops(d, 2045, 4) == pytest.approx(
        4 * per + att * (2046 + 2047 + 2047 + 2047))


def test_window_caps_the_keys():
    assert flops.attended_keys(5, 0) == 1 + 2 + 3 + 4 + 5
    assert flops.attended_keys(5, 0, window=3) == 1 + 2 + 3 + 3 + 3
    assert flops.attended_keys(3, 4, window=3) == 3 * 3
    assert flops.attended_keys(4096, 0, window=2047) == (
        2047 * 2048 // 2 + (4096 - 2047) * 2047)
