"""Roofline math + HLO collective parser."""
import pytest

from repro.roofline import hw
from repro.roofline.analysis import parse_collective_bytes, roofline

HLO = """
HloModule test
  %x = bf16[128,1024]{1,0} parameter(0)
  %ar = bf16[128,1024]{1,0} all-reduce(bf16[128,1024]{1,0} %x), replica_groups={}
  %ag = f32[256,512]{1,0} all-gather(f32[16,512]{1,0} %y), dimensions={0}
  %rs = f32[16,512]{1,0} reduce-scatter(f32[256,512]{1,0} %z), dimensions={0}
  %a2a = bf16[64,64]{1,0} all-to-all(bf16[64,64]{1,0} %w), dimensions={0}
  %cp = s32[8]{0} collective-permute(s32[8]{0} %v), source_target_pairs={{0,1}}
  %dot = f32[128,128]{1,0} dot(f32[128,64]{1,0} %a, f32[64,128]{1,0} %b)
"""


def test_parse_collectives():
    out = parse_collective_bytes(HLO)
    assert out["all-reduce"] == pytest.approx(2 * 128 * 1024 * 2)
    assert out["all-gather"] == pytest.approx(256 * 512 * 4)
    assert out["reduce-scatter"] == pytest.approx(16 * 512 * 4)
    assert out["all-to-all"] == pytest.approx(64 * 64 * 2)
    assert out["collective-permute"] == pytest.approx(8 * 4)
    counts = out["_op_counts"]
    assert counts["all-reduce"] == 1 and counts["all-gather"] == 1


def test_parse_tuple_form_async():
    hlo = ('%ar = (bf16[4,8]{1,0}, bf16[4,8]{1,0}) all-reduce-start('
           'bf16[4,8]{1,0} %p), replica_groups={}')
    out = parse_collective_bytes(hlo)
    assert out["all-reduce"] == pytest.approx(2 * 2 * 4 * 8 * 2)


def test_roofline_terms_and_dominant():
    cost = {"flops": 197e12, "bytes accessed": 819e9 / 2}
    t = roofline(cost, "", n_devices=256, model_flops_global=197e12 * 256)
    assert t.compute_s == pytest.approx(1.0)
    assert t.memory_s == pytest.approx(0.5)
    assert t.dominant == "compute"
    assert t.useful_ratio == pytest.approx(1.0)
    assert t.roofline_fraction() == pytest.approx(1.0)


def test_collective_dominant():
    cost = {"flops": 1e9, "bytes accessed": 1e6}
    hlo = "%ar = f32[1000000]{0} all-reduce(f32[1000000]{0} %x)"
    t = roofline(cost, hlo, n_devices=4)
    assert t.dominant == "collective"
    assert t.collective_bytes == pytest.approx(8e6)


def test_hw_constants():
    assert hw.PEAK_FLOPS_BF16 == 197e12
    assert hw.HBM_BW == 819e9
    assert hw.ICI_BW_PER_LINK == 50e9


def test_peak_table_keyed_by_device_kind():
    v5e = hw.chip_peak("TPU v5 lite")
    assert (v5e.flops_bf16, v5e.hbm_bw, v5e.hbm_bytes) == \
        (197e12, 819e9, 16 * 1024 ** 3)
    with pytest.raises(ValueError, match="no published peak"):
        hw.chip_peak("cpu")
    assert hw.resolve_peak_flops(3e11) == 3e11
    # this test process runs on the CPU, which the table does not list
    with pytest.raises(ValueError, match="pass peak_flops explicitly"):
        hw.resolve_peak_flops(None)
