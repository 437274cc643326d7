"""Roofline extraction unit tests: HLO collective parsing + terms."""

import pytest

from repro.launch import roofline as R

HLO_SAMPLE = """
  %ag = bf16[16,4096,5120] all-gather(bf16[16,256,5120] %x), replica_groups={{0,1,2,3,4,5,6,7,8,9,10,11,12,13,14,15}}, dimensions={1}
  %ar = f32[16,256,5120] all-reduce(f32[16,256,5120] %y), replica_groups=[16,16]<=[256] to_apply=%add
  %rs = bf16[16,256,5120] reduce-scatter(bf16[16,4096,5120] %z), replica_groups={{0,1,2,3,4,5,6,7,8,9,10,11,12,13,14,15}}, dimensions={1}
  %a2a = bf16[16,256,128] all-to-all(bf16[16,256,128] %w), replica_groups={{0,1,2,3}}
  %cp = f32[8,128] collective-permute(f32[8,128] %v), source_target_pairs={{0,1}}
"""


def test_collective_parse_kinds():
    wire = R.collective_wire_bytes(HLO_SAMPLE, 16)
    assert wire["all-gather"] > 0
    assert wire["all-reduce"] > 0
    assert wire["reduce-scatter"] > 0
    assert wire["all-to-all"] > 0
    assert wire["collective-permute"] > 0


def test_allgather_wire_formula():
    wire = R.collective_wire_bytes(HLO_SAMPLE, 16)
    full = 16 * 4096 * 5120 * 2
    assert wire["all-gather"] == pytest.approx(full * 15 / 16)


def test_allreduce_uses_iota_groups():
    wire = R.collective_wire_bytes(HLO_SAMPLE, 999)
    size = 16 * 256 * 5120 * 4
    assert wire["all-reduce"] == pytest.approx(2 * size * 15 / 16)


def test_reduce_scatter_scales_by_group():
    wire = R.collective_wire_bytes(HLO_SAMPLE, 16)
    shard = 16 * 256 * 5120 * 2
    assert wire["reduce-scatter"] == pytest.approx(shard * 16 * 15 / 16)


def test_terms_and_dominant():
    t = R.RooflineTerms(hlo_flops=197e12, hlo_bytes=819e9 * 2,
                        wire_bytes=50e9 * 0.5, wire_by_kind={},
                        model_flops=197e12 * 256 * 0.5, n_devices=256)
    assert t.compute_s == pytest.approx(1.0)
    assert t.memory_s == pytest.approx(2.0)
    assert t.collective_s == pytest.approx(0.5)
    assert t.dominant == "memory"
    assert t.useful_flops_ratio == pytest.approx(0.5)

