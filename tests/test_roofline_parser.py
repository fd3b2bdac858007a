"""Unit tests for the HLO roofline parser (launch/roofline.py): trip-count
multiplication, wire-byte factors, bf16 dtype correction, dot-FLOP
accounting — on hand-written HLO snippets with known answers.
"""
import numpy as np

from repro.launch.roofline import (parse_hlo_collectives, _wire_factor,
                                   _shape_bytes, analytic_flops,
                                   model_param_counts)

HLO = """
HloModule test

%body_1 (p: (s32[], f32[128,256])) -> (s32[], f32[128,256]) {
  %p = (s32[], f32[128,256]) parameter(0)
  %ar = f32[128,256]{1,0} all-reduce(%gte), replica_groups=[16,16]<=[256], metadata={op_name="jit(f)/...d,df->...f/dot_general"}
  %d = f32[128,256]{1,0} dot(%ar, %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
}

%cond_1 (p: (s32[], f32[128,256])) -> pred[] {
  %c = s32[] constant(4)
}

ENTRY %main (a: f32[128,256]) -> f32[128,256] {
  %a = f32[128,256] parameter(0)
  %ag = f32[64,512]{1,0} all-gather(%a), replica_groups=[32,8]<=[256], dimensions={1}
  %w = (s32[], f32[128,256]) while(%t), condition=%cond_1, body=%body_1, backend_config={"known_trip_count":{"n":"4"}}
}
"""


def test_wire_factors():
    assert _wire_factor("all-reduce", 16) == 2 * 15 / 16
    assert _wire_factor("all-gather", 8) == 7 / 8
    assert _wire_factor("reduce-scatter", 4) == 3.0
    assert _wire_factor("collective-permute", 2) == 1.0
    assert _wire_factor("all-reduce", 1) == 0.0


def test_shape_bytes():
    assert _shape_bytes("f32", "128,256") == 128 * 256 * 4
    assert _shape_bytes("bf16", "8") == 16
    assert _shape_bytes("pred", "") == 1


def test_parser_trip_counts_and_kinds():
    r = parse_hlo_collectives(HLO)
    # all-reduce inside body x4 trips, output 128*256*4 B, factor 2*15/16
    ar_out = 128 * 256 * 4
    assert r["bytes_by_kind"]["all-reduce"] == 4 * ar_out
    np.testing.assert_allclose(r["wire_bytes_by_kind"]["all-reduce"],
                               4 * ar_out * 2 * 15 / 16)
    # entry all-gather once, group size 8
    ag_out = 64 * 512 * 4
    np.testing.assert_allclose(r["wire_bytes_by_kind"]["all-gather"],
                               ag_out * 7 / 8)
    # dot inside body: out 128*256 elems x contracting 256 x 2 flops x 4 trips
    np.testing.assert_allclose(r["dot_flops"], 4 * 2 * 128 * 256 * 256)


def test_parser_bf16_correction():
    r = parse_hlo_collectives(HLO, bf16_dot_comms=True)
    ar_out = 128 * 256 * 4 // 2            # tagged dot_general -> halved
    assert r["bytes_by_kind"]["all-reduce"] == 4 * ar_out
    # the all-gather has no dot tag -> unchanged
    assert r["bytes_by_kind"]["all-gather"] == 64 * 512 * 4


def test_analytic_flops_moe_uses_active_params():
    from repro.configs import get_config
    dense = get_config("llama3-8b")
    moe = get_config("phi3.5-moe-42b-a6.6b")
    info = {"seq": 4096, "global_batch": 256, "kind": "train"}
    cm = model_param_counts(moe)
    assert cm["active"] < cm["total"]
    fd = analytic_flops(dense, info, 256, local_steps=8)
    fm = analytic_flops(moe, info, 256, local_steps=8)
    # phi3.5 total 42B but active 6.6B-ish: flops must track active
    assert fm["params"]["total"] > 35e9
    assert fm["params"]["active"] < 9e9
    assert fd["model_flops"] > 0 and fm["model_flops"] > 0


def test_chip_peaks_keyed_by_device_kind():
    import pytest
    from repro.launch.roofline import chip_peaks
    v5e = chip_peaks("TPU v5 lite")
    assert v5e["flops"] == 197e12 and v5e["hbm_bw"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        chip_peaks("cpu")
