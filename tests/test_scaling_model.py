"""Scaling-efficiency model: analytic ICI curve
asserts the BASELINE.md 0.90 row; the HLO collective parser is unit-tested;
the committed artifact must exist and be self-consistent with the model."""
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))
sys.path.insert(0, REPO)


def test_analytic_curve_meets_baseline_row():
    import scaling_model as sm

    chips = [8, 16, 32, 64, 128, 256]
    curve, t_c = sm.bert_dp_curve(chips, mfu=0.40, overlap=0.9)
    assert curve[-1]["chips"] == 256
    eff = curve[-1]["efficiency_vs_8"]
    assert eff >= 0.90, eff  # the BASELINE.md row the model must support
    # efficiency must be monotone non-increasing with chip count
    effs = [r["efficiency_vs_8"] for r in curve]
    assert all(a >= b - 1e-9 for a, b in zip(effs, effs[1:]))
    # worst case (zero overlap) must be strictly worse but sane
    worst, _ = sm.bert_dp_curve(chips, mfu=0.40, overlap=0.0)
    assert worst[-1]["efficiency_vs_8"] < eff
    assert worst[-1]["efficiency_vs_8"] > 0.5


def test_allreduce_time_model_shape():
    import scaling_model as sm

    # volume term: (n-1)/n growth, never decreasing with n
    t8 = sm.allreduce_time(4.4e8, 8)
    t256 = sm.allreduce_time(4.4e8, 256)
    assert t256 > t8
    # magnitude sanity: 440MB over 2x45GB/s ~ 2*440e6/90e9 ~ 9.8ms
    assert 0.005 < t256 < 0.02


def test_hlo_collective_parser():
    import scaling_model as sm

    hlo = """
  %all-reduce.1 = f32[1024,512]{1,0} all-reduce(f32[1024,512] %p), channel_id=1, replica_groups={{0,1},{2,3}}, to_apply=%add
  %ar2 = bf16[64]{0} all-reduce-start(bf16[64] %q), replica_groups={{0,1,2,3}}, to_apply=%add
  %cp = f32[8,4]{1,0} collective-permute(f32[8,4] %x), source_target_pairs={{0,1},{1,0}}
  %ag = (f32[16], f32[16]) all-gather(f32[8] %y, f32[8] %z), replica_groups={{0,2}}, dimensions={0}
  %noise = f32[2] add(f32[2] %a, f32[2] %b)
"""
    inv = sm.parse_hlo_collectives(hlo)
    assert inv["all-reduce"]["count"] == 2
    assert inv["all-reduce"]["bytes"] == 1024 * 512 * 4 + 64 * 2
    assert sorted(inv["all-reduce"]["group_sizes"]) == [2, 4]
    assert inv["collective-permute"]["count"] == 1
    assert inv["all-gather"]["bytes"] == 2 * 16 * 4
    assert "add" not in inv


def test_committed_artifact_consistent():
    path = os.path.join(REPO, "tools", "scaling_model_r5.json")
    assert os.path.exists(path), "run tools/scaling_model.py to regenerate"
    with open(path) as f:
        art = json.load(f)
    base = art["baseline_row"]
    assert base["model_prediction_overlap0.9"] >= 0.90
    # r5 hardening: the model must state its worst case and
    # where it CAN fail, not only validate
    assert "met_under_worst_case" in base
    assert "structural_note" in base
    dcn = base["dcn_sensitivity_8_to_1024_worst_case"]
    assert any(not v["meets_0.90"] for v in dcn.values()), \
        "model has no failure point — it cannot validate the target"
    assert any(v["meets_0.90"] for v in dcn.values())
    inv = art["composed_step_collectives"]["inventory"]
    # the composed dp x tp x pp program must actually communicate on all
    # three axes: tp/dp psums -> all-reduce, pp ring -> collective-permute
    assert "all-reduce" in inv and inv["all-reduce"]["count"] > 0
    assert "collective-permute" in inv \
        and inv["collective-permute"]["count"] > 0
    assert all(g == 2 for g in inv["all-reduce"]["group_sizes"])  # axis size 2


def test_tp_pp_dcn_terms():
    """The r5 terms behave physically: tp collectives grow with tp and sit
    on the critical path; the pp bubble is (S-1)/M; DCN kicks in past one
    pod and slows the cross-pod all-reduce."""
    import scaling_model as sm

    assert sm.tp_collective_time(1) == 0.0
    assert sm.tp_collective_time(8) > sm.tp_collective_time(2) > 0
    assert sm.pp_bubble_overhead(1, 32) == 0.0
    assert abs(sm.pp_bubble_overhead(4, 32) - 3 / 32) < 1e-12
    assert sm.dcn_allreduce_time(4.4e8, 256) == 0.0
    assert sm.dcn_allreduce_time(4.4e8, 1024) > 0
    # strategy table: tp/pp terms surface in step time
    t_c = 0.04
    dp = sm.strategy_step_time(256, 0.0, t_c)
    tp8 = sm.strategy_step_time(256, 0.0, t_c, tp=8)
    pp4 = sm.strategy_step_time(256, 0.0, t_c, pp=4)
    assert tp8["t_tp_collectives_ms"] > 0 and dp["t_tp_collectives_ms"] == 0
    assert pp4["t_pp_bubble_ms"] > 0
    # sharded grads: smaller exposed dp all-reduce under tp/pp
    assert tp8["t_dp_allreduce_ms"] < dp["t_dp_allreduce_ms"]


def test_required_overlap_is_honest():
    """required_overlap_for scans the same formulas as the curve: at an mfu
    where the worst case already meets 0.90 it returns 0.0; an absurdly
    slow DCN pushes the requirement toward full overlap (it always lands
    in [0,1] — at overlap 1.0 nothing is exposed)."""
    import scaling_model as sm

    assert sm.required_overlap_for(0.90, [8, 256], 0.4) == 0.0
    saved = sm.DCN_GBYTES_PER_HOST
    try:
        sm.DCN_GBYTES_PER_HOST = 0.01
        need = sm.required_overlap_for(0.90, [8, 1024], 0.4)
        assert need is not None and need > 0.9  # always lands in [0,1]
    finally:
        sm.DCN_GBYTES_PER_HOST = saved
