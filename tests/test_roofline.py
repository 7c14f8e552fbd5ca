"""tools/roofline.py — the no-hardware roofline report."""
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_roofline_smoke_artifact(tmp_path):
    """One smoke mode end-to-end: compiles (never executes) the bench train
    step, emits flops/bytes/AI/ceiling-MFU and a non-empty non-matmul sink
    list with plausible values."""
    out = tmp_path / "roofline.json"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "roofline.py"),
         "--modes", "lstm", "--smoke", "--json", str(out)],
        capture_output=True, text=True, timeout=560, cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-2000:]
    rec = json.loads(out.read_text())
    assert rec["critical_intensity_flops_per_byte"] > 100
    m = rec["modes"]["lstm"]
    assert "error" not in m, m
    assert m["flops_per_step"] > 1e8
    assert m["hbm_bytes_per_step"] > 1e6
    assert 0 < m["ceiling_mfu_v5e"] <= 1.0
    assert m["bound"] in ("compute", "memory")
    sinks = m["top_non_matmul_sinks"]
    assert sinks and all(s["out_bytes"] > 0 for s in sinks)
    assert all(s["op"] not in ("dot", "convolution", "custom-call")
               for s in sinks)


def test_top_sinks_parser():
    """The HLO parser ranks by output bytes and skips matmul/bookkeeping."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import importlib

    rl = importlib.import_module("roofline")
    hlo = """
HloModule m

%fused_computation.1 (param_0: f32[128,30522]) -> f32[128,30522] {
  %param_0 = f32[128,30522]{1,0} parameter(0)
  ROOT %exp.9 = f32[128,30522]{1,0} exponential(%param_0)
}

ENTRY %main (p0: f32[128,30522]) -> (f32[128,30522]) {
  %p0 = f32[128,30522]{1,0} parameter(0)
  %fusion.1 = f32[128,30522]{1,0} fusion(%p0), kind=kLoop, calls=%fused_computation.1
  %dot.2 = f32[128,768]{1,0} dot(%p0, %p0), lhs_contracting_dims={1}
  %copy.3 = bf16[128,768]{1,0} copy(%dot.2)
  ROOT %tuple.4 = (f32[128,30522]{1,0}) tuple(%fusion.1)
}
"""
    sinks = rl.top_sinks(hlo, k=5)
    # the fusion BODY's exponential is registers, not HBM — only ENTRY
    # instructions count
    assert [s["op"] for s in sinks] == ["fusion", "copy"]
    assert sinks[0]["out_bytes"] == 128 * 30522 * 4
    assert sinks[1]["out_bytes"] == 128 * 768 * 2
    agg = rl.aggregate_sinks(hlo, k=2)
    assert agg[0]["total_bytes"] == 128 * 30522 * 4
    assert "LM log-probs" in agg[0]["mitigation"]
