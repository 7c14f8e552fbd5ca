"""The readers PR 31 added (``span_field``, ``kernel_roofline_live``,
``kernel_in_runs_share``) on a hand-built trace with exact answers."""
import pytest

import run
from lib import trace_reduce


def built(ops=(), modules=(), spans=(), lo=100.0, hi=110.0):
    """A trace whose slice is [lo, hi]; the events are given in slice time."""
    at = lambda evs: [(n, s + lo, d) for n, s, d in evs]
    return trace_reduce.Trace({0: at(ops)}, {0: at(modules)}, at(spans),
                              lo, hi)


def test_expert_readers_on_a_built_trace():
    """``span_field``, ``kernel_roofline_live`` and ``kernel_in_runs_share``
    (PR 31) with exact answers: the spans' own count of experts hit where
    they carry it, nothing where they do not; the kernel's time inside the
    steps alone, not a prefill's call that falls between two of them."""
    counts = run.load_module("counts", "cohere_moe")
    sizes = run.load_json("configs", "command-a-plus")["sizes"]
    step = lambda tag: "decode[step fill=0.50 b32%s]" % tag
    ops = [("moe_ffn.1", 0.0, 0.002), ("fusion.2", 0.002, 0.008),
           ("moe_ffn.9", 0.5, 0.100),          # a prefill's, between steps
           ("moe_ffn.3", 1.0, 0.004), ("fusion.4", 1.004, 0.006)]
    modules = [("jit_pure_step_c8192", 0.0, 0.010),
               ("jit_pure_prefill_t1024c8192", 0.5, 0.100),
               ("jit_pure_step_c8192", 1.0, 0.010)]
    record = {"counts": counts, "sizes": sizes, "chips": 1,
              "device_kind": "TPU v5 lite",
              "samples": {"slice_decode_context": [100] * 20}}
    roof = run.load_json("metrics", "moe_ffn_roofline.serve")
    reader = run.load_module("readers", roof["reader"])
    field = run.load_json("metrics", "expert_load_max_over_mean.serve")
    share = run.load_json("metrics", "moe_ffn_step_share.serve")
    for tags, experts in (((" xmax=2.00 xhit=30", " xmax=4.00 xhit=10"), 40),
                          (("", ""), None)):
        trace = built(ops, modules, [(step(tags[0]), 0.0, 0.012),
                                     (step(tags[1]), 1.0, 0.012)])
        record["trace"] = trace
        got = reader.read(record, roof["params"])
        assert got == (pytest.approx(
            100.0 * counts.moe_ffn_touched_bytes(sizes, experts, 20)
            / 819e9 / 0.006) if experts else None)
        got = run.load_module("readers", field["reader"]).read(
            record, field["params"])
        assert got == (3.0 if experts else None)
        assert run.load_module("readers", share["reader"]).read(
            record, share["params"]) == pytest.approx(100.0 * 0.006 / 0.020)
    # the program's own count is the smaller: random routers are not even
    assert counts.moe_ffn_touched_bytes(sizes, 40, 20) \
        < 2 * counts.moe_ffn_bytes(sizes, 10.0)
