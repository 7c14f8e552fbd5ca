"""The readers of the decode loop's spans and of the named serving programs
(``idle_named_share``, ``span_mean_ms``, ``program_mean_ms``) on hand-built
traces with exact answers, and on the recorded ``sample.xplane.pb``, whose
serving programs are still all ``jit_pure`` and which has no such span."""
import os

import pytest

import run
from lib import trace_reduce

SAMPLE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "sample.xplane.pb")
NEW = {"idle_named_share.serve": "idle_named_share",
       "join_stall_ms.serve": "span_mean_ms",
       "readout_ms.serve": "span_mean_ms",
       "deliver_ms.serve": "span_mean_ms",
       "decode_step_device_ms.serve": "program_mean_ms"}


def read(metric, trace):
    spec = run.load_json("metrics", metric)
    return run.load_module("readers", spec["reader"]).read(
        {"trace": trace}, spec["params"])


def built(ops=(), modules=(), spans=(), lo=100.0, hi=110.0):
    """A trace whose slice is [lo, hi]; the events are given in slice time."""
    at = lambda evs: [(n, s + lo, d) for n, s, d in evs]
    return trace_reduce.Trace({0: at(ops)}, {0: at(modules)}, at(spans),
                              lo, hi)


def test_idle_time_is_named_by_the_programs_spans_alone():
    # busy 0-1, 2-3, 5-6, 8-10: idle 1-2 (1 s), 3-5 (2 s), 6-8 (2 s)
    ops = [("fusion.1", 0.0, 1.0), ("fusion.2", 2.0, 1.0),
           ("copy.3", 5.0, 1.0), ("fusion.4", 8.0, 2.0)]
    spans = [("decode[step fill=0.50 b4]", 0.9, 0.4),
             ("decode[deliver fill=0.50 b4]", 1.3, 0.4),    # over 1.5
             ("bench[train_step]", 3.5, 1.0),               # over 4.0
             ("decode[ctl fill=0.50 b4]", 7.5, 0.2)]        # not over 7.0
    assert read("idle_named_share.serve", built(ops, spans=spans)) \
        == pytest.approx(100.0 * 1.0 / 5.0)
    # nested and repeated spans count a gap once
    spans += [("decode[join64 fill=0.50 b4]", 6.0, 2.0),
              ("decode[readout64 fill=0.50 b4]", 6.5, 1.0)]
    assert read("idle_named_share.serve", built(ops, spans=spans)) \
        == pytest.approx(100.0 * 3.0 / 5.0)
    # nothing to name where the device never waited, or never worked
    assert read("idle_named_share.serve",
                built([("fusion.1", 0.0, 10.0)], spans=spans)) is None
    assert read("idle_named_share.serve",
                trace_reduce.Trace({}, {}, [], 0.0, 1.0)) is None


def test_span_means_leave_out_what_straddles_the_slice():
    spans = [("decode[join64 fill=0.50 b4]", -0.010, 0.030),    # from before
             ("decode[join64 fill=0.50 b4]", 1.0, 0.020),
             ("decode[join256 fill=0.53 b4]", 2.0, 0.030),
             ("decode[join128 fill=0.53 b4]", 9.990, 0.040),    # past the end
             ("decode[readout64 fill=0.50 b4]", 1.010, 0.008),
             ("decode[deliver fill=0.50 b4]", 3.0, 0.001),
             ("decode[deliver fill=0.53 b4]", 4.0, 0.003),
             ("decode[delivery fill=0.53 b4]", 5.0, 0.5)]       # another kind
    trace = built(spans=spans)
    assert read("join_stall_ms.serve", trace) == pytest.approx(25.0)
    assert read("readout_ms.serve", trace) == pytest.approx(8.0)
    assert read("deliver_ms.serve", trace) == pytest.approx(2.0)
    bare = built(spans=[("decode[step fill=0.50 b4]", 1.0, 0.037)])
    for metric in ("join_stall_ms.serve", "readout_ms.serve",
                   "deliver_ms.serve"):
        assert read(metric, bare) is None


def test_a_program_is_found_by_its_name():
    modules = [("jit_pure_step_c1024(123)", -0.010, 0.030),     # from before
               ("jit_pure_step_c1024(123)", 1.0, 0.030),
               ("jit_pure_prefill_t64c1024(7)", 1.030, 0.500),
               ("jit_pure_step_c1024(123)", 2.0, 0.034),
               ("jit_pure_step_c1024(123)", 9.990, 0.030)]      # past the end
    assert read("decode_step_device_ms.serve", built(modules=modules)) \
        == pytest.approx(32.0)
    unnamed = [("jit_pure(123)", 1.0, 0.030), ("jit_pure(7)", 2.0, 0.5)]
    assert read("decode_step_device_ms.serve",
                built(modules=unnamed)) is None


@pytest.mark.parametrize("metric", sorted(NEW))
def test_the_new_metric_resolves_and_reads_nothing_without_a_trace(metric):
    spec = run.load_json("metrics", metric)
    assert spec["name"] == metric and spec["reader"] == NEW[metric]
    reader = run.load_module("readers", spec["reader"])
    assert reader.read({"trace": None}, spec["params"]) is None


def test_on_the_recorded_sample_no_program_carries_a_kinds_name():
    trace = trace_reduce.reduce_file(SAMPLE)
    assert read("decode_step_device_ms.serve", trace) is None
    for metric in ("join_stall_ms.serve", "readout_ms.serve",
                   "deliver_ms.serve"):
        assert read(metric, trace) is None
    # the same gaps as the breakdown's, named by the same rule
    by_kind = dict(trace.breakdown(top=1000)["idle_gaps"])
    named = sum(v for k, v in by_kind.items() if k.startswith("decode["))
    assert read("idle_named_share.serve", trace) == pytest.approx(
        100.0 * named / sum(by_kind.values()))
