"""The readers of the decode loop's ``decode[wait]`` and ``decode[copy<tp>]``
spans (``tick_host_ms.serve``, ``readout_copy_ms.serve``) on hand-built
traces with exact answers."""
import pytest

import run
from lib import trace_reduce

NEW = {"tick_host_ms.serve": "span_less_ms",
       "readout_copy_ms.serve": "span_mean_ms"}


def read(metric, trace):
    spec = run.load_json("metrics", metric)
    return run.load_module("readers", spec["reader"]).read(
        {"trace": trace}, spec["params"])


def built(spans, lo=100.0, hi=110.0):
    """A trace whose slice is [lo, hi]; the spans are given in slice time."""
    return trace_reduce.Trace({0: []}, {0: []},
                              [(n, s + lo, d) for n, s, d in spans], lo, hi)


def tick(at, length, waits, join=None):
    """A busy tick's spans: the tick, its step with one wait a step, and a
    join where given (start, length)."""
    spans = [("decode[tick fill=0.50 b4]", at, length)]
    for s, d in waits:
        spans += [("decode[step fill=0.50 b4 kvread=0.500 ahead=1]",
                   s - 0.0005, d + 0.0005),
                  ("decode[wait fill=0.50 b4]", s, d)]
    if join is not None:
        spans += [("decode[join64 fill=0.50 b4]", *join),
                  ("decode[prefill64 fill=0.50 b4]", join[0], join[1] / 2)]
    return spans


def test_the_host_part_of_a_tick_is_the_tick_less_its_waits():
    spans = (tick(1.0, 0.004, [(1.002, 0.0015)])           # 2.5 ms
             + tick(2.0, 0.003, [(2.001, 0.001)])          # 2.0 ms
             + tick(3.0, 0.050, [(3.045, 0.002)],          # holds a join
                    join=(3.001, 0.040))
             + tick(-0.001, 0.004, [(0.001, 0.001)])       # from before
             + tick(9.998, 0.004, [(9.999, 0.001)])        # past the end
             + [("decode[tick fill=0.50 b4]", 4.0, 0.003),  # reads nothing
                ("decode[waiting fill=0.50 b4]", 4.001, 0.001),
                ("decode[gc0]", 4.002, 0.0005)])
    assert read("tick_host_ms.serve", built(spans)) == pytest.approx(2.25)
    # a wait on another span's time is not the tick's
    spans += [("decode[wait fill=0.50 b4]", 1.0045, 0.001)]
    assert read("tick_host_ms.serve", built(spans)) == pytest.approx(2.25)


def test_a_tick_may_hold_several_waits_and_a_chunk_is_skipped():
    spans = tick(1.0, 0.010, [(1.002, 0.002), (1.006, 0.003)])
    spans += tick(2.0, 0.010, [(2.002, 0.002)])
    spans += [("decode[chunk64 fill=0.50 b4]", 2.005, 0.004)]
    assert read("tick_host_ms.serve", built(spans)) == pytest.approx(5.0)


def test_the_copy_is_the_mean_of_the_copy_spans_inside_the_slice():
    spans = [("decode[readout64 fill=0.50 b4]", 1.0, 0.012),
             ("decode[copy64 fill=0.50 b4]", 1.004, 0.008),
             ("decode[readout1024 fill=0.50 b4]", 2.0, 0.050),
             ("decode[copy1024 fill=0.50 b4]", 2.010, 0.040),
             ("decode[copy64 fill=0.50 b4]", 9.995, 0.010)]   # past the end
    assert read("readout_copy_ms.serve", built(spans)) == pytest.approx(24.0)


def test_nothing_is_read_without_the_spans():
    """The parent program writes no ``wait`` and no ``copy``: both metrics
    leave the line, and do not raise."""
    spans = [("decode[tick fill=0.50 b4]", 1.0, 0.004),
             ("decode[step fill=0.50 b4 kvread=0.500 ahead=1]", 1.001, 0.002),
             ("decode[readout64 fill=0.50 b4]", 2.0, 0.012)]
    for metric in NEW:
        assert read(metric, built(spans)) is None
        assert read(metric, built([])) is None


@pytest.mark.parametrize("metric", sorted(NEW))
def test_the_new_metric_resolves_by_name(metric):
    spec = run.load_json("metrics", metric)
    assert spec["name"] == metric and spec["reader"] == NEW[metric]
    reader = run.load_module("readers", spec["reader"])
    assert reader.read({"trace": None}, spec["params"]) is None
