"""The trace reduction on the small recorded trace ``sample.xplane.pb``
(one TPU v5e chip, ``make_sample_trace.py``: three steps of a two-layer BERT
and a dozen requests against a two-layer GPT, cut down to the device's module
and op lines and the host's decode[...] / bench[...] spans)."""
import os

import pytest

from lib import trace_reduce

SAMPLE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "sample.xplane.pb")
DECODE_STEP = r"^jit_pure\(2996622927838797104\)"
LONG_PREFILL = r"^jit_pure\(7752204452423745631\)"


@pytest.fixture(scope="module")
def trace():
    return trace_reduce.reduce_file(SAMPLE)


def test_union_and_gaps_arithmetic():
    iv = [(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (3.2, 3.4)]
    assert trace_reduce.union_length(iv) == pytest.approx(3.0)
    assert trace_reduce.gaps(iv, 0.0, 5.0) == [(2.0, 3.0), (4.0, 5.0)]
    assert trace_reduce.gaps(iv, 0.25, 3.5) == [(2.0, 3.0)]
    assert trace_reduce.span_kind("decode[step fill=0.75 b32]") == "decode[step]"
    assert trace_reduce.span_kind("decode[prefill256 fill=0.1 b4]") \
        == "decode[prefill256]"


def test_busy_is_the_union_of_op_intervals(trace):
    ops = trace.ops[0]
    assert len(ops) > 5000
    plain = sum(d for _n, _s, d in ops)
    assert 0.0 < trace.busy_s <= trace.window_s
    # nested and overlapping events may not be counted twice
    assert trace.busy_s <= plain + 1e-9
    # three 0.41 ms train steps, ten 0.089 ms decode steps, two 0.135 ms
    # long prefills and the small programs: between 2 and 3 ms busy
    assert 0.002 < trace.busy_s < 0.003
    assert 0.98 < trace.idle_share() < 0.99


def test_kernel_time_by_name(trace):
    spent, calls = trace.op_time(r"^layernorm_fwd")
    # 3 train steps x 6 calls, 10 decode steps x 5, 5 prefills x 5
    assert calls == 3 * 6 + 10 * 5 + 5 * 5
    assert 20e-6 < spent < 60e-6
    assert trace.op_time(r"softmax_xent_fwd")[1] == 3
    assert trace.op_time(r"^flash_fwd")[1] == 2 * 2      # 2 prefills x 2 layers
    assert trace.op_time(r"^no_such_kernel") == (0.0, 0)


def test_program_runs(trace):
    steps = trace.module_runs(r"^jit_step")
    assert len(steps) == 3
    assert all(0.40e-3 < d < 0.42e-3 for _s, d in steps)
    # the sample's serving programs are all ``jit_pure`` and differ in the
    # fingerprint behind the name: the decode step's ten runs, and the two
    # prefills of the 1024 bucket
    decode = trace.module_runs(DECODE_STEP)
    assert len(decode) == 10
    assert all(88e-6 < d < 90e-6 for _s, d in decode)
    long_prefill = trace.module_runs(LONG_PREFILL)
    assert [round(d * 1e6) for _s, d in long_prefill] == [135, 135]


def test_gap_attribution(trace):
    b = trace.breakdown()
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) <= 10
    gaps = dict(b["idle_gaps"])
    # the device waits longest with no span of the program open at all
    assert b["idle_gaps"][0][0] == "between spans"
    assert "decode[step]" in gaps and "decode[prefill16]" in gaps
    total = sum(gaps.values())
    assert total == pytest.approx(trace.window_s - trace.busy_s, rel=0.02)


def test_readers_on_the_sample(trace):
    import run

    record = {"trace": trace, "device_kind": "TPU v5 lite", "chips": 1,
              "counts": run.load_module("counts", "bert"), "samples": {},
              "sizes": {"num_layers": 2, "units": 256, "hidden_size": 1024,
                        "vocab_size": 1024},
              "traffic": {"batch": 8, "seq": 128, "masked": 8}}
    read = lambda reader, **params: run.load_module(
        "readers", reader).read(record, params)
    share = read("kernel_step_share", kernel="layernorm_fwd",
                 program="^jit_step")
    assert 1.0 < share < 3.0         # 18 calls of ~1.2 us in 3 x 410 us
    assert read("kernel_step_share", kernel="flash_fwd",
                program="^jit_step") is None
    # 3 calls of 0.289 us on 64 rows x 1024 bf16 logits: 131 KB at 819 GB/s
    # would take 0.160 us
    roof = read("kernel_roofline", kernel="softmax_xent_fwd",
                program="^jit_step", bytes="softmax_xent_fwd_bytes")
    assert roof == pytest.approx(55.4, abs=1.0)
    assert read("kernel_roofline", kernel="flash_fwd", program="^jit_step",
                bytes="softmax_xent_fwd_bytes") is None
    # 3 steps x 8 samples x 1.32 GFLOP over the 4.2 ms they span
    mfu = read("train_step_mfu", program="^jit_step")
    assert mfu == pytest.approx(3.8, abs=0.1)
    assert read("device_idle_share") == pytest.approx(
        100.0 * trace.idle_share())
    assert read("span_fill", span=r"^decode\[step ") == pytest.approx(
        100.0 * 7 / 9)     # five full steps and four of the five half-full ones
    assert read("device_idle_share") > 0 and run.load_module(
        "readers", "device_idle_share").read({"trace": None}, {}) is None


def test_serving_readers_on_the_sample(trace):
    import run

    # the sample's server: two layers of width 256; six tokens were decoded
    # in the slice, at these contexts
    record = {"trace": trace, "device_kind": "TPU v5 lite", "chips": 1,
              "counts": run.load_module("counts", "gpt"), "traffic": {},
              "sizes": {"num_layers": 2, "units": 256, "hidden": 1024,
                        "vocab_size": 1024},
              "samples": {"slice_prefill_len": [700, 900],
                          "slice_decode_context": [13, 17, 701, 11, 901, 15]}}
    read = lambda reader, **params: run.load_module(
        "readers", reader).read(record, params)
    # ten decode steps of 88-89 us: the weights (2.1 MB a step) and the K/V
    # of six tokens at their contexts over 819 GB/s
    weights = 2 * (2 * (12 * 256 * 256 + 13 * 256) + 2 * 256 + 1024 * 256)
    need = 10 * weights + 2 * 2 * 256 * 2 * (13 + 17 + 701 + 11 + 901 + 15)
    assert read("decode_step_roofline", program=DECODE_STEP) \
        == pytest.approx(100.0 * need / 819e9 / sum(
            d for _s, d in trace.module_runs(DECODE_STEP)))
    assert read("decode_step_roofline", program="^jit_pure_step_") is None
    # a kernel held to bytes that follow the traffic (``each``): one column
    # of K and V a token decoded, six of them, whatever the ten steps moved
    # (the sample predates the write kernel: any kernel of the step stands in)
    column = dict(kernel="layernorm_fwd", program=DECODE_STEP,
                  bytes="kv_cache_write_bytes")
    spent = trace.op_time_within("layernorm_fwd",
                                 trace.module_runs(DECODE_STEP))
    assert spent > 0
    assert read("kernel_roofline", each="slice_decode_context", **column) \
        == pytest.approx(100.0 * 6 * (2 * 2 * 256 * 2) / 819e9 / spent)
    assert read("kernel_roofline", **column) == pytest.approx(
        100.0 * 10 * (2 * 2 * 256 * 2) / 819e9 / spent)
    # silent where the slice decoded nothing
    assert read("kernel_roofline", each="none_recorded", **column) is None
