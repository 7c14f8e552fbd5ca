"""The traffic generator: the schedule comes from the seed alone, every seed
carries the same sizes at the same arrival times, and the window holds the
rate the cell states."""
import json
import os

import numpy as np

from lib import loadgen

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = json.load(open(os.path.join(HERE, "..", "workloads",
                                   "gpt2-large.chat-decode.json")))


def sched(seed, window=36.0):
    return loadgen.schedule(CELL["traffic"], seed, 50257, window)


def test_same_seed_same_schedule():
    a, b = sched(7), sched(7)
    assert len(a) == len(b) > 100
    for x, y in zip(a, b):
        assert x["due_s"] == y["due_s"]
        assert x["max_new_tokens"] == y["max_new_tokens"]
        assert np.array_equal(x["prompt"], y["prompt"])


def test_seeds_share_sizes_and_arrivals():
    a, b = sched(1, 1e9), sched(3000000000, 1e9)
    assert [x["due_s"] for x in a] == [y["due_s"] for y in b]
    sizes = lambda s: [(len(x["prompt"]), x["max_new_tokens"]) for x in s]
    assert sizes(a) == sizes(b)
    assert not np.array_equal(a[0]["prompt"][:8], b[0]["prompt"][:8])


def test_the_window_holds_the_stated_rate():
    t = CELL["traffic"]
    rate, pre = t["rate_per_s"], t["preroll_s"]
    due = np.array([x["due_s"] for x in sched(5, 1e9)])
    assert np.all(np.diff(due) >= 0) and due[-1] < t["horizon_s"]
    assert int(np.sum(due < pre)) == int(rate * pre)
    for seconds in (17.0, 34.0, 51.0, 102.0):
        n = int(np.sum((due >= pre) & (due < pre + seconds)))
        assert abs(n - rate * seconds) < 1.0, (seconds, n)
    # inside a stretch the arrivals are as bursty as a Poisson process's
    gaps = np.diff(due[(due >= pre) & (due < pre + 51.0)])
    assert 0.7 < np.std(gaps) / np.mean(gaps) < 1.3


def test_sizes_follow_the_cell():
    t = CELL["traffic"]
    s = sched(11, 1e9)
    assert abs(len(s) - t["rate_per_s"] * t["horizon_s"]) < 1.0
    p = np.array([len(x["prompt"]) for x in s])
    a = np.array([x["max_new_tokens"] for x in s])
    assert p.min() >= t["prompt"]["min"] and p.max() <= t["prompt"]["max"]
    assert a.min() >= t["answer"]["min"] and a.max() <= t["answer"]["max"]
    assert abs(np.median(p) - t["prompt"]["median"]) < 15
    assert abs(np.median(a) - t["answer"]["median"]) < 20
    assert all(0 <= int(x["prompt"].max()) < 50257 for x in s[:20])


def test_train_batches_are_distinct_rows():
    tr = {"batch": 4, "seq": 16, "masked": 3}
    a = loadgen.train_batches(tr, 5, 100, 3)
    b = loadgen.train_batches(tr, 5, 100, 3)
    assert all(np.array_equal(x, y) for p, q in zip(a, b)
               for x, y in zip(p, q))
    rows = np.concatenate([batch[0] for batch in a])
    assert len({tuple(r) for r in rows}) == len(rows)
    assert a[0][3].shape == (4, 3) and a[0][3].max() < 16
