"""The control of each cell comes out as not correct.

The control is the plain reference, put in the program's place and computed
in the nearest precision below the one the configuration states (the cell's
file names it under ``control``). It was read on the chip at the cell's own
size, which set the limits (PERF.md); here it is kept at the tiny size a
test run can hold, against the tiny size's own limits, beside the sound run
of the same seed. The seed is pinned: the tiny decoder has few near-ties
among its logits, so that over 6 seeds its served tokens read a mean gap of
0.5e-6 to 3.0e-6 and int8's tokens 1.2e-6 to 8.0e-6 (4,300 tokens each), and
only some seeds separate; at the cell's own size every seed does.
"""
import glob
import json
import os

import pytest

import run

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELLS = sorted(
    (os.path.basename(p)[:-5], json.load(open(p)).get("control"))
    for p in glob.glob(os.path.join(BENCH, "workloads", "*.json")))


@pytest.mark.parametrize("cell,control", CELLS)
def test_control_is_not_correct(capsys, cell, control):
    assert control, "cell %s names no control" % cell
    seconds = "1" if "train" in json.load(open(os.path.join(
        BENCH, "workloads", cell + ".json")))["driver"] else "8"
    argv = ["--workload", cell, "--seed", "4242", "--seconds", seconds,
            "--trace", "0", "--cpu-rehearsal"]
    assert run.main(argv) == 0
    sound = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert sound["correct"] is True
    assert run.main(argv + ["--control", control]) == 0
    low = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert low["correct"] is False
    failed = [k for k, v in low["compared"].items()
              if not v["value"] <= v["limit"]]
    assert failed
