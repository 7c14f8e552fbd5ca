"""Every name resolves to a file, ``BENCHMARK.json`` says what the files
say, and a cell's metrics are those ``BENCHMARK.json`` gives it."""
import copy
import glob
import json
import os
import re

import pytest

import run

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def names(kind):
    return sorted(os.path.basename(p)[:-5]
                  for p in glob.glob(os.path.join(BENCH, kind, "*.json")))


def benchmark_json():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("cell", names("workloads"))
def test_cell_resolves(cell):
    spec, config, metrics, counts = run.resolve(cell, rehearsal=False)
    assert spec["name"] == cell and NAME.match(cell)
    assert "end_to_end" not in spec and "per_layer" not in spec
    run.load_module("drivers", spec["driver"])
    ref = run.load_module("reference", config["reference"])
    assert ref.param_specs(config["sizes"])
    assert counts.__file__.endswith(
        os.path.join("counts", config["counts"] + ".py"))
    for group in ("end_to_end", "per_layer"):
        assert metrics[group]
        for name, m in metrics[group]:
            assert m["name"] == name
            run.load_module("readers", m["reader"])
    e2e = [name for name, _m in metrics["end_to_end"]]
    assert "setup_s" in e2e and len(e2e) >= 2
    run.resolve(cell, rehearsal=True)


def test_a_metric_reaches_a_cell_through_benchmark_json_alone():
    """An entry that lists the cell, or that has no list and moves an
    end-to-end metric the cell reports, is all a metric with a file needs;
    no file under ``workloads/`` knows a metric."""
    cell, other = "gpt2-large.chat-decode", "bert-large.pretrain-seq128"
    b = benchmark_json()
    reported = lambda bench, c=cell: [
        name for name, _m in run.resolve(c, False, bench)[2]["per_layer"]]
    per = {m["name"]: m for m in b["per_layer"]}
    # listed: reported; struck from the list: gone, and from no other cell
    assert "readout_ms.serve" in reported(b)
    less = copy.deepcopy(b)
    next(m for m in less["per_layer"] if m["name"] == "readout_ms.serve")[
        "workloads"].remove(cell)
    assert "readout_ms.serve" not in reported(less)
    assert reported(less) == [n for n in reported(b)
                              if n != "readout_ms.serve"]
    # a new entry for a metric file that is there, listing the cell
    more = copy.deepcopy(b)
    more["per_layer"] = [m for m in more["per_layer"]
                         if m["name"] != "deliver_ms.serve"]
    assert "deliver_ms.serve" not in reported(more)
    more["per_layer"].append(dict(per["deliver_ms.serve"],
                                  workloads=[cell]))
    assert reported(more)[-1] == "deliver_ms.serve"
    # without a list: due wherever the metric it moves is reported
    assert "workloads" not in per["step_mfu.serve"]
    assert "step_mfu.serve" in reported(b)
    assert "step_mfu.serve" not in reported(b, other)
    assert "step_mfu.train" in reported(b, other)
    # an entry whose metric has no file names the missing path
    more["per_layer"].append(dict(per["deliver_ms.serve"],
                                  name="no_such_metric.serve"))
    with pytest.raises(SystemExit) as e:
        run.resolve(cell, False, more)
    assert "benchmark/metrics/no_such_metric.serve.json" in str(e.value)


@pytest.mark.parametrize("metric", names("metrics"))
def test_metric_resolves(metric):
    m = json.load(open(os.path.join(BENCH, "metrics", metric + ".json")))
    assert m["name"] == metric and NAME.match(metric)
    assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    assert m["source"] in ("device_trace", "program_span", "program_counter",
                           "host_clock")
    assert hasattr(run.load_module("readers", m["reader"]), "read")
    if "moves" in m:
        assert os.path.isfile(os.path.join(BENCH, "metrics",
                                           m["moves"] + ".json"))
        assert m["layer"] and "\n" not in m["layer"]
    if "roofline" in metric or "mfu" in metric:
        assert m["unit"] == "%"


def test_a_missing_name_names_the_missing_path():
    with pytest.raises(SystemExit) as e:
        run.resolve("no-such.cell", rehearsal=False)
    assert "benchmark/workloads/no-such.cell.json" in str(e.value)
    with pytest.raises(SystemExit) as e:
        run.load_module("readers", "no_such_reader")
    assert "benchmark/readers/no_such_reader.py" in str(e.value)


def test_a_missing_counts_module_names_the_missing_path(monkeypatch):
    real = run.load_json

    def with_counts(value):
        def load(kind, name):
            found = dict(real(kind, name))
            if kind == "configs":
                found.pop("counts")
                found.update(value)
            return found
        return load

    monkeypatch.setattr(run, "load_json", with_counts({"counts": "no_such"}))
    with pytest.raises(SystemExit) as e:
        run.resolve("gpt2-large.chat-decode", rehearsal=False)
    assert "benchmark/counts/no_such.py" in str(e.value)
    monkeypatch.setattr(run, "load_json", with_counts({}))
    with pytest.raises(SystemExit) as e:
        run.resolve("gpt2-large.chat-decode", rehearsal=False)
    assert "names no module of counts" in str(e.value)


def test_benchmark_json_agrees_with_the_files():
    b = benchmark_json()
    assert sorted(b) == sorted(["command", "paths", "run_seconds", "configs",
                                "workloads", "end_to_end", "per_layer"])
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024
    configs = {c["name"]: c for c in b["configs"]}
    for c in configs.values():
        assert sorted(c) == ["file", "name", "reduced", "source", "why"]
        f = json.load(open(os.path.join(ROOT, c["file"])))
        assert f["source"] == c["source"] and f["name"] == c["name"]
        assert f["reduced"] == c["reduced"]
        assert len(c["why"]) <= 200 and all(NAME.match(k)
                                            for k in c["reduced"])
    e2e = {m["name"]: m for m in b["end_to_end"]}
    per = {m["name"]: m for m in b["per_layer"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    cells = {w["name"]: w for w in b["workloads"]}
    assert sum(w["chips"] == 4 for w in cells.values()) <= max(
        1, len(cells) // 4)
    for name, w in cells.items():
        assert sorted(w) == ["chips", "config", "name", "traffic", "why"]
        spec = json.load(open(os.path.join(BENCH, "workloads",
                                           name + ".json")))
        assert name == "%s.%s" % (w["config"], w["traffic"])
        assert (spec["config"], spec["chips"], spec["why"]) == (
            w["config"], w["chips"], w["why"])
        assert w["config"] in configs and len(w["why"]) <= 200
        due = run.cell_metrics(name, b)
        # every cell reports the set-up time, another end-to-end metric and
        # a per-layer metric; what a per-layer metric moves, the cell reports
        assert "setup_s" in due["end_to_end"] and len(due["end_to_end"]) >= 2
        assert due["per_layer"]
        for m in due["per_layer"]:
            assert per[m]["moves"] in due["end_to_end"]
    for group, table in (("end_to_end", e2e), ("per_layer", per)):
        for name, m in table.items():
            f = json.load(open(os.path.join(BENCH, "metrics",
                                            name + ".json")))
            for k in ("unit", "better", "source"):
                assert f[k] == m[k], (name, k)
            if group == "per_layer":
                assert (f["layer"], f["moves"]) == (m["layer"], m["moves"])
                assert m["moves"] in e2e and "bound" not in m
            else:
                assert 0.01 <= m["bound"] <= 0.1
                assert m["source"] in ("host_clock", "device_trace")
            assert all(w in cells for w in m.get("workloads", []))
    # a whole-step share of the peak beside the kernels' rooflines
    for moved in {m["moves"] for n, m in per.items() if "roofline" in n}:
        assert any("mfu" in n and m["moves"] == moved
                   for n, m in per.items())
