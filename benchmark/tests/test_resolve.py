"""Every name resolves to a file, and ``BENCHMARK.json`` says what the files
say."""
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


@pytest.mark.parametrize("cell", names("workloads"))
def test_cell_resolves(cell):
    spec, config, metrics = run.resolve(cell, rehearsal=False)
    assert spec["name"] == cell and NAME.match(cell)
    run.load_module("drivers", spec["driver"])
    ref = run.load_module("reference", config["reference"])
    assert ref.param_specs(config["sizes"])
    for group in ("end_to_end", "per_layer"):
        assert metrics[group]
        for name, m in metrics[group]:
            assert m["name"] == name
            run.load_module("readers", m["reader"])
    assert "setup_s" in spec["end_to_end"] and len(spec["end_to_end"]) >= 2
    run.resolve(cell, rehearsal=True)


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


def test_benchmark_json_agrees_with_the_files():
    b = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
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
        for m in spec["end_to_end"]:
            assert name in e2e[m].get("workloads", [name])
        for m in spec["per_layer"]:
            # a metric without the key is due in every cell that reports
            # the end-to-end metric it moves
            assert name in per[m].get("workloads", [name])
            assert per[m]["moves"] in spec["end_to_end"]
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
            for w in m.get("workloads", []):
                spec = json.load(open(os.path.join(BENCH, "workloads",
                                                   w + ".json")))
                assert name in spec[group]
    # a whole-step share of the peak beside the kernels' rooflines
    for moved in {m["moves"] for n, m in per.items() if "roofline" in n}:
        assert any("mfu" in n and m["moves"] == moved
                   for n, m in per.items())
