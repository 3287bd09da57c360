"""A later change adds a configuration (with its own comparison, faults
and CPU size), a mix, a cell and a per-layer metric by adding files and
entries: the harness finds them by name and no file that was there
changes. A configuration that names no comparison or no faults is
refused."""
import hashlib
import json
import os
import shutil
from types import SimpleNamespace

import pytest

from bench import check
from bench.scenarios import items
from bench.spec import load_cell

MODEL_GAP = """
COMPARED = ("model_gap",)
PRINTED = ()


def answer(record):
    return {"model": [float(v) for v in record.model]}


def gaps(program, reference):
    return {"model": [abs(a - b) for a, b in zip(program["model"],
                                                 reference["model"])]}


def numbers(per_scenario):
    return {"model_gap": max(g for s in per_scenario for g in s["model"])}
"""

A2A_FAULTS = """
import contextlib

FAULTS = ("nothing",)


def plant(name):
    return contextlib.nullcontext()
"""


def _digests(top):
    out = {}
    for d, _, files in os.walk(top):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, top)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def _checkout(tmp_path, root):
    copy = tmp_path / "checkout"
    shutil.copytree(os.path.join(root, "bench"), copy / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(root, "BENCHMARK.json"), copy)
    return copy


def test_new_files_are_found_by_name(tmp_path, root, shrink):
    copy = _checkout(tmp_path, root)
    before = _digests(copy / "bench")

    config = json.load(open(copy / "bench/configs/paper-htl.json"))
    config["name"] = "paper-htl-a2a"
    config["skip_algos"] = ["edge_only", "star"]
    config["comparison"] = "model_gap"
    config["faults"] = "a2a"
    config["cpu"] = {"preset_args": {"windows": 2}}
    json.dump(config, open(copy / "bench/configs/paper-htl-a2a.json", "w"))
    (copy / "bench/comparisons/model_gap.py").write_text(MODEL_GAP)
    (copy / "bench/faults/a2a.py").write_text(A2A_FAULTS)
    json.dump({"driver": "closed", "why": "two callers",
               "params": {"compare": 1}},
              open(copy / "bench/traffic/closed-pair.json", "w"))
    limits = {"model_gap": 0.1}
    json.dump({"limits": limits, "params": {"trace_seconds": 2}},
              open(copy / "bench/cells/paper-htl-a2a.closed-pair.json",
                   "w"))
    (copy / "bench/metrics/dispatch_share.py").write_text(
        "def read(run):\n    return 42.0\n")

    bench = json.load(open(copy / "BENCHMARK.json"))
    bench["configs"].append({"name": "paper-htl-a2a", "source": "x",
                             "file": "bench/configs/paper-htl-a2a.json",
                             "reduced": [], "why": "A2AHTL rows only"})
    bench["workloads"].append({"name": "paper-htl-a2a.closed-pair",
                               "config": "paper-htl-a2a",
                               "traffic": "closed-pair", "chips": 1,
                               "why": "a new cell"})
    bench["per_layer"].append({"name": "dispatch_share", "unit": "%",
                               "better": "lower", "source": "device_trace",
                               "layer": "device", "moves": "windows_per_s",
                               "workloads": ["paper-htl-a2a.closed-pair"]})
    for m in bench["end_to_end"]:
        if m["name"] == "windows_per_s":
            m["workloads"].append("paper-htl-a2a.closed-pair")
    json.dump(bench, open(copy / "BENCHMARK.json", "w"))

    cell = load_cell("paper-htl-a2a.closed-pair", str(copy))
    assert cell.config["skip_algos"] == ["edge_only", "star"]
    assert cell.traffic["params"]["compare"] == 1
    assert cell.params == {"trace_seconds": 2}
    assert cell.limits == limits
    assert cell.driver().Driver.span == "bench.scenario"
    assert cell.reference().answer
    names = [m["name"] for m in cell.per_layer]
    assert "dispatch_share" in names
    assert cell.metric_reader("dispatch_share").read(None) == 42.0
    assert [m["name"] for m in cell.end_to_end] == ["windows_per_s",
                                                    "setup_s"]
    assert all(r.cfg.algo == "a2a" for r in items(cell.config).rows)

    comparison = cell.comparison()
    program = comparison.answer(SimpleNamespace(model=[1.0, 2.0]))
    reference = comparison.answer(SimpleNamespace(model=[1.0, 2.5]))
    values = comparison.numbers([comparison.gaps(program, reference)])
    assert check.judge(values, cell.limits, comparison.COMPARED) == {
        "model_gap": {"value": 0.5, "limit": 0.1}}
    assert cell.faults().FAULTS == ("nothing",)
    assert shrink(cell).config["preset_args"]["windows"] == 2
    assert cell.config["preset_args"]["engine"] == "scan"
    after = _digests(copy / "bench")
    assert {k: v for k, v in after.items() if k in before} == before


@pytest.mark.parametrize("key", ["comparison", "faults"])
def test_a_configuration_names_its_comparison_and_faults(key, tmp_path,
                                                          root):
    copy = _checkout(tmp_path, root)
    bench = json.load(open(copy / "BENCHMARK.json"))
    for c in bench["configs"]:
        path = copy / c["file"]
        config = json.load(open(path))
        del config[key]
        json.dump(config, open(path, "w"))
    for w in bench["workloads"]:
        with pytest.raises(KeyError, match=key):
            load_cell(w["name"], str(copy))


def test_every_named_file_exists(root):
    bench = json.load(open(os.path.join(root, "BENCHMARK.json")))
    for w in bench["workloads"]:
        cell = load_cell(w["name"], root)
        cell.driver()
        cell.reference()
        for m in cell.per_layer:
            cell.metric_reader(m["name"])
        comparison = cell.comparison()
        assert set(cell.limits) == set(comparison.COMPARED)
        assert not set(comparison.PRINTED) & set(comparison.COMPARED)
        assert cell.faults().FAULTS
        assert cell.config["cpu"]
