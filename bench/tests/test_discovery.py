"""A later change adds a configuration, a mix, a cell and a per-layer
metric by adding files and entries: the harness finds them by name and no
file that was there changes."""
import hashlib
import json
import os
import shutil

from bench.check import COMPARED
from bench.scenarios import items
from bench.spec import load_cell


def _digests(top):
    out = {}
    for d, _, files in os.walk(top):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, top)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def test_new_files_are_found_by_name(tmp_path, root):
    copy = tmp_path / "checkout"
    shutil.copytree(os.path.join(root, "bench"), copy / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(root, "BENCHMARK.json"), copy)
    before = _digests(copy / "bench")

    config = json.load(open(copy / "bench/configs/paper-htl.json"))
    config["name"] = "paper-htl-a2a"
    config["skip_algos"] = ["edge_only", "star"]
    json.dump(config, open(copy / "bench/configs/paper-htl-a2a.json", "w"))
    json.dump({"driver": "closed", "why": "two callers",
               "params": {"compare": 1}},
              open(copy / "bench/traffic/closed-pair.json", "w"))
    limits = {"f1_mean_gap": 0.1, "energy_gap": 1e-8}
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
    after = _digests(copy / "bench")
    assert {k: v for k, v in after.items() if k in before} == before


def test_every_named_file_exists(root):
    bench = json.load(open(os.path.join(root, "BENCHMARK.json")))
    for w in bench["workloads"]:
        cell = load_cell(w["name"], root)
        cell.driver()
        cell.reference()
        for m in cell.per_layer:
            cell.metric_reader(m["name"])
        assert set(cell.limits) == set(COMPARED)
