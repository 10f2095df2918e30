"""BENCHMARK.json and the tiny manifest against the contract's rules, and
every name resolved to its file."""

import json
import os
import re

import pytest

from conftest import CHECKOUT, DATA

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}


@pytest.mark.parametrize("path", [os.path.join(CHECKOUT, "BENCHMARK.json"),
                                  os.path.join(DATA, "BENCHMARK.json")])
def test_manifest_rules(path):
    m = json.load(open(path))
    assert set(m) == KEYS
    assert os.path.getsize(path) < 64 * 1024
    assert 1 <= m["run_seconds"] <= 51
    cells = {w["name"] for w in m["workloads"]}
    configs = {c["name"] for c in m["configs"]}
    assert len(cells) == len(m["workloads"]) and len(configs) == len(m["configs"])
    four = sum(1 for w in m["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(cells) // 4)
    for c in m["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and os.path.isfile(os.path.join(CHECKOUT, c["file"]))
        assert all(NAME.match(k) for k in c["reduced"]) and len(c["reduced"]) <= 16
        assert any(w["config"] == c["name"] for w in m["workloads"])
    pairs = set()
    for w in m["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
    e2e = {e["name"]: e for e in m["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    names = set()
    for e in m["end_to_end"]:
        assert set(e) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert e["source"] in {"host_clock", "device_trace"}
        assert 0.01 <= e["bound"] <= 0.1
    for e in m["per_layer"]:
        assert set(e) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert e["source"] in SOURCES and e["moves"] in e2e
        for cell in e.get("workloads", []):
            assert cell in cells
            assert cell in e2e[e["moves"]].get("workloads", cells)
    for e in m["end_to_end"] + m["per_layer"]:
        assert NAME.match(e["name"]) and UNIT.match(e["unit"])
        assert e["better"] in ("lower", "higher")
        assert e["name"] not in names
        names.add(e["name"])
        assert set(e.get("workloads", cells)) <= cells
    for cell in cells:  # setup_s, another end-to-end metric, a per-layer one
        mine = lambda ms: [x for x in ms if cell in x.get("workloads", [cell])]
        assert len(mine(m["end_to_end"])) >= 2 and len(mine(m["per_layer"])) >= 1


def test_every_name_has_its_file():
    from benchmark import harness

    m = json.load(open(os.path.join(CHECKOUT, "BENCHMARK.json")))
    assert m["paths"] == ["benchmark"] and m["command"] == ["python3", "benchmark/run.py"]
    for w in m["workloads"]:
        cell = harness.load_cell(w["name"], os.path.join(CHECKOUT, "BENCHMARK.json"),
                                 harness.BENCH_DIR)
        assert os.path.isfile(os.path.join(harness.BENCH_DIR, "kinds",
                                           cell.workload["kind"] + ".py"))
        assert set(cell.workload["limits"]) and cell.workload["why"]
        for entry in cell.per_layer:
            spec = harness.load_json(os.path.join(
                harness.BENCH_DIR, "metrics", entry["name"] + ".json"))
            assert spec["source"] == entry["source"] and spec["moves"] == entry["moves"]
            assert os.path.isfile(os.path.join(harness.BENCH_DIR, "readers",
                                               spec["reader"] + ".py"))
    for c in m["configs"]:
        cfg = json.load(open(os.path.join(CHECKOUT, c["file"])))
        assert cfg["source"] == c["source"] and cfg["reduced"] == c["reduced"]
        widths = ("d_model", "d_state", "headdim", "expand", "attn_head_dim")
        assert not any(k in c["reduced"] for k in widths)
