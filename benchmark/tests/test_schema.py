"""Every cell in BENCHMARK.json finds its configuration, traffic and
metric files by name, and the file keeps to the contract's shapes."""

import json
import os
import re

import pytest

from benchmark import observe, run

ROOT = run.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["benchmark"]
    assert 1 <= bench["run_seconds"] <= 51
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= max(
        1, len(bench["workloads"]) // 4)


def test_every_cell_finds_its_files(bench):
    configs = {c["name"]: c for c in bench["configs"]}
    used = set()
    for cell in bench["workloads"]:
        assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
        assert cell["chips"] in (1, 4) and 1 <= len(cell["why"]) <= 200
        _, got, config, traffic = run.load_cell(cell["name"])
        assert got is cell or got == cell
        used.add(cell["config"])
        entry = configs[cell["config"]]
        assert entry["file"].startswith("benchmark/")
        assert config["tpuanomaly"]["model_config"]["d_model"] > 0
        assert set(config["reduced"]) == set(entry["reduced"])
        assert traffic["schedule"] in ("closed", "open")
        for group in ("end_to_end", "per_layer"):
            for m in run.cell_metrics(bench, cell, group):
                if m["name"] != "setup_s":
                    assert callable(observe.load_reader(m["name"]))
        e2e = [m["name"] for m in run.cell_metrics(bench, cell, "end_to_end")]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert run.cell_metrics(bench, cell, "per_layer")
    assert used == set(configs)
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_metrics_keep_to_the_contract(bench):
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names))
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    assert e2e["setup_s"]["bound"] == 0.1
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert NAME.match(m["name"]) and m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert re.match(r"^[A-Za-z0-9_/%.\-]{1,16}$", m["unit"])
        moved = e2e[m["moves"]]
        for w in m.get("workloads", []):
            assert w in cells
            assert w in moved.get("workloads", cells)
    assert any("mfu" in m["name"] for m in bench["per_layer"])


def test_configurations_state_published_widths(bench):
    for c in bench["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        pub, mc = cfg["published"], cfg["tpuanomaly"]["model_config"]
        assert mc["d_model"] == pub["hidden_size"]
        assert mc["n_layers"] == pub["num_hidden_layers"]
        assert mc["n_heads"] == pub["num_attention_heads"]
        assert mc["d_ff"] == pub["intermediate_size"]
        assert cfg["assumed"] and "guarantees" in cfg
        assert cfg["correct"]["delivery_faults"] == 0
