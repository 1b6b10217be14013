"""Every cell in BENCHMARK.json finds its configuration, traffic and
metric files by name, and the file keeps to the contract's shapes.

Every test here runs twice: on BENCHMARK.json as committed, and on a copy
with a second configuration's cell laid over it the way PERF.md section 4
says a ``model_config`` PR does it (entries added, its cell's name
appended to ``workloads`` lists, no file of the harness edited). What
holds of the committed file only by being the one it is today (how many
cells, entries or layers there are) is asserted of neither."""

import copy
import json
import os
import re

import pytest

from benchmark import observe, reference, run

ROOT = run.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
# a configuration file of the tests' own, in no entry of BENCHMARK.json
SECOND = "benchmark/tests/second_config.json"


def lay_a_second_cell_over(bench: dict) -> dict:
    """A copy of ``bench`` as the next ``model_config`` PR would leave it:
    one entry in ``configs``, one in ``workloads``, the cell's name
    appended to the ``workloads`` of every metric the backlog cell
    reports, a split of its own for a quantity that has a reader, and a
    metric of a layer no entry names yet."""
    b = copy.deepcopy(bench)
    b["configs"].append({
        "name": "second", "source": "a test's double", "file": SECOND,
        "reduced": ["num_hidden_layers"], "why": "a second configuration"})
    b["workloads"].append({
        "name": "second.backlog", "config": "second", "traffic": "backlog",
        "chips": 1, "why": "a second cell, added by entries alone"})
    for m in b["end_to_end"] + b["per_layer"]:
        if "vit-h14.backlog" in m.get("workloads", []):
            m["workloads"].append("second.backlog")
    for name, layer in (("device_step_ms.second", "model step"),
                        ("step_rest_ms.gate", "exit gate")):
        b["per_layer"].append({
            "name": name, "unit": "ms", "better": "lower",
            "source": "device_trace", "layer": layer,
            "moves": "spans_per_s", "workloads": ["second.backlog"]})
    return b


@pytest.fixture()
def second(monkeypatch):
    """BENCHMARK.json with the second cell laid over it, for everything
    that reads it through ``run.load_json``."""
    path, sound = os.path.join(ROOT, "BENCHMARK.json"), run.load_json
    laid = lay_a_second_cell_over(sound(path))
    monkeypatch.setattr(
        run, "load_json",
        lambda p: copy.deepcopy(laid) if p == path else sound(p))
    return laid


@pytest.fixture(params=["as_committed", "with_a_second_cell"])
def bench(request):
    if request.param == "as_committed":
        return run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    return request.getfixturevalue("second")


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
        assert int(config["tpuanomaly"]["model_config"]["max_len"]) > 0
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


# a published key and the model_config key that has to state its value
PUBLISHED_KEYS = {"hidden_size": "d_model", "num_hidden_layers": "n_layers",
                  "num_attention_heads": "n_heads",
                  "intermediate_size": "d_ff"}
# never cut: widths, and what the contract counts among them
WIDTHS = re.compile(r"hidden_size|intermediate_size|head_dim|"
                    r"num_attention_heads|num_key_value_heads|"
                    r"num_experts_per_tok|_dim$|_rank$")


def published_mismatches(cfg: dict) -> list[str]:
    """The published keys whose value ``model_config`` does not state,
    those listed in ``reduced`` left out."""
    pub, mc = cfg["published"], cfg["tpuanomaly"]["model_config"]
    return [k for k, ours in PUBLISHED_KEYS.items()
            if k in pub and k not in cfg["reduced"] and mc[ours] != pub[k]]


def test_configurations_state_published_widths(bench):
    for c in bench["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert set(PUBLISHED_KEYS) & set(cfg["published"])
        assert published_mismatches(cfg) == []
        assert not [k for k in cfg["reduced"] if WIDTHS.search(k)]
        assert cfg["assumed"] and "guarantees" in cfg
        assert cfg["correct"]["delivery_faults"] == 0


def test_a_key_in_reduced_may_differ_and_no_other(bench):
    """The guide's usual cut in depth: ``num_hidden_layers`` listed in
    ``reduced`` may differ from ``n_layers``; unlisted it may not, and a
    width may not whether listed or not."""
    entry = next(c for c in bench["configs"] if c["name"] == "vit-h14")
    with open(os.path.join(ROOT, entry["file"])) as f:
        cfg = json.load(f)
    cut = json.loads(json.dumps(cfg))
    cut["tpuanomaly"]["model_config"]["n_layers"] = 8
    assert published_mismatches(cut) == ["num_hidden_layers"]
    cut["reduced"] = ["num_hidden_layers"]
    assert published_mismatches(cut) == []
    cut["tpuanomaly"]["model_config"]["d_ff"] = 1024
    assert published_mismatches(cut) == ["intermediate_size"]
    assert WIDTHS.search("intermediate_size") and WIDTHS.search("head_dim")
    assert not WIDTHS.search("num_hidden_layers")


def test_every_configuration_names_an_architecture(bench):
    from benchmark import architectures

    for c in bench["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert NAME.match(cfg["architecture"])
        assert os.path.isfile(os.path.join(
            run.HERE, "architectures", cfg["architecture"] + ".py"))
        arch = run.load_architecture(cfg)
        assert all(hasattr(arch, n) for n in architectures.EXPORTS)
        assert "::" in arch.__doc__            # the equations, set apart
        # a precision below the reference's own. That ``scores`` computes
        # it is ``test_reference.py``'s to show, for every architecture
        # named; the shared product knows ``reference.PRECISIONS`` and an
        # architecture with another control brings its own
        assert isinstance(arch.CONTROL, str) and arch.CONTROL
        assert arch.CONTROL != reference.PRECISIONS[0] == "float32"
        assert isinstance(arch.PARTS, dict) and arch.PARTS
        by = arch.flops_by_part(cfg["tpuanomaly"]["model_config"], [3, 1])
        assert set(arch.PARTS.values()) <= set(by)


# the quantities read off the joined trace (hosttrace.reduce through
# run.py's traced branch): name -> (unit, layer)
JOINED = {"device_step_ms": ("ms", "model step"),
          "step_attn_ms": ("ms", "model step"),
          "step_mlp_ms": ("ms", "model step"),
          "step_rest_ms": ("ms", "model step"),
          "device_queue_ms": ("ms", "scoring engine"),
          "fetch_ms": ("ms", "scoring engine"),
          "device_idle_host": ("%", "device")}


def test_the_joined_quantities_are_entries_with_readers(bench):
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for split, moves in (("backlog", "spans_per_s"),
                         ("steady", "latency_p95_ms")):
        for quantity, (unit, layer) in JOINED.items():
            m = by_name[f"{quantity}.{split}"]
            assert (m["unit"], m["layer"], m["moves"]) == (unit, layer, moves)
            assert (m["source"], m["better"]) == ("device_trace", "lower")
            # a later cell appends its name; the accepted one stays
            assert f"vit-h14.{split}" in m["workloads"]
            read = observe.load_reader(m["name"])
            assert callable(read) and read(object()) is None
    assert sum(m["name"].rpartition(".")[0] in JOINED
               for m in bench["per_layer"]) >= 14
    # a layer's name is one string, letter for letter
    layers = {m["layer"] for m in bench["per_layer"]}
    assert {"model step", "scoring engine", "device"} <= layers


def test_a_step_share_stands_beside_the_parts(bench):
    """The whole step's share of the peak moves what the parts of the
    step move, so that a part taken off the path cannot hide."""
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert by_name["step_mfu.backlog"]["moves"] \
        == by_name["step_attn_ms.backlog"]["moves"]


def test_a_cell_added_by_entries_alone_runs(second, rehearsal,
                                            stood_in_trace):
    """The second cell through ``run.run_cell`` at the rehearsal's size,
    traced: it is judged correct by the architecture its own file names
    and its line carries the metrics whose ``workloads`` name it, the
    accepted cell's quantities and the entries of its own alike."""
    line = run.run_cell("second.backlog", 51, 1.0, True, rehearse=rehearsal)
    assert line["correct"] is True and line["failed"] == 0
    got = set(line["metrics"])
    assert {"device_step_ms.second", "step_rest_ms.gate",
            "device_step_ms.backlog", "step_mfu.backlog",
            "padded_share.backlog", "queue_ms.backlog"} <= got
    assert not [m for m in got if m.endswith(".steady")]
    cell = next(w for w in second["workloads"]
                if w["name"] == "second.backlog")
    assert [m["name"] for m in run.cell_metrics(second, cell, "end_to_end")] \
        == ["spans_per_s", "setup_s"]
    # the accepted cells report what they reported
    for w in second["workloads"][:2]:
        names = {m["name"] for m in run.cell_metrics(second, w, "per_layer")}
        assert not [n for n in names if n.endswith((".second", ".gate"))]
