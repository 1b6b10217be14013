"""The benchmark's own checks run on the CPU, in seconds:
``python -m pytest benchmark/``."""

import json
import os
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def _named_architectures() -> list[str]:
    """Every architecture that a configuration of BENCHMARK.json names."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    names = set()
    for entry in bench["configs"]:
        with open(os.path.join(ROOT, entry["file"])) as f:
            names.add(json.load(f).get("architecture"))
    return sorted(n for n in names if n)


ARCHITECTURES = _named_architectures()


def arch_case(name: str):
    """(the architecture's module, its tests' case file
    ``arch_cases/<name>.py``)."""
    from benchmark import architectures

    path = os.path.join(HERE, "arch_cases", name + ".py")
    assert os.path.isfile(path), \
        f"architecture {name!r} has no case file for the tests: {path}"
    return (architectures.load(name),
            architectures.load_file(path, "benchmark_arch_case_"))


@pytest.fixture()
def rehearsal():
    """``rehearsal.json``: the tiny size ``run.run_cell`` is driven at
    here."""
    with open(os.path.join(HERE, "rehearsal.json")) as f:
        r = json.load(f)
    r["settle_s"] = 4.0
    return r


@pytest.fixture()
def stood_in_trace(monkeypatch):
    """A traced rehearsal whose profiler file is stood in for by the
    hand-made records of ``test_tracered.py`` and ``test_hosttrace.py``
    (the CPU's own trace holds no device plane), on a device kind the
    table of peaks knows."""
    from benchmark import hosttrace, run, tracered
    from benchmark.tests import test_hosttrace, test_tracered

    monkeypatch.setattr(tracered, "load", lambda d: test_tracered.planes())
    monkeypatch.setattr(hosttrace, "load", lambda d: test_hosttrace.planes())
    gate = run.device_gate
    monkeypatch.setattr(run, "device_gate", lambda chips, rehearsal: {
        **gate(chips, rehearsal), "kind": "TPU v5 lite"})
