"""The trace reduction on a hand-made event list."""

import pytest

from benchmark import tracered
from benchmark.tracered import Line, Plane


def planes():
    dev0 = Plane("/device:TPU:0", [
        Line("XLA Modules", [("jit_step(1)", 1.0, 2.0), ("jit_step(1)", 4.0, 2.0)]),
        Line("XLA Ops", [("fusion.1", 1.0, 1.0), ("fusion.2", 2.0, 0.5),
                         ("fusion.1", 4.0, 1.5), ("copy.3", 5.0, 1.0)]),
    ])
    dev1 = Plane("/device:TPU:1", [
        Line("XLA Modules", [("jit_step(1)", 1.0, 1.0)]),
        Line("XLA Ops", [("fusion.1", 1.0, 1.0)]),
    ])
    host = Plane("/host:CPU", [Line("python", [("f", 0.0, 9.0)])])
    return [dev0, dev1, host]


def test_union_and_gaps():
    iv = [(1.0, 2.0), (1.5, 2.5), (4.0, 5.0)]
    assert tracered.union_s(iv, 0.0, 6.0) == pytest.approx(2.5)
    assert tracered.union_s(iv, 2.0, 4.5) == pytest.approx(1.0)
    g = tracered.gaps(iv, 0.0, 6.0)
    assert g[0] == (2.5, 4.0)
    assert sorted(g) == [(0.0, 1.0), (2.5, 4.0), (5.0, 6.0)]


def test_reduce_busy_idle_module_time():
    d = tracered.reduce(planes())
    # window: first device op (1.0) to last device op end (6.0)
    assert d.window_s == pytest.approx(5.0)
    assert d.busy_s == [pytest.approx(1.5 + 2.0), pytest.approx(1.0)]
    assert d.module_s == [pytest.approx(4.0), pytest.approx(1.0)]
    assert d.busy_mean_s == pytest.approx(2.25)
    assert d.idle_share_max == pytest.approx(1.0 - 1.0 / 5.0)
    # operations are summed by family: fusion.1 and fusion.2 are "fusion"
    assert d.top_ops[0] == ("fusion", pytest.approx(4.0))
    assert d.top_ops[1] == ("copy", pytest.approx(1.0))
    # idle gaps are those of the idlest device (dev1)
    assert d.idle_gaps[0] == (pytest.approx(2.0), pytest.approx(6.0))


def test_op_family_drops_the_instruction_text_and_serial():
    assert tracered.op_family(
        "%convert_reduce_fusion.16 = (f32[1024,64]{0,1}) fusion(...)") \
        == "convert_reduce_fusion"
    assert tracered.op_family("%custom-call.13 = x") == "custom-call"
    assert tracered.op_family("fusion") == "fusion"


def test_no_device_operation_reads_nothing():
    assert tracered.reduce([Plane("/host:CPU", [Line("x", [("f", 0, 1)])])]) \
        is None
    assert tracered.reduce([Plane("/device:TPU:0", [Line("XLA Ops", [])])]) \
        is None


def test_operations_without_an_executable_line_are_an_error():
    bare = Plane("/device:TPU:0", [Line("XLA Ops", [("fusion.1", 1.0, 1.0)])])
    with pytest.raises(ValueError, match="XLA Modules"):
        tracered.reduce([bare])


def test_host_cover_names_what_the_host_did_in_a_gap():
    host = Plane("/host:CPU", [
        Line("engine-pack", [("pack", 2.0, 0.4), ("dispatch", 2.6, 1.6)]),
        Line("MainThread", [("sleep", 0.0, 9.0)])])
    got = tracered.host_cover(planes()[:2] + [host],
                              [(2.5, 4.0), (6.0, 7.0), (2.0, 2.4)])
    assert got == ["engine-pack/dispatch", "MainThread/sleep",
                   "engine-pack/pack"]
    assert tracered.host_cover(planes()[:2], [(2.5, 4.0)]) \
        == ["no host event"]
