"""The joined reduction on hand-made planes, alone and fed through
``run.py``'s traced branch, and the pin that what was there reads as it
did."""

import copy
import os
import types

import pytest

from benchmark import architectures, hosttrace, observe, run, tracered
from benchmark.hosttrace import Event, Line, Plane

# an architecture's PARTS, made by hand: scope -> the part it folds into
FOLD = {"embed": "rest", "attn_mask": "rest", "attn": "attn", "mlp": "mlp",
        "final_norm": "rest", "head": "rest"}
# the quantities that read the joined trace, each in both splits
QUANTITIES = ("device_step_ms", "device_queue_ms", "fetch_ms",
              "device_idle_host", "step_attn_ms", "step_mlp_ms",
              "step_rest_ms")

OPS = {  # event name -> scope path, as the profiler's metadata has it
    "%fusion.1 = f(x)": "jit(f)/M/encoder/block_0/attn/LayerNorm_0/mul",
    "%fusion.2 = f(x)": "jit(f)/M/encoder/block_0/mlp/Dense_0/dot_general",
    "%copy.3 = c(x)": "",
    "%fusion.4 = f(x)": "jit(f)/M/encoder/embed/embed/name_embed/gather",
}


def ann(name, start, dur, **args):
    return Event(name, start, dur, args)


def planes(second_harvest=True):
    """Two calls. Call 0 (256 rows) is enqueued at 1.0-1.2, runs 1.5-3.5;
    call 1 (512 rows) is enqueued at 2.0-2.1 behind it, runs 3.6-6.6.
    Between the runs the chip idles 0.1 s with the worker in
    engine/scatter and engine/collect; after call 0's enqueue and before
    its run 0.3 s with the worker in engine/pack of call 1 (0.2 s)."""
    dev = Plane("/device:TPU:0", [
        Line("XLA Modules", [
            Event("jit_f(11)", 1.5, 2.0, {"run_id": 7}),
            Event("jit_f(22)", 3.6, 3.0, {"run_id": 8})]),
        Line("XLA Ops", [
            Event("%fusion.1 = f(x)", 1.5, 0.5),
            Event("%fusion.2 = f(x)", 2.0, 1.2),
            Event("%copy.3 = c(x)", 3.2, 0.3),
            Event("%fusion.1 = f(x)", 3.6, 1.0),
            Event("%fusion.2 = f(x)", 4.6, 1.5),
            Event("%fusion.4 = f(x)", 6.1, 0.5)]),
    ], op_names=dict(OPS))
    worker = [
        ann("engine/collect", 0.0, 0.9, queued=1),
        ann("engine/pack", 0.9, 0.1, call=0),
        ann("engine/enqueue", 1.0, 0.2, call=0, rows=256, spans=9000),
        ann("engine/collect", 1.2, 0.6, queued=2),
        ann("engine/pack", 1.8, 0.2, call=1),
        ann("engine/enqueue", 2.0, 0.1, call=1, rows=512, spans=20000),
        ann("engine/harvest", 2.1, 1.42, call=0),
        ann("engine/scatter", 3.52, 0.05, call=0),
        ann("engine/collect", 3.57, 0.05, queued=0)]
    if second_harvest:
        worker += [ann("engine/harvest", 3.62, 3.0, call=1),
                   ann("engine/scatter", 6.62, 0.08, call=1)]
    host = Plane("/host:CPU", [
        Line("odigos-engine", worker),
        Line("pjrt-tpu-tasks/9", [
            ann("DoEnqueueProgram", 1.15, 0.01, run_id=7),
            ann("DoEnqueueProgram", 2.08, 0.01, run_id=8)])])
    return [dev, host]


def test_calls_join_their_runs_in_order():
    got = hosttrace.calls(planes())
    assert [(c.serial, c.rows, c.spans) for c in got] \
        == [(0, 256, 9000), (1, 512, 20000)]
    assert [c.runs[0].args["run_id"] for c in got] == [7, 8]
    assert hosttrace.run_id_agreement(planes(), got) == 1.0


def test_reduce_reads_the_joined_times():
    ht = hosttrace.reduce(planes(), 10.0, FOLD)
    assert (ht.n_calls, ht.n_joined, ht.n_runs) == (2, 2, 2)
    assert ht.joined_share == 1.0 and ht.run_id_agree == 1.0
    assert ht.step_ms == pytest.approx(2500.0)
    # call 0: enqueue ends 1.2, run starts 1.5; call 1: 2.1 -> 3.6
    assert ht.queue_ms == pytest.approx((300.0 + 1500.0) / 2)
    # call 0: run ends 3.5, scatter ends 3.57; call 1: 6.6 -> 6.7
    assert ht.fetch_ms == pytest.approx((70.0 + 100.0) / 2)
    assert ht.runs_by_rows == {256: 1, 512: 1}


def test_idle_with_the_worker_working_and_waiting():
    """The one gap between the runs, 3.5-3.6: the worker is in
    engine/harvest until 3.52 and engine/scatter until 3.57 (0.07 s of
    work), then in engine/collect until 3.62 (0.03 s of the gap)."""
    ht = hosttrace.reduce(planes(), 10.0, FOLD)
    assert ht.idle_s == pytest.approx(0.1)
    assert ht.idle_host_s == pytest.approx(0.07)
    assert ht.idle_collect_s == pytest.approx(0.03)
    host = types.SimpleNamespace(host=ht)
    read = observe.load_reader("device_idle_host.backlog")
    assert read(host) == pytest.approx(0.7)       # percent of 10 s
    assert read(host) <= 100.0 * ht.idle_s / ht.window_s


def test_a_call_that_never_joins():
    """Call 1 is never harvested inside the trace: it joins no run, one
    of two calls joined is under the floor, and the joined metrics are
    not reported while the others are."""
    ht = hosttrace.reduce(planes(second_harvest=False), 10.0, FOLD)
    assert (ht.n_calls, ht.n_joined) == (2, 1)
    assert ht.joined_share == 0.5 < hosttrace.JOIN_FLOOR
    host = types.SimpleNamespace(host=ht)
    assert observe.load_reader("device_queue_ms.steady")(host) is None
    assert observe.load_reader("fetch_ms.steady")(host) is None
    assert observe.load_reader("device_step_ms.steady")(host) \
        == pytest.approx(2500.0)
    # the second run's operations belong to no rung
    assert ht.runs_by_rows == {256: 1, 0: 1}


def test_a_run_that_ends_after_the_harvest_is_not_the_calls():
    ps = planes()
    late = ps[0].lines[0].events[0]
    late.dur = 2.5                      # ends 4.0, the harvest at 3.52
    got = hosttrace.calls(ps)
    assert not got[0].joined


def test_parts_fold_by_scope_and_family():
    ht = hosttrace.reduce(planes(), 10.0, FOLD)
    assert ht.parts[(256, "attn", "fusion")] == pytest.approx(0.5)
    assert ht.parts[(512, "mlp", "fusion")] == pytest.approx(1.5)
    assert ht.parts[(256, "unscoped", "copy")] == pytest.approx(0.3)
    assert ht.parts[(512, "embed", "fusion")] == pytest.approx(0.5)
    host = types.SimpleNamespace(host=ht)
    attn = observe.load_reader("step_attn_ms.backlog")(host)
    mlp = observe.load_reader("step_mlp_ms.backlog")(host)
    rest = observe.load_reader("step_rest_ms.backlog")(host)
    assert (attn, mlp, rest) == (pytest.approx(750.0), pytest.approx(1350.0),
                                 pytest.approx(400.0))
    assert attn + mlp + rest == pytest.approx(ht.step_ms)
    assert ht.scoped_share == pytest.approx(4.7 / 5.0)
    text = "\n".join(hosttrace.table(ht))
    assert "mlp/fusion" in text and "unscoped/copy" in text
    assert "256-row runs: 1 runs" in text and "512-row runs" in text


def test_two_chips_read_a_run_as_one_chip_does():
    """The operations' seconds are summed over the chips and the runs
    counted per chip: a millisecond a run is a chip's."""
    ps = planes()
    second = copy.deepcopy(ps[0])
    second.name = "/device:TPU:1"
    one = hosttrace.reduce(ps, 10.0, FOLD)
    two = hosttrace.reduce([ps[0], second, ps[1]], 10.0, FOLD)
    assert (two.n_dev, two.n_runs, two.n_joined) == (2, 2, 2)
    assert two.step_ms == pytest.approx(one.step_ms)
    assert two.part_s("mlp") == pytest.approx(2 * one.part_s("mlp"))
    for part in ("attn", "mlp", "rest"):
        assert two.part_ms(part) == pytest.approx(one.part_ms(part))
    assert "all runs: 2 runs, 10.000 s of operations, 2500.00 ms a run" \
        in hosttrace.table(two)[0]


def test_a_scope_folds_into_the_part_the_architecture_says():
    """Another architecture, other scopes: what it folds into ``attn``
    reads under step_attn_ms, and a scope it does not name is unscoped
    and reads under ``rest``."""
    fold = {"attn": "attn", "embed": "attn"}
    ht = hosttrace.reduce(planes(), 10.0, fold)
    assert ht.parts[(512, "unscoped", "fusion")] == pytest.approx(1.5)
    assert ht.part_s("attn") == pytest.approx(0.5 + 1.0 + 0.5)
    assert ht.part_s("rest") == pytest.approx(1.2 + 0.3 + 1.5)
    assert ht.part_s("mlp") == 0.0


def test_part_is_the_first_scope_on_the_path_that_names_one():
    def part(path):
        return hosttrace.scope_of(path, FOLD)

    assert part("jit(f)/M/encoder/block_3/mlp/Dense_1/dot_general") == "mlp"
    assert part("jit(f)/M/encoder/embed/embed/pos_embed/take") == "embed"
    # an attention projection's own module is called "out"/"key": the
    # block's scope comes first on the path and wins
    assert part("jit(f)/M/encoder/block_0/attn/MHA_0/out/add") == "attn"
    assert part("jit(f)/head/logistic") == "head"
    assert part("jit(f)/M/encoder/final_norm/final_ln/mul") == "final_norm"
    assert part("") == part(None) == "unscoped"
    assert part("jit(f)/transpose") == "unscoped"


def test_a_program_without_the_annotations_reads_nothing():
    dev = planes()[0]
    assert hosttrace.reduce([dev], None, FOLD) is None
    bare = types.SimpleNamespace(host=None)
    for q in QUANTITIES:
        assert observe.load_reader(q + ".backlog")(bare) is None
        assert observe.load_reader(q + ".steady")(object()) is None


def test_the_wire_reader_finds_an_operations_scope():
    """A serialized XSpace with one device plane: two event metadata, one
    with a tf_op given as a string, one as a reference to a stat
    metadata's name."""
    def varint(n):
        out = b""
        while True:
            out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
            n >>= 7
            if not n:
                return out

    def ld(field, payload):          # length-delimited
        return varint(field << 3 | 2) + varint(len(payload)) + payload

    def vi(field, n):
        return varint(field << 3) + varint(n)

    def stat_meta(key, name):
        return ld(5, vi(1, key) + ld(2, vi(1, key) + ld(2, name)))

    def event_meta(key, name, stat):
        return ld(4, vi(1, key) + ld(2, vi(1, key) + ld(2, name)
                                     + ld(5, stat)))

    plane = (ld(2, b"/device:TPU:0")
             + ld(3, b"\x08\x01")      # a line: skipped
             + stat_meta(26, b"tf_op")
             + stat_meta(40, b"jit(f)/M/encoder/block_1/attn/add:")
             + event_meta(1, b"%fusion.1 = f(x)",
                          vi(1, 26) + ld(5, b"jit(f)/M/encoder/embed/add:"))
             + event_meta(2, b"%fusion.2 = f(x)", vi(1, 26) + vi(7, 40))
             + event_meta(3, b"%copy.3 = c(x)", vi(1, 99) + ld(5, b"x")))
    host = ld(2, b"/host:CPU") + stat_meta(26, b"tf_op")
    got = hosttrace.op_metadata(ld(1, plane) + ld(1, host))
    assert got == {"/device:TPU:0": {
        "%fusion.1 = f(x)": "jit(f)/M/encoder/embed/add",
        "%fusion.2 = f(x)": "jit(f)/M/encoder/block_1/attn/add"}}


def test_the_traced_branch_hands_the_trace_to_the_readers(
        rehearsal, stood_in_trace):
    """``run.run_cell`` with ``--trace 1`` at the rehearsal's size, the
    profiler's file stood in for by the hand-made records: the fourteen
    metrics' quantities read what ``reduce`` reads off them, through the
    configuration's own architecture's PARTS, and the line says how the
    calls joined."""
    for cell, split in (("vit-h14.backlog", "backlog"),
                        ("vit-h14.steady", "steady")):
        line = run.run_cell(cell, 31, 1.0, True, rehearse=rehearsal)
        assert line["correct"] is True
        got = {k: v["value"] for k, v in line["metrics"].items()}
        assert {f"{q}.{split}" for q in QUANTITIES} <= set(got)
        assert got[f"device_step_ms.{split}"] == pytest.approx(2500.0)
        assert got[f"device_queue_ms.{split}"] == pytest.approx(900.0)
        assert got[f"fetch_ms.{split}"] == pytest.approx(85.0)
        assert got[f"step_attn_ms.{split}"] == pytest.approx(750.0)
        assert got[f"step_mlp_ms.{split}"] == pytest.approx(1350.0)
        assert got[f"step_rest_ms.{split}"] == pytest.approx(400.0)
        # 0.07 s of the traced window, whose length is the run's own
        window_s = line["device"]["window_s"]
        assert got[f"device_idle_host.{split}"] \
            == pytest.approx(100.0 * 0.07 / window_s)
        assert f"padded_share.{split}" in got
        assert {m: line["metrics"][m]["unit"] for m in got} \
            == {m["name"]: m["unit"] for m in run.cell_metrics(
                run.load_json(os.path.join(run.ROOT, "BENCHMARK.json")),
                run.load_cell(cell)[1], "per_layer") if m["name"] in got}
        ht = line["hosttrace"]
        assert (ht["calls"], ht["joined"], ht["runs"]) == (2, 2, 2)
        assert ht["run_id_agree"] == 1.0
        assert set(ht["parts"]) == {"attn", "mlp", "rest"}
        assert ht["parts"]["mlp"]["flops_needed"] > 0
        assert ht["parts"]["mlp"]["flops_dispatched"] \
            >= ht["parts"]["mlp"]["flops_needed"]
        assert list(line)[-1] == "compared"


def test_a_ring_that_lapped_the_reader_leaves_the_padded_share_out(
        rehearsal, monkeypatch):
    """Spans the tracer's ring dropped before they were read make the
    collected calls a part of the window's: ``padded_share`` is left out
    of the line rather than read off them."""
    sound = run.ScoreSpans.finish

    def lapped(self):
        calls = sound(self)
        self.missed += 3
        return calls

    monkeypatch.setattr(run.ScoreSpans, "finish", lapped)
    line = run.run_cell("vit-h14.backlog", 32, 1.0, True, rehearse=rehearsal)
    assert line["correct"] is True
    assert "padded_share.backlog" not in line["metrics"]
    assert "ingest_ms.backlog" in line["metrics"]


# ------------------------------------------------- what was there, pinned


def test_the_readers_that_were_there_read_as_they_did():
    """Same planes and observation in, same values out: tracered.reduce,
    host_cover and the thirteen readers PR 23 brought are not this PR's
    to move. The expected values were taken on the tree as PR 23 left
    it."""
    from benchmark.tests.test_tracered import planes as old_planes

    d = tracered.reduce(old_planes())
    d.window_s = 6.0
    assert (d.busy_s, d.module_s) == ([3.5, 1.0], [4.0, 1.0])
    assert d.top_ops == [("fusion", 4.0), ("copy", 1.0)]
    assert d.idle_gaps == [(2.0, 6.0)]
    assert tracered.host_cover(old_planes(), d.idle_gaps) == ["python/f"]
    import numpy as np

    obs = observe.Observation(
        arch=architectures.load("encoder_preln"),
        model={"d_model": 8, "n_heads": 2, "n_layers": 3, "d_ff": 16,
               "max_len": 4},
        chips=1, device_kind="TPU v5 lite", deadline_ms=8000.0,
        window_s=2.0, scored_spans=1000,
        latency_ms=np.array([10.0, 20.0, 30.0, 40.0]),
        late_ms=np.array([1.0, 2.0, 3.0, 4.0]),
        stages={"admission": (4.0, 4), "decode": (8.0, 4),
                "submit": (2.0, 4), "featurize": (40.0, 4),
                "pack": (20.0, 4), "enqueue": (1.0, 4),
                "queue": (100.0, 4), "device": (400.0, 4),
                "harvest": (200.0, 4), "wait": (12.0, 4),
                "tag": (16.0, 4), "forward": (24.0, 4)},
        counters={}, score_calls=[(100, 4, 64), (28, 4, 64)],
        piece_lengths=[3, 1], device=d)
    want = {
        "delivered_spans_per_s.steady": 500.0,
        "device_idle.steady": 100.0 * (1.0 - 1.0 / 6.0),
        "device_wait_ms.backlog": 150.0,
        "featurize_pack_ms.steady": 15.0,
        "generator_late_p95_ms.steady": 3.85,
        "ingest_ms.backlog": 3.5,
        "latency_p50_ms.steady": 25.0,
        "latency_p95_ms": 38.5,
        "padded_share.backlog": 75.0,
        "queue_ms.steady": 25.25,
        "retire_ms.backlog": 13.0,
        "spans_per_s": 500.0,
        "step_mfu.backlog": 100.0 * 13504.0 / (5.0 * 197e12),
    }
    assert len(want) == 13
    for name, value in want.items():
        assert observe.load_reader(name)(obs) == pytest.approx(value), name
