"""The program's stages and the model's parts on the profiler's clock
(ISSUE 25): ``selftelemetry.latency.annotate`` brackets each working
stage of the scored path where the work happens, the engine gives each
coalesced call a serial that the trace, the ``tpu/score`` span and the
frame's record share, the threads carry their role's name, the encoder's
parts are ``jax.named_scope``s that change nothing but names, and
``/debug/xlaz?trace_s=`` captures one trace at a time."""

from __future__ import annotations

import gc
import glob
import json
import os
import threading
import types
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from odigos_tpu.pdata import synthesize_traces
from odigos_tpu.pipeline.service import Collector
from odigos_tpu.selftelemetry import latency
from odigos_tpu.selftelemetry.latency import (
    ANNOTATIONS, Stage, StageClock, annotate, latency_ledger, name_thread)
from odigos_tpu.selftelemetry.tracer import tracer
from odigos_tpu.wire.client import WireExporter

from tests.test_ingest_fastpath import soak_config, wait_for

TINY = {"d_model": 64, "n_heads": 2, "n_layers": 2, "d_ff": 128,
        "max_len": 16}


def trace_events(trace_dir):
    """[(line, event name, start ns, {args})] of every host line."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name in ANNOTATIONS:
                    out.append((line.name, e.name, e.start_ns,
                                dict(e.stats)))
    return sorted(out, key=lambda r: r[2])


# ------------------------------------------------------------ the helper


class TestAnnotate:
    def test_stamps_on_the_way_out_and_not_past_an_exception(self):
        clock = StageClock()
        with annotate("lane/tag", clock, Stage.TAG):
            pass
        assert [s for s, _ in clock.stages] == ["tag"]
        with pytest.raises(KeyError):
            with annotate("lane/forward", clock, Stage.FORWARD):
                raise KeyError("x")
        assert [s for s, _ in clock.stages] == ["tag"]

    def test_close_ends_it_early_and_the_exit_is_then_a_no_op(self):
        with annotate("engine/pack", call=3) as packing:
            packing.close()
            packing.close()
        assert packing._tm is None

    def test_opens_nothing_in_a_process_without_jax(self, monkeypatch):
        monkeypatch.setattr(latency, "_trace_me", None)
        monkeypatch.setattr(latency, "sys",
                            types.SimpleNamespace(modules={}))
        clock = StageClock()
        with annotate("wire/decode", clock, Stage.DECODE) as a:
            assert a._tm is None
            a.set(queued=1)
        assert [s for s, _ in clock.stages] == ["decode"]

    def test_with_no_session_live_nothing_is_recorded(self, tmp_path):
        """An annotation closed before the session starts is an inactive
        TraceMe: it leaves no event. The one inside does."""
        assert not jax.profiler.TraceAnnotation.is_enabled()
        with annotate("engine/pack", call=990):
            pass
        jax.profiler.start_trace(str(tmp_path))
        try:
            with annotate("engine/pack", call=991) as a:
                a.set(queued=5)
        finally:
            jax.profiler.stop_trace()
        got = [args for _, name, _, args in trace_events(str(tmp_path))
               if name == "engine/pack"]
        assert [a["call"] for a in got] == [991]
        assert got[0]["queued"] == 5

    def test_a_thread_carries_its_roles_name_to_the_os(self):
        seen = {}

        def run():
            name_thread("odigos-lane-7")
            with open(f"/proc/self/task/{threading.get_native_id()}/comm") \
                    as f:
                seen["comm"] = f.read().strip()

        t = threading.Thread(target=run)
        t.start()
        t.join()
        assert seen["comm"] == "odigos-lane-7"


# ------------------------------------------------- the traced short window


# a collection runs on whichever thread allocated past a threshold, or
# on the janitor's: python/gc may show on any line
ROLE_OF = {"wire": "odigos-receiver", "fastpath": "odigos-submit-",
           "engine": "odigos-engine", "lane": "odigos-lane-", "python": ""}


@pytest.fixture()
def traced_window(tmp_path):
    """Six wire frames through a started Collector (the transformer on
    the host route, the ingest fast path) under one profiler session."""
    latency_ledger.reset()
    cfg = soak_config(fast_path=True, model="transformer",
                      deadline_ms=30_000)
    cfg["processors"]["tpuanomaly"].update(
        model_config=dict(TINY), trace_bucket=4, bucket_ladder=2,
        warm_ladder=True, max_len=16)
    collector = Collector(cfg).start()
    try:
        port = collector.graph.receivers["otlpwire"].port
        exp = WireExporter("t", {"endpoint": f"127.0.0.1:{port}"})
        exp.start()
        sink = collector.graph.exporters["tracedb"]
        tracer.ring.drain()
        jax.profiler.start_trace(str(tmp_path))
        try:
            want = 0
            for seed in range(6):
                b = synthesize_traces(3, seed=seed)
                exp.export(b)
                want += len(b)
                assert wait_for(lambda: sink.span_count == want)
            gc.collect()
        finally:
            jax.profiler.stop_trace()
        exp.shutdown()
        collector.drain_receivers(20.0)
        snap = latency_ledger.snapshot()["pipelines"]["traces/in"]
        spans = [s for s in tracer.ring.snapshot() if s.name == "tpu/score"]
        yield trace_events(str(tmp_path)), spans, snap["recent"]
    finally:
        collector.shutdown()
        latency_ledger.reset()


class TestTracedWindow:
    def test_every_annotation_shows_on_its_roles_line(self, traced_window):
        events, _, _ = traced_window
        names = {name for _, name, _, _ in events}
        assert names == set(ANNOTATIONS)
        for line, name, _, _ in events:
            role = ROLE_OF[name.split("/")[0]]
            assert line.startswith(role), (line, name)

    def test_the_calls_serial_joins_trace_span_and_frame(self,
                                                         traced_window):
        events, spans, frames = traced_window
        by_stage = {}
        for _, name, _, args in events:
            if name.startswith("engine/") and name != "engine/collect":
                by_stage.setdefault(name, []).append(int(args["call"]))
        serials = by_stage["engine/enqueue"]
        assert len(serials) >= 2
        assert serials == list(range(serials[0], serials[0] + len(serials)))
        for name in ("engine/pack", "engine/harvest", "engine/ready",
                     "engine/scatter"):
            assert by_stage[name] == serials, name
        assert [s.attrs["call.serial"] for s in spans] == serials
        # every frame rode one of the window's calls, in order
        rode = [f["call"] for f in frames if f["scored"]]
        assert rode and set(rode) <= set(serials) and rode == sorted(rode)
        enq = [a for _, n, _, a in events if n == "engine/enqueue"]
        assert all(a["rows"] in (4, 8) and a["spans"] > 0 for a in enq)
        collect = [a for _, n, _, a in events if n == "engine/collect"]
        # one request a frame; the collect that waited for the first may
        # have begun before the session did, and then left no event
        assert sum(a["queued"] for a in collect) in (5, 6)


    def test_the_wait_for_the_device_nests_inside_the_harvest(
            self, traced_window):
        """``engine/ready`` is the first part of each call's
        ``engine/harvest``, on the same line; the rest is the copy."""
        events, _, _ = traced_window
        by = {}
        for line, name, start, args in events:
            if name in ("engine/harvest", "engine/ready"):
                by.setdefault((name, int(args["call"])), []).append(
                    (line, start))
        harvests = {k[1]: v for k, v in by.items()
                    if k[0] == "engine/harvest"}
        assert harvests
        for call, [(line, start)] in harvests.items():
            [(ready_line, ready_start)] = by[("engine/ready", call)]
            assert ready_line == line and ready_start >= start

    def test_the_score_span_carries_the_split_and_the_learned_cost(
            self, traced_window):
        """``harvest.ready_ms`` and ``harvest.copy_ms`` are the fetch's
        two parts; ``call.alone_ms`` the wall the call had the device to
        itself and ``rung.cost_ms`` its rung's cost after this call's
        update: each rung's costs are the engine's EWMA of its calls'
        walls, in order."""
        from odigos_tpu.serving.engine import _ewma

        _, spans, _ = traced_window
        assert spans
        cost = {}
        for sp in spans:
            a = sp.attrs
            assert a["harvest.ready_ms"] >= 0.0
            assert a["harvest.copy_ms"] >= 0.0
            assert a["harvest.ready_ms"] + a["harvest.copy_ms"] \
                <= a["harvest_ms"] + 1e-3
            rung = int(a["device.shape"].split("x")[0])
            if a.get("bucket.hit") is not False:
                cost[rung] = _ewma(cost.get(rung), a["call.alone_ms"])
            assert a["rung.cost_ms"] == pytest.approx(cost[rung], abs=2e-3)


# ------------------------------------------------------- the model's parts


# a block kind's extra fields, and operations its lowered program names
KINDS = {
    "encoder": ({}, ("block_0/attn/", "block_1/mlp/Dense_1")),
    "decoder": ({"block": "decoder", "passes": 4, "rope_theta": 1e6},
                ("block_0/attn/q_proj", "block_1/mlp/down_proj",
                 "block_0/norm/attn_norm", "stack/norm/final_rms")),
}


class TestNamedScopes:
    def _model_and_args(self, kind="encoder"):
        from odigos_tpu.models.transformer import (TraceTransformer,
                                                   TransformerConfig)

        model = TraceTransformer(TransformerConfig(**TINY, **KINDS[kind][0]))
        variables = model.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        R, L = 4, TINY["max_len"]
        args = (jnp.asarray(rng.integers(0, 4, (R, L, 5)), jnp.int32),
                jnp.asarray(rng.normal(size=(R, L, 3)), jnp.float32),
                jnp.asarray(rng.integers(0, 3, (R, L)), jnp.int32),
                jnp.asarray(np.tile(np.arange(L), (R, 1)), jnp.int32))
        return model, variables, args

    @pytest.mark.parametrize("kind", list(KINDS))
    def test_scopes_change_names_and_nothing_else(self, monkeypatch, kind):
        import contextlib

        model, variables, args = self._model_and_args(kind)
        scores = np.asarray(model.score_packed(variables, *args))
        monkeypatch.setattr(jax, "named_scope",
                            lambda name: contextlib.nullcontext())
        bare, bare_vars, _ = self._model_and_args(kind)
        paths = jax.tree_util.tree_structure(variables)
        assert paths == jax.tree_util.tree_structure(bare_vars)
        for a, b in zip(jax.tree_util.tree_leaves(variables),
                        jax.tree_util.tree_leaves(bare_vars)):
            assert np.array_equal(np.asarray(a), np.asarray(b))
        bare_scores = np.asarray(bare.score_packed(bare_vars, *args))
        assert np.array_equal(scores, bare_scores)   # bit for bit

    @pytest.mark.parametrize("kind", list(KINDS))
    def test_the_lowered_text_names_the_parts(self, kind):
        from odigos_tpu.models.layers import BLOCK_PARTS, PARTS

        assert BLOCK_PARTS["encoder"] is PARTS
        model, variables, args = self._model_and_args(kind)
        text = model.score_packed.lower(variables, *args).as_text(
            debug_info=True)
        for part in BLOCK_PARTS[kind]:
            assert f"/{part}/" in text or f"/{part}\"" in text, part
        for named in KINDS[kind][1]:
            assert named in text, named

    @pytest.mark.parametrize("kind", list(KINDS))
    def test_the_mesh_plan_carries_them_through(self, kind):
        """The dp x tp plan jits the model's own traced body, so its
        sharded program names the same parts."""
        from odigos_tpu.models.layers import BLOCK_PARTS
        from odigos_tpu.parallel import compile_plan, make_mesh

        model, variables, args = self._model_and_args(kind)
        plan = compile_plan(model, make_mesh({"data": 2}))
        text = plan._packed_jit.lower(
            plan.place_variables(variables), *args).as_text(debug_info=True)
        for part in BLOCK_PARTS[kind]:
            assert f"/{part}/" in text or f"/{part}\"" in text, part

    def test_the_int8_scorer_carries_the_same_parts(self):
        from odigos_tpu.models.quantized import QuantizedTraceScorer

        model, variables, args = self._model_and_args()
        scorer = QuantizedTraceScorer(model, variables)
        text = scorer.score_packed.lower(*args).as_text(debug_info=True)
        for part in ("embed", "attn_mask", "attn", "mlp", "final_norm",
                     "head"):
            assert f"/{part}/" in text or f"/{part}\"" in text, part


# ---------------------------------------------------- /debug/xlaz?trace_s=


class TestXlazTraceCapture:
    @pytest.fixture()
    def zpages(self):
        cfg = soak_config(fast_path=True)
        cfg["extensions"] = {"zpages": {"port": 0}}
        cfg["service"]["extensions"] = ["zpages"]
        collector = Collector(cfg).start()
        try:
            yield collector.graph.extensions["zpages"].port
        finally:
            collector.shutdown()

    @staticmethod
    def get(port, query):
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/debug/xlaz{query}",
                    timeout=30) as r:
                return r.status, json.load(r)
        except urllib.error.HTTPError as e:
            return e.code, json.load(e)

    def test_captures_one_trace_and_answers_with_its_directory(self, zpages):
        jnp.zeros(1).block_until_ready()     # this process holds a backend
        code, body = self.get(zpages, "?trace_s=1")
        assert code == 200 and body["seconds"] == 1.0
        assert glob.glob(os.path.join(body["trace_dir"], "plugins",
                                      "profile", "*", "*.xplane.pb"))
        # without the query the page is the device plane it was
        code, body = self.get(zpages, "")
        assert code == 200 and "cost" in body and "compiles" in body

    def test_one_capture_at_a_time(self, zpages, tmp_path):
        jnp.zeros(1).block_until_ready()
        results = []
        first = threading.Thread(
            target=lambda: results.append(self.get(zpages, "?trace_s=2")))
        first.start()
        assert wait_for(jax.profiler.TraceAnnotation.is_enabled, timeout=10)
        code, body = self.get(zpages, "?trace_s=1")
        first.join()
        assert code == 409 and "already running" in body["error"]
        assert results[0][0] == 200
        # and not across anyone else's session either
        jax.profiler.start_trace(str(tmp_path))
        try:
            code, body = self.get(zpages, "?trace_s=1")
        finally:
            jax.profiler.stop_trace()
        assert code == 409 and "session is live" in body["error"]

    def test_refuses_what_is_no_window(self, zpages):
        assert self.get(zpages, "?trace_s=0")[0] == 400
        assert self.get(zpages, "?trace_s=31")[0] == 400
        assert self.get(zpages, "?trace_s=soon")[0] == 400
