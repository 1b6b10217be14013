"""Fused device-side featurize→pack→score (ISSUE 19 tentpole).

The fused route hands the engine a decoded frame's raw column views and
one jitted XLA call does hashing, the parent self-join, feature
assembly, next-fit packing, and the model forward — host featurize+pack
collapse into a single device call. These tests pin the contract:

* the columns twin (``featurize_columns`` / ``featurize_columns_jax``)
  matches the numpy featurizer — bitwise on the host twin, within the
  documented f32 duration bound on device;
* ``dispatch_columns`` parity vs the host dispatch/harvest route on
  every sequence backend (transformer / autoencoder / quantized),
  pinned for truncated, orphan-parent, and multi-frame coalesced
  groups;
* the fallback ladder: legacy JSON-attr frames, attr-slot configs,
  zero-span frames, and misaligned columns silently take the host
  route with the reason counted — a mixed fused/fallback storm loses
  nothing;
* the ``fused`` knob is opt-in, hot-reloads as RECONFIGURE (never
  FULL), and the ``ODIGOS_FUSED=0`` kill switch falls back per frame;
* predictive shed stays correct on the fused route: the burn table
  prices the ``fused`` stage (featurize/pack are absent) and overload
  still sheds ``blame=predicted`` before decode.
"""

import socket
import time
from dataclasses import replace

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from odigos_tpu.features import FeaturizerConfig, featurize  # noqa: E402
from odigos_tpu.features.featurizer import (  # noqa: E402
    SpanFeatures, batch_columns, featurize_columns, featurize_columns_jax)
from odigos_tpu.models import TransformerConfig  # noqa: E402
from odigos_tpu.models.autoencoder import AutoencoderConfig  # noqa: E402
from odigos_tpu.pdata import concat_batches, synthesize_traces  # noqa: E402
from odigos_tpu.pipeline.configdiff import (  # noqa: E402
    INCREMENTAL, RECONFIGURE, diff_configs)
from odigos_tpu.pipeline.service import Collector  # noqa: E402
from odigos_tpu.selftelemetry.flow import flow_ledger  # noqa: E402
from odigos_tpu.selftelemetry.latency import (  # noqa: E402
    Stage, latency_ledger)
from odigos_tpu.serving import EngineConfig, ScoringEngine  # noqa: E402
from odigos_tpu.serving.fastpath import (  # noqa: E402
    FUSED_FALLBACK_METRIC, FUSED_FRAMES_METRIC, SCORE_ATTR, IngestFastPath)
from odigos_tpu.serving.fused import (  # noqa: E402
    FALLBACK_REASONS, PARITY_BF16, PARITY_F32, PARITY_INT8, _device_tables,
    _split_u64, extract_columns, fused_enabled, routes_agree,
    served_precision)
from odigos_tpu.utils.telemetry import labeled_key, meter  # noqa: E402
from odigos_tpu.wire.codec import decode_frame, encode_batch, frame  # noqa: E402
from odigos_tpu.wire.server import REJECTED  # noqa: E402

# the documented parity bound (docs/architecture.md "Device-resident
# featurize"): the device twin computes log1p(duration_us) in f32 from
# split-clock borrow arithmetic where the host uses f64 intermediates —
# a few ULP on the continuous features, which the float32 model forward
# cannot amplify past ~1e-5 relative on scores
FUSED_RTOL = 2e-5
FUSED_ATOL = 1e-6

TINY_TF = TransformerConfig(d_model=32, n_heads=2, n_layers=1, d_ff=64,
                            max_len=16, dtype=jnp.float32)
TINY_AE = AutoencoderConfig(d_model=32, n_heads=2, n_layers=1, d_ff=64,
                            max_len=16, dtype=jnp.float32,
                            service_vocab=64, name_vocab=64)


def tf_cfg(**kw) -> EngineConfig:
    base = dict(model="transformer", model_config=TINY_TF, max_len=16,
                trace_bucket=8, bucket_ladder=2)
    base.update(kw)
    return EngineConfig(**base)


def ae_cfg(**kw) -> EngineConfig:
    base = dict(model="autoencoder", model_config=TINY_AE, max_len=16,
                trace_bucket=8, bucket_ladder=2)
    base.update(kw)
    return EngineConfig(**base)


def wait_for(cond, timeout=30.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if cond():
            return True
        time.sleep(0.01)
    return False


def legacy_batch(n_traces=8, seed=0):
    """A decoded legacy-wire frame: JSON span attrs, tuple-of-dicts
    ``span_attrs`` — the shape the fused route must refuse."""
    raw = encode_batch(synthesize_traces(n_traces, seed=seed),
                       attr_format="json")
    batch, _tp = decode_frame(raw)
    return batch


def misaligned_batch(n_traces=8, seed=0):
    """A frame whose span_id column is a strided (non-contiguous) view —
    the uint32-split trick cannot reinterpret it zero-copy."""
    b = synthesize_traces(n_traces, seed=seed)
    doubled = np.repeat(b.col("span_id"), 2)
    cols = dict(b.columns)
    cols["span_id"] = doubled[::2]
    assert not cols["span_id"].flags["C_CONTIGUOUS"]
    return replace(b, columns=cols)


# ------------------------------------------------------------ column twins


class TestColumnTwins:
    def test_featurize_columns_matches_featurize_bitwise(self):
        """One spec, two entry points: the SpanColumns path must be the
        byte-identical computation the SpanBatch path delegates to."""
        cfg = FeaturizerConfig()
        for seed in range(3):
            b = synthesize_traces(24, seed=seed)
            f1 = featurize(b, cfg)
            f2 = featurize_columns(batch_columns(b), cfg)
            np.testing.assert_array_equal(f1.categorical, f2.categorical)
            np.testing.assert_array_equal(f1.continuous, f2.continuous)

    def test_featurize_columns_jax_matches_numpy(self):
        """The device twin: categorical features exact, continuous
        within the documented f32 duration bound."""
        cfg = FeaturizerConfig()
        for seed in (0, 7):
            b = synthesize_traces(48, seed=seed)
            cols = batch_columns(b)
            want = featurize_columns(cols, cfg)
            svc_tab, nam_tab = _device_tables(
                cols.strings, cfg.service_vocab, cfg.name_vocab)
            span_lo, span_hi = _split_u64(cols.span_id)
            par_lo, par_hi = _split_u64(cols.parent_span_id)
            start_lo, start_hi = _split_u64(cols.start_unix_nano)
            end_lo, end_hi = _split_u64(cols.end_unix_nano)
            frame_id = np.zeros(len(b), np.int32)
            cat, cont = featurize_columns_jax(
                svc_tab, nam_tab,
                jnp.asarray(cols.service), jnp.asarray(cols.name),
                jnp.asarray(cols.kind), jnp.asarray(cols.status_code),
                jnp.asarray(span_hi), jnp.asarray(span_lo),
                jnp.asarray(par_hi), jnp.asarray(par_lo),
                jnp.asarray(end_hi), jnp.asarray(end_lo),
                jnp.asarray(start_hi), jnp.asarray(start_lo),
                jnp.asarray(frame_id))
            np.testing.assert_array_equal(np.asarray(cat),
                                          want.categorical)
            np.testing.assert_allclose(np.asarray(cont), want.continuous,
                                       rtol=FUSED_RTOL, atol=FUSED_ATOL)


# --------------------------------------------------------- backend parity


class TestBackendParity:
    """dispatch_columns == dispatch/harvest, per span, every backend."""

    def test_product_bounds_are_the_ones_pinned_here(self):
        """chip_smoke.py's parity gate judges with serving/fused.py's
        bounds; this file is where they are set."""
        assert PARITY_F32 == (FUSED_RTOL, FUSED_ATOL)
        assert PARITY_BF16 == (1e-2, 1e-4)  # test_bfloat16_backend_parity
        assert PARITY_INT8 == (0.05, 5e-3)  # test_quantized_backend_parity

    @pytest.mark.parametrize("make_cfg", [tf_cfg, ae_cfg],
                             ids=["transformer", "autoencoder"])
    def test_fused_scores_match_host_route(self, make_cfg):
        eng = ScoringEngine(make_cfg())  # unstarted: direct backend use
        backend = eng.backend
        assert backend.supports_fused
        for seed in (3, 4):
            b = synthesize_traces(40, seed=seed)
            want = backend.score(b, featurize(b, eng.cfg.featurizer))
            cols, reason = extract_columns(b, eng.cfg.featurizer)
            assert reason is None
            got = backend.harvest(backend.dispatch_columns([cols]))
            assert got.shape == want.shape and got.dtype == np.float32
            np.testing.assert_allclose(got, want, rtol=FUSED_RTOL,
                                       atol=FUSED_ATOL)

    def test_quantized_backend_parity(self):
        """int8 route: bucket flips near quantization boundaries allow a
        looser per-span bound, but the population must agree tightly."""
        backend = ScoringEngine(tf_cfg(quantized=True)).backend
        assert backend.supports_fused
        b = synthesize_traces(40, seed=5)
        want = backend.score(b, featurize(b))
        cols, reason = extract_columns(b, FeaturizerConfig())
        assert reason is None
        got = backend.harvest(backend.dispatch_columns([cols]))
        assert served_precision(backend) == "int8"
        assert np.max(np.abs(got - want)) < PARITY_INT8[0]
        assert np.mean(np.abs(got - want)) < PARITY_INT8[1]
        assert routes_agree(got, want, "int8")

    def test_bfloat16_backend_parity(self):
        """bfloat16 route: its own bound, set from the chip's reading on
        the flagship and far inside int8's. A route wrong on 2% of its
        spans by a tenth of the score range random weights produce
        (0.44..0.70) passes int8's bound and must not pass this one."""
        backend = ScoringEngine(tf_cfg(model_config=replace(
            TINY_TF, dtype=jnp.bfloat16))).backend
        assert served_precision(backend) == "bfloat16"
        assert served_precision(ScoringEngine(tf_cfg()).backend) == "float32"
        b = synthesize_traces(40, seed=5)
        want = backend.score(b, featurize(b))
        cols, reason = extract_columns(b, FeaturizerConfig())
        assert reason is None
        got = backend.harvest(backend.dispatch_columns([cols]))
        assert routes_agree(got, want, "bfloat16")
        wrong = got.copy()
        wrong[::50] += 0.026
        assert routes_agree(wrong, want, "int8")
        assert not routes_agree(wrong, want, "bfloat16")

    def test_truncated_traces_parity(self):
        """Traces longer than max_len: the device next-fit must chunk
        exactly where the host pack does (the OOB-drop scatter may not
        eat real spans)."""
        ae8 = AutoencoderConfig(d_model=32, n_heads=2, n_layers=1,
                                d_ff=64, max_len=8, dtype=jnp.float32,
                                service_vocab=64, name_vocab=64)
        backend = ScoringEngine(ae_cfg(model_config=ae8,
                                       max_len=8)).backend
        b = synthesize_traces(30, seed=6)
        assert int(np.max(np.bincount(
            b.col("trace_id_lo").astype(np.int64) % (1 << 31)))) >= 1
        want = backend.score(b, featurize(b))
        cols, _ = extract_columns(b, FeaturizerConfig())
        got = backend.harvest(backend.dispatch_columns([cols]))
        np.testing.assert_allclose(got, want, rtol=FUSED_RTOL,
                                   atol=FUSED_ATOL)

    def test_orphan_parent_parity(self):
        """Parents that resolve to no span in the frame: the device
        self-join must miss exactly where the host join misses."""
        backend = ScoringEngine(tf_cfg()).backend
        b = synthesize_traces(24, seed=11)
        par = b.col("parent_span_id").copy()
        par[::3] = np.uint64(0xDEADBEEFCAFEF00D)  # no such span anywhere
        b = replace(b, columns=dict(b.columns, parent_span_id=par))
        want = backend.score(b, featurize(b))
        cols, reason = extract_columns(b, FeaturizerConfig())
        assert reason is None
        got = backend.harvest(backend.dispatch_columns([cols]))
        np.testing.assert_allclose(got, want, rtol=FUSED_RTOL,
                                   atol=FUSED_ATOL)

    def test_multi_frame_coalesced_group_parity(self):
        """A coalesced group (several frames, one device call) must
        match the host multi-frame merge: featurize per frame, pack on
        the concatenated columns — including trace ids SHARED across
        frames (same-seed frames), which pack into one trace exactly as
        the host sort does."""
        backend = ScoringEngine(tf_cfg()).backend
        batches = [synthesize_traces(n, seed=s)
                   for n, s in ((9, 21), (13, 22), (9, 21))]
        feats = [featurize(b) for b in batches]
        merged = SpanFeatures(
            np.concatenate([f.categorical for f in feats]),
            np.concatenate([f.continuous for f in feats]))
        want = backend.score(concat_batches(batches), merged)
        cols = [extract_columns(b, FeaturizerConfig())[0]
                for b in batches]
        assert all(c is not None for c in cols)
        got = backend.harvest(backend.dispatch_columns(cols))
        np.testing.assert_allclose(got, want, rtol=FUSED_RTOL,
                                   atol=FUSED_ATOL)


# -------------------------------------------------------- fallback ladder


class TestFallbackLadder:
    def test_covered_frame_extracts(self):
        cols, reason = extract_columns(synthesize_traces(8, seed=0),
                                       FeaturizerConfig())
        assert reason is None and len(cols) > 0

    def test_zero_span_frame_falls_back(self):
        b = synthesize_traces(4, seed=0)
        empty = b.filter(np.zeros(len(b), bool))
        cols, reason = extract_columns(empty, FeaturizerConfig())
        assert cols is None and reason == "zero_span"

    def test_attr_slot_config_falls_back(self):
        cols, reason = extract_columns(synthesize_traces(8, seed=0),
                                       FeaturizerConfig(attr_slots=4))
        assert cols is None and reason == "attr_slots"

    def test_legacy_json_attr_frame_falls_back(self):
        cols, reason = extract_columns(legacy_batch(), FeaturizerConfig())
        assert cols is None and reason == "legacy_attrs"

    def test_misaligned_columns_fall_back(self):
        cols, reason = extract_columns(misaligned_batch(),
                                       FeaturizerConfig())
        assert cols is None and reason == "misaligned_columns"

    def test_every_reason_is_in_the_closed_vocabulary(self):
        for reason in ("zero_span", "attr_slots", "legacy_attrs",
                       "misaligned_columns", "disabled", "backend"):
            assert reason in FALLBACK_REASONS

    def test_non_sequence_backends_are_not_fused_capable(self):
        for model in ("mock", "zscore"):
            backend = ScoringEngine(EngineConfig(model=model)).backend
            assert not getattr(backend, "supports_fused", False)

    def test_kill_switch_env(self, monkeypatch):
        monkeypatch.delenv("ODIGOS_FUSED", raising=False)
        assert fused_enabled()
        monkeypatch.setenv("ODIGOS_FUSED", "0")
        assert not fused_enabled()


# ------------------------------------------------------ fast-path route


class _Sink:
    def __init__(self):
        self.batches = []

    def consume(self, b):
        self.batches.append(b)

    @property
    def span_count(self):
        return sum(len(b) for b in self.batches)


def run_fastpath(frames, fp_cfg, engine_cfg=None, threshold=0.0):
    """One fast path over a started engine; returns (sink, fp counters
    snapshot) after every frame retires."""
    eng = ScoringEngine(engine_cfg or tf_cfg()).start()
    sink = _Sink()
    fp = IngestFastPath("traces/in", eng, threshold, sink,
                        dict({"deadline_ms": 30_000.0}, **fp_cfg))
    fp.start()
    try:
        for f in frames:
            fp.consume(f)
        assert wait_for(lambda: fp.flow_pending() == 0)
        assert wait_for(
            lambda: sink.span_count == sum(len(f) for f in frames))
    finally:
        fp.shutdown()
        eng.shutdown()
    return sink


class TestFusedFastPath:
    FUSED_KEY = labeled_key(FUSED_FRAMES_METRIC, pipeline="traces/in")

    def fallback_key(self, reason):
        return labeled_key(FUSED_FALLBACK_METRIC, pipeline="traces/in",
                           reason=reason)

    def test_fused_route_scores_match_host_route(self):
        # ordered: the comparison flattens sink batches positionally, and
        # unordered lanes retire frames in completion order — a host run
        # and a fused run would interleave differently under load
        meter.reset()
        frames = [synthesize_traces(10, seed=s) for s in range(3)]
        fused = run_fastpath(frames, {"fused": True, "ordered": True})
        assert meter.counter(self.FUSED_KEY) == len(frames)
        meter.reset()
        host = run_fastpath(frames, {"ordered": True})  # knob unset: host
        assert meter.counter(self.FUSED_KEY) == 0
        got = [d[SCORE_ATTR] for b in fused.batches for d in b.span_attrs]
        want = [d[SCORE_ATTR] for b in host.batches for d in b.span_attrs]
        assert len(got) == len(want) == sum(len(f) for f in frames)
        np.testing.assert_allclose(got, want, rtol=FUSED_RTOL,
                                   atol=1e-5)

    def test_kill_switch_falls_back_with_nothing_lost(self, monkeypatch):
        meter.reset()
        monkeypatch.setenv("ODIGOS_FUSED", "0")
        frames = [synthesize_traces(8, seed=s) for s in range(2)]
        sink = run_fastpath(frames, {"fused": True})
        assert sink.span_count == sum(len(f) for f in frames)
        assert meter.counter(self.FUSED_KEY) == 0
        assert meter.counter(self.fallback_key("disabled")) == len(frames)
        # every span still scored (host route, not a shed)
        assert all(SCORE_ATTR in d for b in sink.batches
                   for d in b.span_attrs)
        # restored: the switch is read per frame, so the very next
        # frames ride the fused route again and none falls back
        monkeypatch.delenv("ODIGOS_FUSED")
        sink = run_fastpath(frames, {"fused": True})
        assert sink.span_count == sum(len(f) for f in frames)
        assert meter.counter(self.FUSED_KEY) == len(frames)
        assert meter.counter(self.fallback_key("disabled")) == len(frames)

    def test_mixed_storm_conserves_exact(self):
        """Covered, legacy-JSON, and misaligned frames interleaved: every
        span comes out scored, and fused + fallback counters partition
        the storm exactly."""
        meter.reset()
        covered = [synthesize_traces(8, seed=s) for s in range(4)]
        legacy = [legacy_batch(6, seed=s) for s in range(3)]
        crooked = [misaligned_batch(5, seed=s) for s in range(2)]
        frames = []
        for trio in zip(covered, legacy + [None], crooked + [None, None]):
            frames.extend(f for f in trio if f is not None)
        sink = run_fastpath(frames, {"fused": True})
        assert sink.span_count == sum(len(f) for f in frames)
        assert meter.counter(self.FUSED_KEY) == len(covered)
        assert meter.counter(
            self.fallback_key("legacy_attrs")) == len(legacy)
        assert meter.counter(
            self.fallback_key("misaligned_columns")) == len(crooked)
        handled = meter.counter(self.FUSED_KEY) + sum(
            meter.counter(self.fallback_key(r)) for r in FALLBACK_REASONS)
        assert handled == len(frames)
        assert all(SCORE_ATTR in d for b in sink.batches
                   for d in b.span_attrs)

    def test_unfusable_backend_counts_backend_fallback(self):
        meter.reset()
        frames = [synthesize_traces(6, seed=1)]
        sink = run_fastpath(frames, {"fused": True},
                            engine_cfg=EngineConfig(model="mock"),
                            threshold=0.6)
        assert sink.span_count == len(frames[0])
        assert meter.counter(self.fallback_key("backend")) == 1

    def test_fused_stage_lands_in_latency_waterfall(self):
        latency_ledger.reset()
        run_fastpath([synthesize_traces(10, seed=2)], {"fused": True})
        wf = latency_ledger.recorder("traces/in").waterfall()
        assert wf.get(Stage.FUSED.value, {}).get("count", 0) >= 1
        # the fused frame never stamped a featurize wall
        assert Stage.FEATURIZE.value not in wf


# ------------------------------------------- predictive shed on fused route


class TestPredictiveShedFused:
    def test_recorder_prices_fused_stage(self):
        """The burn table must price the ``fused`` stage on fused
        frames — pricing only featurize/pack (both absent) would zero
        the prediction and hold the admission gate open through
        overload."""
        flow_ledger.reset()
        latency_ledger.reset()
        eng = ScoringEngine(tf_cfg()).start()
        sink = _Sink()
        fp = IngestFastPath("traces/pr", eng, 0.0, sink,
                            {"deadline_ms": 30_000.0, "fused": True,
                             "predictive_min_frames": 1})
        fp.start()
        try:
            for s in range(3):
                fp.consume(synthesize_traces(8, seed=s))
            assert wait_for(lambda: fp.flow_pending() == 0)
            fp._stage_cost_next_ns = 0  # force a re-price on refresh
            fp.consume(synthesize_traces(8, seed=9))
            assert wait_for(lambda: fp.flow_pending() == 0)
            frames, means = fp._recorder.stage_means()
            assert frames >= 1
            assert means.get(Stage.FUSED.value, 0.0) > 0.0
            assert means.get(Stage.FEATURIZE.value, 0.0) == 0.0
            assert fp._stage_cost_ms is not None \
                and fp._stage_cost_ms > 0.0
            wm = flow_ledger.watermark_current("fastpath/traces/pr",
                                               "predicted_burn_ms")
            assert wm is not None and wm >= 0.0
        finally:
            fp.shutdown()
            eng.shutdown()

    def test_fused_overload_sheds_predicted_before_decode(self):
        """The ISSUE 12 pre-decode gate, fused edition: with the route
        armed and frames flowing fused, a predicted_burn_ms breach is
        REJECTED at the socket with blame=predicted — ledger exact."""
        flow_ledger.reset()
        meter.reset()
        cfg = fused_collector_cfg()
        cfg["receivers"]["otlpwire"] = {"admission": {
            "watermarks": {"fastpath/traces/in":
                           {"predicted_burn_ms": 25.0}},
            "refresh_ms": 0.0}}
        collector = Collector(cfg).start()
        try:
            port = collector.graph.receivers["otlpwire"].port
            b = synthesize_traces(6, seed=3)
            sink = collector.graph.exporters["tracedb"]
            s = socket.create_connection(("127.0.0.1", port), timeout=5)
            flow_ledger.watermark("fastpath/traces/in",
                                  "predicted_burn_ms", 3.0)
            s.sendall(frame(b))
            assert s.recv(1) == b"\x00"
            assert wait_for(lambda: sink.span_count == len(b))
            # the admitted frame rode the fused route
            assert meter.counter(labeled_key(
                FUSED_FRAMES_METRIC, pipeline="traces/in")) >= 1
            flow_ledger.watermark("fastpath/traces/in",
                                  "predicted_burn_ms", 80.0)
            s.sendall(frame(b))
            assert s.recv(1) == REJECTED
            s.close()
            key = ("odigos_admission_rejected_frames_total"
                   "{receiver=otlpwire,"
                   "reason=fastpath/traces/in:predicted_burn_ms}")
            assert meter.counter(key) == 1
            blamed = [k for k in meter.snapshot()
                      if k.startswith("odigos_flow_dropped_items_total")
                      and "blame=predicted" in k]
            assert blamed, "fused-route predictive shed lost its blame"
            bal = flow_ledger.conservation()["traces/in"]
            assert bal["leak"] == 0, bal
        finally:
            collector.shutdown()


# -------------------------------------------------- config + hot reload


def fused_collector_cfg(fused=True, threshold=0.0):
    return {
        "receivers": {"otlpwire": {}},
        "processors": {
            "memory_limiter": {"limit_mib": 512},
            "batch": {"send_batch_size": 1, "timeout_s": 0.0},
            "tpuanomaly": {"model": "transformer", "threshold": threshold,
                           "timeout_ms": 30_000, "shared_engine": False,
                           "max_len": 16, "trace_bucket": 8,
                           "model_config": {"d_model": 32, "n_heads": 2,
                                            "n_layers": 1, "d_ff": 64,
                                            "max_len": 16,
                                            "dtype": "float32"}},
        },
        "exporters": {"tracedb": {}},
        "service": {"pipelines": {"traces/in": {
            "receivers": ["otlpwire"],
            "processors": ["memory_limiter", "batch", "tpuanomaly"],
            "exporters": ["tracedb"],
            "fast_path": dict({"deadline_ms": 30_000.0},
                              **({"fused": True} if fused else {})),
        }}},
    }


class TestConfigAndReload:
    def test_validate_accepts_fused_and_rejects_non_bool(self):
        from odigos_tpu.pipeline.graph import validate_config

        assert validate_config(fused_collector_cfg()) == []
        bad = fused_collector_cfg()
        bad["service"]["pipelines"]["traces/in"]["fast_path"][
            "fused"] = "yes"
        assert any("fused" in p for p in validate_config(bad))

    def test_fused_knob_diffs_reconfigure_never_full(self):
        old = fused_collector_cfg(fused=False)
        new = fused_collector_cfg(fused=True)
        d = diff_configs(old, new)
        assert d.mode == INCREMENTAL, d.reasons
        [act] = d.actions
        assert act.kind == "fastpath" and act.action == RECONFIGURE
        assert "fused" in act.changed
        # and back off again — still a knob turn
        assert diff_configs(new, old).mode == INCREMENTAL

    def test_pipelinegen_renders_fused_only_when_armed(self):
        from odigos_tpu.components.api import Signal
        from odigos_tpu.config.model import AnomalyStageConfiguration
        from odigos_tpu.destinations import Destination
        from odigos_tpu.pipelinegen import (
            GatewayOptions, build_gateway_config)

        dest = Destination(id="j1", dest_type="jaeger",
                           signals=[Signal.TRACES],
                           config={"JAEGER_URL": "jaeger:4317"})
        def render(**kw):
            opts = GatewayOptions(anomaly=AnomalyStageConfiguration(
                enabled=True, fast_path=True, **kw))
            cfg, _, _ = build_gateway_config([dest], options=opts)
            return cfg["service"]["pipelines"]["traces/in"]["fast_path"]

        assert "fused" not in render(), \
            "fused must be opt-in: existing configs stay byte-identical"
        assert render(fast_path_fused=True).get("fused") is True

    def test_live_reload_arms_and_disarms_fused(self):
        """The knob flips on a running graph via reconfigure — the fast
        path instance survives (RECONFIGURE, not a rebuild) and frames
        keep flowing on the newly selected route."""
        meter.reset()
        flow_ledger.reset()
        collector = Collector(fused_collector_cfg(fused=False)).start()
        try:
            fp = collector.graph.fastpaths["traces/in"]
            assert fp.fused is False
            port = collector.graph.receivers["otlpwire"].port
            new = fused_collector_cfg(fused=True)
            new["receivers"]["otlpwire"] = {"port": port}
            collector.reload(new)
            assert collector.graph.fastpaths["traces/in"] is fp, \
                "fused flip must patch in place, not rebuild the route"
            assert fp.fused is True
        finally:
            collector.shutdown()
