"""North-star acceptance: trained trace transformer reaches ROC-AUC >= 0.95
on held-out injected faults (BASELINE.json), at default model scale — and the
trained weights actually serve: exported as a bundle, loaded by a Collector's
tpuanomaly processor via ``checkpoint_path``, flagging injected-fault spans
into the anomaly-stream tracedb (the simple-trace-db assert pattern,
/root/reference tests/e2e/trace-collection).

Training runs once (module fixture, ~2 min single-core CPU; fast on TPU) and
feeds both tests.
"""

import numpy as np
import pytest

from odigos_tpu.components.processors.tpuanomaly import FLAG_ATTR
from odigos_tpu.pdata import inject_faults, synthesize_traces
from odigos_tpu.pipeline import Collector
from odigos_tpu.training import TrainConfig, Trainer, evaluate_detector, load_bundle
from odigos_tpu.training.evaluate import transformer_scorer


@pytest.fixture(scope="module")
def trained(tmp_path_factory):
    cfg = TrainConfig(steps=200, traces_per_step=64, max_len=32, seed=0)
    trainer = Trainer(cfg)
    res = trainer.train()
    bundle = trainer.export(
        str(tmp_path_factory.mktemp("bundle") / "transformer"), res.variables)
    return trainer, res, bundle


def test_northstar_auc(trained):
    trainer, res, _ = trained
    assert res.losses[-1] < res.losses[0] / 2
    scorer = transformer_scorer(trainer.model, res.variables, max_len=32)
    ev = evaluate_detector(scorer, n_traces=1000, seed=999)
    assert ev["auc"] >= 0.95, ev


def test_northstar_auc_quantized(trained):
    """The int8 serving path meets the same AUC bar on the same trained
    checkpoint (round-3 review item 6: max-|dp| parity alone does not bound
    ranking quality; assert the detection metric directly)."""
    from odigos_tpu.training.evaluate import quantized_transformer_scorer

    trainer, res, _ = trained
    scorer = quantized_transformer_scorer(trainer.model, res.variables,
                                          max_len=32)
    ev = evaluate_detector(scorer, n_traces=1000, seed=999)
    assert ev["auc"] >= 0.95, ev


def test_train_serve_loop_flags_faults_into_tracedb(trained):
    """The round-1 review critical path: checkpoint → pipeline → anomaly stream."""
    _, _, bundle_path = trained

    # the bundle carries the trained geometry — serving needs only the path
    bundle = load_bundle(bundle_path)
    assert bundle.model == "transformer"
    assert bundle.model_config.max_len == 32

    cfg = {
        "receivers": {"synthetic": {"traces_per_batch": 2, "n_batches": 1}},
        "processors": {
            "batch": {"send_batch_size": 100000, "timeout_s": 0.05},
            "tpuanomaly": {
                "model": "transformer", "checkpoint_path": bundle_path,
                "threshold": 0.5, "timeout_ms": 30000,
                "trace_bucket": 512, "shared_engine": False},
        },
        "connectors": {"anomalyrouter": {
            "anomaly_pipelines": ["traces/anomaly"],
            "default_pipelines": ["traces/normal"],
            "mode": "trace"}},
        "exporters": {"tracedb/anomaly": {}, "tracedb/normal": {}},
        "service": {"pipelines": {
            "traces/in": {"receivers": ["synthetic"],
                          "processors": ["batch", "tpuanomaly"],
                          "exporters": ["anomalyrouter"]},
            "traces/anomaly": {"receivers": ["anomalyrouter"],
                               "exporters": ["tracedb/anomaly"]},
            "traces/normal": {"receivers": ["anomalyrouter"],
                              "exporters": ["tracedb/normal"]},
        }},
    }
    clean = synthesize_traces(400, seed=4242)
    faulty, labels, reports = inject_faults(clean, fault_fraction=0.15,
                                            seed=4243)
    assert labels.any() and reports

    with Collector(cfg) as c:
        proc = c.component("tpuanomaly")
        # the engine restored the trained variables, not a random init
        assert proc.engine.backend.max_len == 32
        c.drain_receivers()
        c.graph.pipeline_entries["traces/in"].consume(faulty)
        c.drain_receivers()

        anomaly = c.component("tracedb/anomaly")
        normal = c.component("tracedb/normal")
        assert anomaly.span_count > 0, "no traces reached the anomaly stream"
        assert normal.span_count > 0, "all traffic was flagged anomalous"

        spans = anomaly.all_spans()
        flagged = [d for d in spans.span_attrs if FLAG_ATTR in d]
        assert flagged, "anomaly stream contains no flagged spans"

    # flagged spans should be enriched in true culprits: compare the label
    # rate among flagged spans vs the base rate of the injected batch
    by_span = {}
    for i in range(len(faulty)):
        by_span[int(faulty.col("span_id")[i])] = bool(labels[i])
    flag_mask = np.fromiter((FLAG_ATTR in d for d in spans.span_attrs),
                            bool, len(spans))
    hit = [by_span.get(int(s), False)
           for s in spans.col("span_id")[flag_mask]]
    base_rate = labels.mean()
    assert np.mean(hit) > base_rate * 2, (np.mean(hit), base_rate)
