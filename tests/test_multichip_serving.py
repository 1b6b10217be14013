"""Multi-chip sharded serving: the ISSUE 7 tentpole.

The ScoringEngine owns a jax.sharding.Mesh and dispatches every packed
call through a partition-rule dp×tp plan (parallel.compile_plan). These
tests pin the contract on the 8-virtual-device CPU mesh (conftest — the
CPU-fallback path itself is an ISSUE 7 satellite):

* one mesh, one owner: the engine builds it, the backend receives it;
* "data"-axis sharding is BITWISE identical to single-device scoring
  (rows are independent — same per-row program, rows merely placed),
  and tags follow; a "model" axis reassociates the contraction psum, so
  dp×tp parity is ULP-level with identical tags;
* the bucket ladder lcm-aligns its rungs to the mesh, so warmed shapes
  cover steady-state traffic — zero recompiles per mesh shape;
* the adaptive coalescer learns device-step cost PER MESH (a fresh
  engine on a known mesh shape seeds from the registry; single-device
  engines keep their exact cold start);
* the wire plumbing renders and honors the mesh (pipelinegen →
  tpuanomaly → EngineConfig), and the autoscaler co-schedules gateway
  replicas with whole mesh slices.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from odigos_tpu.features import featurize  # noqa: E402
from odigos_tpu.models import TransformerConfig  # noqa: E402
from odigos_tpu.pdata import synthesize_traces  # noqa: E402
from odigos_tpu.serving import (  # noqa: E402
    BucketLadder, EngineConfig, ScoringEngine)
from odigos_tpu.serving.fastpath import tag_anomalies  # noqa: E402

TINY_TF = TransformerConfig(d_model=32, n_heads=2, n_layers=1, d_ff=64,
                            max_len=16, dtype=jnp.float32)


def cfg_for(mesh=None, **kw) -> EngineConfig:
    base = dict(model="transformer", model_config=TINY_TF, max_len=16,
                trace_bucket=8, bucket_ladder=2, pipeline_depth=2,
                mesh=mesh)
    base.update(kw)
    return EngineConfig(**base)


# ------------------------------------------------------- config + ownership

def test_engine_config_mesh_normalization_and_hashability():
    c = EngineConfig(model="transformer", mesh={"data": 4, "model": 2})
    assert c.mesh == (("data", 4), ("model", 2))
    assert c.mesh_shape() == {"data": 4, "model": 2}
    hash(c)  # shared-engine keying hashes the config
    # legacy data_parallel spells mesh={"data": N}
    c2 = EngineConfig(model="transformer", data_parallel=4)
    assert c2.mesh == (("data", 4),)
    # a 1x1 mesh IS the single-device path
    assert EngineConfig(mesh={"data": 1, "model": 1}).mesh is None
    assert EngineConfig(data_parallel=1).mesh is None
    # explicit mesh wins over the legacy knob
    c3 = EngineConfig(mesh={"data": 2}, data_parallel=8)
    assert c3.mesh == (("data", 2),)
    # a zero-size axis is a config bug, refused — silently dropping it
    # would serve pure-DP while the operator believes tp is active
    with pytest.raises(ValueError, match="positive"):
        EngineConfig(mesh={"data": 4, "model": 0})


def test_engine_owns_the_one_mesh():
    eng = ScoringEngine(cfg_for(mesh={"data": 2, "model": 2}))
    assert eng.mesh is not None
    assert dict(eng.mesh.shape) == {"data": 2, "model": 2}
    # one mesh, one owner: the backend holds the engine's mesh, and the
    # partition plan was compiled against exactly it
    assert eng.backend.mesh is eng.mesh
    assert eng.backend._plan is not None
    assert eng.backend._plan.mesh is eng.mesh
    # non-sequence models never build a mesh (they stay jax-free)
    assert ScoringEngine(EngineConfig(model="mock",
                                      mesh={"data": 2})).mesh is None


def test_bucket_ladder_aligns_rungs_to_mesh():
    lad = BucketLadder(base=6, n_buckets=3, align=4)
    assert lad.base == 12  # lcm(6, 4)
    assert lad.buckets == [12, 24, 48]
    assert all(b % 4 == 0 for b in lad.buckets)
    # beyond-top multiples and floors stay shard-divisible
    assert lad.round_rows(100) % 4 == 0
    assert lad.floor_rows(100) % 4 == 0
    assert lad.stats()["align"] == 4
    # engine wiring: the dp width of the mesh is the alignment
    eng = ScoringEngine(cfg_for(mesh={"data": 2}, trace_bucket=9))
    assert eng.backend.ladder.base == 18  # lcm(9, 2)
    assert eng.backend.ladder.align == 2


# ------------------------------------------------------------ score parity

def _scores_through(mesh, batch, feats):
    eng = ScoringEngine(cfg_for(mesh=mesh)).start()
    try:
        s = eng.score_sync(batch, feats, timeout_s=120.0)
        assert s is not None
        return s
    finally:
        eng.shutdown()


def test_dp_scores_and_tags_bitwise_identical_to_single_device():
    """Matched grouping (same trace_bucket; rungs already dp-divisible)
    -> identical packed shapes -> dp sharding must be BITWISE identical:
    each row runs the same program, rows are merely placed on shards."""
    batch = synthesize_traces(20, seed=3)
    feats = featurize(batch)
    ref = _scores_through(None, batch, feats)
    for mesh in ({"data": 2}, {"data": 4}):
        got = _scores_through(mesh, batch, feats)
        np.testing.assert_array_equal(got, ref)
        # tags are a pure threshold of the scores — bitwise follows
        t_ref = tag_anomalies(batch, ref, 0.5)
        t_got = tag_anomalies(batch, got, 0.5)
        np.testing.assert_array_equal(
            t_ref.attrs().mask_has("odigos.anomaly"),
            t_got.attrs().mask_has("odigos.anomaly"))


def test_dp_tp_scores_ulp_close_and_tags_identical():
    """The "model" axis splits contraction reductions (partial matmul +
    psum): reassociated float sums are ULP-level different from the
    single-device order, NEVER guaranteed bitwise — asserted tight, and
    the tags (the product surface) must still be identical."""
    batch = synthesize_traces(20, seed=3)
    feats = featurize(batch)
    ref = _scores_through(None, batch, feats)
    got = _scores_through({"data": 2, "model": 2}, batch, feats)
    np.testing.assert_allclose(got, ref, atol=1e-6, rtol=0)
    assert not np.any(np.abs(ref - 0.5) < 1e-5), "threshold too close"
    t_ref = tag_anomalies(batch, ref, 0.5)
    t_got = tag_anomalies(batch, got, 0.5)
    np.testing.assert_array_equal(
        t_ref.attrs().mask_has("odigos.anomaly"),
        t_got.attrs().mask_has("odigos.anomaly"))


# --------------------------------------------- zero recompiles per mesh

@pytest.mark.parametrize("mesh", [{"data": 2}, {"data": 2, "model": 2}])
def test_zero_recompiles_per_mesh_shape_after_warm(mesh):
    eng = ScoringEngine(cfg_for(mesh=mesh, warm_ladder=True,
                                trace_bucket=4, bucket_ladder=2)).start()
    try:
        assert eng.backend.ladder.misses == 0  # warming never counts
        for seed, n in ((1, 2), (2, 6), (3, 3), (4, 5)):
            b = synthesize_traces(n, seed=seed)
            assert eng.score_sync(b, featurize(b),
                                  timeout_s=120.0) is not None
    finally:
        eng.shutdown()
    lad = eng.backend.ladder
    assert lad.misses == 0, f"steady-state recompiled on mesh {mesh}"
    assert lad.hits >= 4
    assert all(b % 2 == 0 for b in lad.buckets)  # dp-aligned rungs


# ------------------------------------------------- per-mesh adaptive cost

def test_adaptive_cost_learned_per_mesh_and_seeds_new_engines():
    mesh = {"data": 2}
    # keyed by (model, GEOMETRY, mesh): a blue/green swap to a bigger
    # model on the same mesh must not inherit the small model's cost
    key = ("transformer", TINY_TF, (("data", 2),))
    ScoringEngine._ADAPT_PRIORS.pop(key, None)
    eng = ScoringEngine(cfg_for(mesh=mesh)).start()
    try:
        assert eng._ms_per_span() is None  # nothing learned yet
        b = synthesize_traces(6, seed=7)
        r = eng.submit(b, featurize(b))
        assert r is not None and r.done.wait(120.0)
        assert eng._ms_per_span() is not None
        assert eng.pipeline_stats()["adaptive"]["mesh"] == "data2"
        # rows are learned from the rows the packer filled, not from
        # the dp-aligned rung that padded them
        real = eng.backend.last_real_rows
        assert 0 < real < eng.backend.last_shape[0]
        assert eng._ewma_spans_per_row == len(b) / real
    finally:
        eng.shutdown()
    # a fresh engine on the SAME mesh shape starts from the learned cost
    # and the learned spans per real row
    eng2 = ScoringEngine(cfg_for(mesh=mesh))
    assert eng2._ms_per_span() is not None
    assert eng2._spans_per_row() == eng._spans_per_row() == len(b) / real
    # ... while single-device engines keep their exact cold start
    eng3 = ScoringEngine(cfg_for())
    assert eng3._ms_per_span() is None
    # ... and a DIFFERENT geometry on the same mesh starts cold too
    other = TransformerConfig(d_model=64, n_heads=2, n_layers=1,
                              d_ff=128, max_len=16, dtype=jnp.float32)
    eng4 = ScoringEngine(cfg_for(mesh=mesh, model_config=other))
    assert eng4._ms_per_span() is None
    ScoringEngine._ADAPT_PRIORS.pop(key, None)


# ------------------------------------------------------- partition rules

def test_partition_rules_place_transformer_params():
    from jax.sharding import PartitionSpec as P

    from odigos_tpu.parallel import (
        compile_plan, make_mesh, match_partition_rules)

    eng = ScoringEngine(cfg_for(mesh={"data": 2, "model": 2}))
    variables = eng.backend.variables
    specs = {
        "/".join(str(k.key) for k in path): s
        for path, s in jax.tree_util.tree_leaves_with_path(
            match_partition_rules(variables),
            is_leaf=lambda x: isinstance(x, P))}
    qkv = [s for n, s in specs.items()
           if n.endswith(("query/kernel", "key/kernel", "value/kernel"))]
    assert qkv and all(s == P(None, "model", None) for s in qkv)
    outs = [s for n, s in specs.items() if n.endswith("out/kernel")]
    assert outs and all(s == P("model", None, None) for s in outs)
    embeds = [s for n, s in specs.items() if "embed" in n]
    assert embeds and all(s == P() for s in embeds)
    # the mesh guard replicates "model"-sharded params on a pure-DP mesh
    plan_dp = compile_plan(eng.backend.model, make_mesh({"data": 2}))
    guarded = plan_dp.param_specs(variables)
    flat = jax.tree_util.tree_leaves(
        guarded, is_leaf=lambda x: isinstance(x, P))
    assert all(s == P() for s in flat)


# ---------------------------------------------------------- wire plumbing

def test_pipelinegen_renders_mesh_and_processor_honors_it():
    from odigos_tpu.config.model import AnomalyStageConfiguration
    from odigos_tpu.destinations.registry import Destination
    from odigos_tpu.components.api import Signal
    from odigos_tpu.pipelinegen import GatewayOptions, build_gateway_config

    dest = Destination(id="j1", dest_type="jaeger",
                       signals=[Signal.TRACES],
                       config={"JAEGER_URL": "jaeger:4317"})

    def render(**kw):
        cfg, _status, _sig = build_gateway_config(
            [dest], options=GatewayOptions(
                anomaly=AnomalyStageConfiguration(enabled=True, **kw)))
        return cfg["processors"]["tpuanomaly"]

    # single-chip: byte-identical rendering, no mesh key at all
    assert "mesh" not in render()
    assert render(devices=4, tensor_parallel=2)["mesh"] == {
        "data": 4, "model": 2}
    assert render(devices=4)["mesh"] == {"data": 4, "model": 1}

    # the processor passes the mesh through to the engine config
    from odigos_tpu.components.processors.tpuanomaly import (
        TpuAnomalyProcessor)

    p = TpuAnomalyProcessor("tpuanomaly", {
        "model": "transformer", "shared_engine": False,
        "model_config": {"d_model": 32, "n_layers": 1, "d_ff": 64,
                         "n_heads": 2, "max_len": 16,
                         "dtype": "float32"},
        "max_len": 16, "trace_bucket": 8,
        "mesh": {"data": 2, "model": 2}})
    assert p.engine.cfg.mesh == (("data", 2), ("model", 2))
    assert dict(p.engine.mesh.shape) == {"data": 2, "model": 2}
    # legacy "devices" (what pre-mesh pipelinegen rendered) = pure DP
    p2 = TpuAnomalyProcessor("tpuanomaly", {
        "model": "transformer", "shared_engine": False,
        "model_config": {"d_model": 32, "n_layers": 1, "d_ff": 64,
                         "n_heads": 2, "max_len": 16,
                         "dtype": "float32"},
        "max_len": 16, "trace_bucket": 8, "devices": 2})
    assert p2.engine.cfg.mesh == (("data", 2),)


def test_autoscaler_co_schedules_whole_mesh_slices():
    from odigos_tpu.api import ControllerManager, Store
    from odigos_tpu.config.model import Configuration
    from odigos_tpu.controlplane import Autoscaler, Scheduler
    from odigos_tpu.controlplane.scheduler import (
        GATEWAY_GROUP_NAME, ODIGOS_NAMESPACE)
    from odigos_tpu.nodeagent.deviceplugin import DevicePluginRegistry

    def make_env(tpu_chips, devices, tp, mesh_slices=None):
        store = Store()
        mgr = ControllerManager(store)
        sched = Scheduler(store, mgr)
        cfg = Configuration()
        cfg.anomaly.enabled = True
        cfg.anomaly.devices = devices
        cfg.anomaly.tensor_parallel = tp
        cfg.collector_gateway.mesh_slices = mesh_slices
        asc = Autoscaler(store, mgr, cfg)
        reg = DevicePluginRegistry(tpu_chips=tpu_chips)
        asc.attach_device_registries([reg])
        sched.apply_authored(cfg)
        mgr.run_once()
        return store, asc

    # slice = 2dp x 2tp = 4 devices; 8 chips back at most 2 replicas
    store, asc = make_env(tpu_chips=8, devices=2, tp=2)
    n = asc.observe_metrics(160.0, 10.0, 0.0, now=1000.0)
    assert asc.mesh_slices_held() == n
    assert asc.tpu_devices_held() == 4 * n
    n = asc.observe_metrics(160.0, 10.0, 0.0, now=1020.0)
    n = asc.observe_metrics(160.0, 10.0, 0.0, now=1040.0)
    assert n == 2, "scale-out must cap at whole mesh slices"
    assert asc.tpu_devices_held() == 8
    gw = store.get("CollectorsGroup", ODIGOS_NAMESPACE,
                   GATEWAY_GROUP_NAME)
    cond = next(c for c in gw.conditions if c.type == "TpuScheduling")
    assert "mesh slice = 4 devices" in cond.message
    assert "2dp x 2tp" in cond.message

    # the mesh_slices sizing knob caps co-scheduling below pool capacity
    store, asc = make_env(tpu_chips=8, devices=2, tp=1, mesh_slices=1)
    asc.observe_metrics(160.0, 10.0, 0.0, now=1000.0)
    n = asc.observe_metrics(160.0, 10.0, 0.0, now=1020.0)
    assert n == 1  # 4 slices would fit, the knob allows one
    assert asc.tpu_devices_held() == 2
    gw = store.get("CollectorsGroup", ODIGOS_NAMESPACE,
                   GATEWAY_GROUP_NAME)
    cond = next(c for c in gw.conditions if c.type == "TpuScheduling")
    assert cond.reason == "TpuStarved"


def test_host_unbackable_mesh_degrades_to_single_device_loudly():
    """A devices:N gateway config can land on a pod with fewer visible
    devices: the engine serves single-device and counts the degradation
    instead of refusing to build (the pre-mesh code silently dropped
    the knob; bricking the collector on upgrade is worse)."""
    from odigos_tpu.serving.engine import MESH_UNAVAILABLE_METRIC
    from odigos_tpu.utils.telemetry import labeled_key, meter

    meter.reset()
    eng = ScoringEngine(cfg_for(mesh={"data": 64}))  # host has 8
    assert eng.mesh is None
    assert eng.backend._plan is None
    assert eng.backend.ladder.align == 1
    assert meter.counter(labeled_key(MESH_UNAVAILABLE_METRIC,
                                     model="transformer")) == 1
    # no multi-chip labels or priors for a mesh that never existed
    assert "mesh" not in eng.runtime_gauges()
    assert eng.pipeline_stats()["adaptive"]["mesh"] == "single"
    b = synthesize_traces(4, seed=11)
    s = eng.start().score_sync(b, featurize(b), timeout_s=120.0)
    eng.shutdown()
    assert s is not None and s.shape == (len(b),)


def test_autoscaler_releases_stale_slices_on_resize():
    """A config reload that changes the slice geometry must re-allocate
    held slices — replicas backed by wrong-sized allocations while the
    condition says DevicesAllocated would hide real starvation."""
    from odigos_tpu.api import ControllerManager, Store
    from odigos_tpu.config.model import Configuration
    from odigos_tpu.controlplane import Autoscaler, Scheduler
    from odigos_tpu.nodeagent.deviceplugin import DevicePluginRegistry

    store = Store()
    mgr = ControllerManager(store)
    sched = Scheduler(store, mgr)
    cfg = Configuration()
    cfg.anomaly.enabled = True
    cfg.anomaly.devices = 1
    asc = Autoscaler(store, mgr, cfg)
    reg = DevicePluginRegistry(tpu_chips=8)
    asc.attach_device_registries([reg])
    sched.apply_authored(cfg)
    mgr.run_once()
    asc.observe_metrics(160.0, 10.0, 0.0, now=1000.0)
    asc.observe_metrics(160.0, 10.0, 0.0, now=1020.0)
    assert asc.mesh_slices_held() >= 2
    assert all(len(d) == 1 for _, d in asc._tpu_held)
    # reload: slice becomes 2x2 = 4 devices
    cfg.anomaly.devices = 2
    cfg.anomaly.tensor_parallel = 2
    asc.set_effective_config(cfg)
    asc.observe_metrics(160.0, 10.0, 0.0, now=1040.0)
    assert all(len(d) == 4 for _, d in asc._tpu_held), \
        "stale 1-device slices survived the resize"
    from odigos_tpu.nodeagent.deviceplugin import TPU_DEVICE

    held = asc.tpu_devices_held()
    assert reg.plugins[TPU_DEVICE].ids.free_count == 8 - held


def test_effective_config_clamps_tensor_parallel_without_gate():
    from odigos_tpu.config.effective import calculate_effective_config
    from odigos_tpu.config.model import Configuration

    cfg = Configuration()
    cfg.anomaly.tensor_parallel = 2
    cfg.cluster_version = "1.30"
    eff = calculate_effective_config(cfg)
    gate = eff.features.get("shard-map-scoring", {})
    if gate.get("enabled"):
        assert eff.config.anomaly.tensor_parallel == 2
    else:
        assert eff.config.anomaly.tensor_parallel == 1
        assert any("tensor_parallel" in p for p in eff.problems)
