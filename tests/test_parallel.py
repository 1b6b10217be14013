"""Parallel layer tests on the 8-device virtual CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from odigos_tpu.features import assemble_sequences, featurize
from odigos_tpu.models import TraceTransformer, TransformerConfig
from odigos_tpu.parallel import (
    make_mesh, make_sharded_score_fn, make_sharded_train_step, ring_attention,
    shard_variables)
from odigos_tpu.parallel.ring_attention import reference_attention
from odigos_tpu.pdata import synthesize_traces

TINY = TransformerConfig(d_model=32, n_heads=4, n_layers=1, d_ff=64,
                         max_len=16, dtype=jnp.float32)


def test_make_mesh_shapes():
    assert len(jax.devices()) == 8, "conftest must provide 8 CPU devices"
    m = make_mesh()
    assert m.shape == {"data": 8, "model": 1}
    m2 = make_mesh({"data": 4, "model": 2})
    assert m2.shape == {"data": 4, "model": 2}
    # explicit shapes may use a prefix of the devices (driver dry-runs call
    # with smaller counts than registered)
    m3 = make_mesh({"data": 3, "model": 2})
    assert m3.devices.size == 6
    with pytest.raises(ValueError, match="needs"):
        make_mesh({"data": 3, "model": 3})  # 9 > 8


def test_sharded_scoring_matches_single_device():
    batch = synthesize_traces(12, seed=0)
    seqs = assemble_sequences(batch, max_len=16)
    model = TraceTransformer(TINY)
    variables = model.init(jax.random.PRNGKey(0))
    cat = jnp.asarray(seqs.categorical)
    cont = jnp.asarray(seqs.continuous)
    mask = jnp.asarray(seqs.mask)
    ref_span, ref_trace = model.score_spans(variables, cat, cont, mask)

    mesh = make_mesh({"data": 4, "model": 2})
    sharded_vars = shard_variables(variables, mesh)
    score = make_sharded_score_fn(model, mesh)
    span_p, trace_p = score(sharded_vars, seqs.categorical, seqs.continuous,
                            seqs.mask)
    np.testing.assert_allclose(span_p, np.asarray(ref_span), atol=2e-5)
    np.testing.assert_allclose(trace_p, np.asarray(ref_trace), atol=2e-5)


def test_sharded_scoring_pads_uneven_batch():
    batch = synthesize_traces(5, seed=1)  # 5 traces, dp=4 -> pad to 8
    seqs = assemble_sequences(batch, max_len=16)
    model = TraceTransformer(TINY)
    variables = model.init(jax.random.PRNGKey(0))
    mesh = make_mesh({"data": 4, "model": 2})
    score = make_sharded_score_fn(model, mesh)
    span_p, trace_p = score(shard_variables(variables, mesh),
                            seqs.categorical, seqs.continuous, seqs.mask)
    assert span_p.shape == seqs.mask.shape
    assert trace_p.shape == (5,)


def test_sharded_train_step_runs_and_learns():
    batch = synthesize_traces(16, seed=2)
    seqs = assemble_sequences(batch, max_len=16)
    model = TraceTransformer(TINY)
    variables = model.init(jax.random.PRNGKey(0))
    mesh = make_mesh({"data": 4, "model": 2})
    variables = shard_variables(variables, mesh)
    tx = optax.adam(1e-2)
    opt_state = tx.init(variables)
    step = make_sharded_train_step(model, tx, mesh)

    rng = np.random.default_rng(0)
    span_labels = ((rng.random(seqs.mask.shape) < 0.2) & seqs.mask)
    trace_labels = rng.random(seqs.n_traces) < 0.5
    losses = []
    for _ in range(6):
        variables, opt_state, loss = step(
            variables, opt_state, seqs.categorical, seqs.continuous,
            seqs.mask, span_labels, trace_labels)
        losses.append(float(loss))
    assert losses[-1] < losses[0]


def test_param_sharding_actually_distributes():
    model = TraceTransformer(TINY)
    variables = model.init(jax.random.PRNGKey(0))
    mesh = make_mesh({"data": 2, "model": 4})
    sharded = shard_variables(variables, mesh)
    # find an attention qkv kernel: heads dim (4) split over model axis (4)
    p = sharded["params"]["encoder"]["block_0"]
    qk = None
    for k1 in p:
        if "Attention" in k1 or "attention" in k1:
            qk = p[k1]["query"]["kernel"]
    assert qk is not None
    shard_shapes = {s.data.shape for s in qk.addressable_shards}
    assert all(s[1] == 1 for s in shard_shapes)  # 4 heads / 4-way model axis


def test_ring_attention_matches_reference():
    rng = jax.random.PRNGKey(0)
    B, L, H, D = 2, 32, 2, 8  # L=32 over seq=8 -> blocks of 4
    q, k, v = (jax.random.normal(key, (B, L, H, D))
               for key in jax.random.split(rng, 3))
    mask = jnp.asarray(np.random.default_rng(0).random((B, L)) < 0.8)
    mesh = make_mesh({"seq": 8}, axes=("seq",))
    out = ring_attention(q, k, v, mask, mesh, axis_name="seq")
    ref = reference_attention(q, k, v, mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_ring_attention_fully_masked_rows_safe():
    B, L, H, D = 1, 16, 1, 4
    q = jnp.ones((B, L, H, D))
    k = jnp.ones((B, L, H, D))
    v = jnp.ones((B, L, H, D))
    mask = jnp.zeros((B, L), bool)  # nothing attends to anything
    mesh = make_mesh({"seq": 8}, axes=("seq",))
    out = ring_attention(q, k, v, mask, mesh)
    assert np.isfinite(np.asarray(out)).all()


def test_dp_packed_scoring_matches_single_device():
    """Serving-path DP (round-1 review item 7): SequenceBackend with
    data_parallel=8 scores identically to single-device on the 8-virtual-
    device CPU mesh (BASELINE config #5)."""
    from odigos_tpu.pdata import synthesize_traces
    from odigos_tpu.serving import EngineConfig, ScoringEngine
    from odigos_tpu.features import featurize

    batch = synthesize_traces(60, seed=42)
    feats = featurize(batch)
    tiny = {"d_model": 64, "n_layers": 1, "d_ff": 128, "n_heads": 2,
            "max_len": 16, "dtype": "float32"}
    from odigos_tpu.training import make_model_config

    cfg1 = EngineConfig(model="transformer", trace_bucket=64, max_len=16,
                        model_config=make_model_config("transformer", tiny),
                        seed=5)
    cfg8 = EngineConfig(model="transformer", trace_bucket=64, max_len=16,
                        model_config=make_model_config("transformer", tiny),
                        data_parallel=8, seed=5)
    b1 = ScoringEngine(cfg1).backend
    b8 = ScoringEngine(cfg8).backend
    # same seed -> same init; scores must agree across the mesh boundary
    s1 = b1.score(batch, feats)
    s8 = b8.score(batch, feats)
    assert s1.shape == s8.shape == (len(batch),)
    np.testing.assert_allclose(s1, s8, atol=1e-5, rtol=1e-4)


def test_dp_aligns_bucket_ladder_to_mesh():
    """An indivisible trace_bucket no longer refuses — the ladder lifts
    every rung to lcm(bucket, dp) so packed row groups stay
    shard-divisible by construction (ISSUE 7: dp-aligned packing)."""
    from odigos_tpu.serving import EngineConfig, ScoringEngine

    from odigos_tpu.training import make_model_config

    tiny = make_model_config("transformer", {
        "d_model": 32, "n_layers": 1, "d_ff": 64, "n_heads": 2,
        "max_len": 16, "dtype": "float32"})
    eng = ScoringEngine(EngineConfig(model="transformer", trace_bucket=100,
                                     model_config=tiny, max_len=16,
                                     data_parallel=8))
    lad = eng.backend.ladder
    assert lad.base == 200  # lcm(100, 8)
    assert all(b % 8 == 0 for b in lad.buckets)
    assert lad.align == 8


def test_dp_serving_flagship_geometry_under_load():
    """DP serving at the FLAGSHIP geometry (d_model 256, bucket 256,
    max_len 64 — round-2 review weak item 8): many uneven traces pack into
    row counts that exercise the trace_bucket % data_parallel interaction
    with pack_sequences padding, and scores must match single-device
    bit-for-bit at fp32."""
    from odigos_tpu.features import featurize
    from odigos_tpu.pdata import synthesize_traces
    from odigos_tpu.serving import EngineConfig, ScoringEngine
    from odigos_tpu.training import make_model_config

    flagship = {"d_model": 256, "n_layers": 4, "d_ff": 1024, "n_heads": 4,
                "max_len": 64, "dtype": "float32"}
    mc = make_model_config("transformer", flagship)
    cfg1 = EngineConfig(model="transformer", trace_bucket=256, max_len=64,
                        model_config=mc, seed=5)
    cfg8 = EngineConfig(model="transformer", trace_bucket=256, max_len=64,
                        model_config=mc, data_parallel=8, seed=5)
    b1 = ScoringEngine(cfg1).backend
    b8 = ScoringEngine(cfg8).backend
    # two loads: one that packs well under a bucket, one that spills over
    # a bucket boundary (rows % 256 != 0 before padding)
    for n_traces, seed in ((180, 7), (700, 8)):
        batch = synthesize_traces(n_traces, seed=seed)
        feats = featurize(batch)
        s1 = b1.score(batch, feats)
        s8 = b8.score(batch, feats)
        assert s1.shape == s8.shape == (len(batch),)
        np.testing.assert_allclose(s1, s8, atol=1e-5, rtol=1e-4)
        assert np.isfinite(s1).all()
