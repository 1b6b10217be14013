"""Featurizer + model tests (CPU backend, tiny shapes)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from odigos_tpu.features import (
    CAT_FIELDS, CONT_FIELDS, FeaturizerConfig, assemble_sequences, featurize)
from odigos_tpu.models import (
    SpanAutoencoder, TraceTransformer, TransformerConfig, ZScoreDetector)
from odigos_tpu.models.autoencoder import AutoencoderConfig
from odigos_tpu.pdata import SpanBatchBuilder, SpanKind, synthesize_traces

TINY_TF = TransformerConfig(d_model=32, n_heads=2, n_layers=1, d_ff=64,
                            max_len=16, dtype=jnp.float32)
TINY_AE = AutoencoderConfig(d_model=32, n_heads=2, n_layers=1, d_ff=64,
                            max_len=16, dtype=jnp.float32,
                            service_vocab=64, name_vocab=64)


# ------------------------------------------------------------- featurizer
def test_featurize_shapes_and_stability(demo_batch):
    f = featurize(demo_batch)
    assert f.categorical.shape == (len(demo_batch), len(CAT_FIELDS))
    assert f.continuous.shape == (len(demo_batch), len(CONT_FIELDS))
    # stable across calls (hashes deterministic)
    f2 = featurize(demo_batch)
    np.testing.assert_array_equal(f.categorical, f2.categorical)
    # vocab bounds, 0 reserved
    cfg = FeaturizerConfig()
    assert f.categorical[:, 0].max() < cfg.service_vocab
    assert (f.categorical[:, :2] > 0).all()


def test_featurize_parent_edge():
    b = SpanBatchBuilder()
    b.add_span(trace_id=1, span_id=10, name="root", service="svc-a",
               start_unix_nano=0, end_unix_nano=100)
    b.add_span(trace_id=1, span_id=11, parent_span_id=10, name="child",
               service="svc-b", start_unix_nano=10, end_unix_nano=50)
    b.add_span(trace_id=1, span_id=12, parent_span_id=999, name="orphan",
               service="svc-c", start_unix_nano=20, end_unix_nano=30)
    f = featurize(b.build())
    svc_ids = f.categorical[:, 0]
    parent_ids = f.categorical[:, 4]
    assert parent_ids[0] == 0            # root: no parent
    assert parent_ids[1] == svc_ids[0]   # child's parent edge = svc-a's id
    assert parent_ids[2] == 0            # orphan: parent not in batch
    # continuous: is_root flag
    np.testing.assert_array_equal(f.continuous[:, 1], [1.0, 0.0, 0.0])


def test_featurize_attr_slots():
    b = SpanBatchBuilder()
    b.add_span(trace_id=1, span_id=1, name="op", service="s",
               start_unix_nano=0, end_unix_nano=1,
               attrs={"http.method": "GET"})
    b.add_span(trace_id=1, span_id=2, name="op", service="s",
               start_unix_nano=0, end_unix_nano=1)
    f = featurize(b.build(), FeaturizerConfig(attr_slots=4))
    assert f.categorical.shape[1] == len(CAT_FIELDS) + 4
    assert f.categorical[0, len(CAT_FIELDS):].max() > 0  # hashed attr present
    assert f.categorical[1, len(CAT_FIELDS):].max() == 0  # no attrs


def test_assemble_sequences(demo_batch):
    f = featurize(demo_batch)
    seqs = assemble_sequences(demo_batch, f, max_len=16)
    assert seqs.n_traces == 64
    assert seqs.mask.shape == seqs.span_index.shape
    # span_index scatters every kept span exactly once
    kept = seqs.span_index[seqs.mask]
    assert len(np.unique(kept)) == len(kept)
    assert len(kept) + seqs.n_truncated == len(demo_batch)
    # features at (t, l) match the source row
    t, l = np.argwhere(seqs.mask)[0]
    row = seqs.span_index[t, l]
    np.testing.assert_array_equal(seqs.categorical[t, l], f.categorical[row])
    # within-trace ordering by start time
    starts = demo_batch.col("start_unix_nano")
    for ti in range(5):
        rows = seqs.span_index[ti][seqs.mask[ti]]
        s = starts[rows]
        assert (np.diff(s.astype(np.int64)) >= 0).all()


def test_assemble_sequences_pad_traces():
    batch = synthesize_traces(3, seed=0)
    seqs = assemble_sequences(batch, max_len=8, pad_traces_to=8)
    assert seqs.mask.shape[0] == 8
    assert not seqs.mask[3:].any()


# ---------------------------------------------------------------- zscore
def test_zscore_flags_latency_outlier():
    rng = np.random.default_rng(0)
    n = 2000
    cat = np.zeros((n, 5), np.int32)
    cat[:, 0] = 7   # one service
    cat[:, 1] = 13  # one op
    log_dur = rng.normal(5.0, 0.3, n).astype(np.float32)
    det = ZScoreDetector(n_groups=256, min_count=16)
    det.state = det.update_fn(det.state, jnp.asarray(cat),
                              jnp.asarray(log_dur))
    # normal span scores low, 10x-latency span scores high
    test_cat = cat[:2]
    test_dur = np.array([5.0, 5.0 + np.log(10)], np.float32)
    z = np.asarray(det.score_fn(det.state, jnp.asarray(test_cat),
                                jnp.asarray(test_dur)))
    assert z[0] < 2.0 and z[1] > 4.0


def test_zscore_cold_group_scores_zero():
    det = ZScoreDetector(n_groups=64, min_count=8)
    cat = np.zeros((4, 5), np.int32)
    z = np.asarray(det.score_fn(det.state, jnp.asarray(cat),
                                jnp.asarray(np.ones(4, np.float32))))
    np.testing.assert_array_equal(z, 0.0)


def test_zscore_streaming_merge_matches_batch():
    rng = np.random.default_rng(1)
    cat = np.zeros((500, 5), np.int32)
    cat[:, 0] = rng.integers(0, 4, 500)
    vals = rng.normal(3.0, 1.0, 500).astype(np.float32)
    det_a = ZScoreDetector(n_groups=128)
    det_b = ZScoreDetector(n_groups=128)
    # one-shot vs two-chunk streaming must agree
    det_a.state = det_a.update_fn(det_a.state, jnp.asarray(cat),
                                  jnp.asarray(vals))
    det_b.state = det_b.update_fn(det_b.state, jnp.asarray(cat[:200]),
                                  jnp.asarray(vals[:200]))
    det_b.state = det_b.update_fn(det_b.state, jnp.asarray(cat[200:]),
                                  jnp.asarray(vals[200:]))
    np.testing.assert_allclose(det_a.state.mean, det_b.state.mean, atol=1e-4)
    np.testing.assert_allclose(det_a.state.m2, det_b.state.m2, rtol=1e-3,
                               atol=1e-3)


# ----------------------------------------------------------- transformer
@pytest.fixture(scope="module")
def tiny_seqs():
    batch = synthesize_traces(8, seed=0)
    return assemble_sequences(batch, max_len=16)


def test_transformer_shapes(tiny_seqs):
    model = TraceTransformer(TINY_TF)
    variables = model.init(jax.random.PRNGKey(0))
    span_p, trace_p = model.score_spans(
        variables, jnp.asarray(tiny_seqs.categorical),
        jnp.asarray(tiny_seqs.continuous), jnp.asarray(tiny_seqs.mask))
    assert span_p.shape == tiny_seqs.mask.shape
    assert trace_p.shape == (tiny_seqs.n_traces,)
    assert ((span_p >= 0) & (span_p <= 1)).all()


def test_transformer_loss_decreases(tiny_seqs):
    import optax
    model = TraceTransformer(TINY_TF)
    variables = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    span_labels = jnp.asarray(
        (rng.random(tiny_seqs.mask.shape) < 0.2) & tiny_seqs.mask)
    trace_labels = jnp.asarray(rng.random(tiny_seqs.n_traces) < 0.5)
    tx = optax.adam(1e-2)
    opt_state = tx.init(variables)
    args = (jnp.asarray(tiny_seqs.categorical),
            jnp.asarray(tiny_seqs.continuous), jnp.asarray(tiny_seqs.mask),
            span_labels, trace_labels)

    @jax.jit
    def step(variables, opt_state):
        loss, grads = jax.value_and_grad(model.loss_fn)(variables, *args)
        updates, opt_state = tx.update(grads, opt_state)
        return optax.apply_updates(variables, updates), opt_state, loss

    losses = []
    for _ in range(10):
        variables, opt_state, loss = step(variables, opt_state)
        losses.append(float(loss))
    assert losses[-1] < losses[0]


def test_transformer_respects_padding(tiny_seqs):
    # scores of padded positions must not affect real-span scores: changing
    # padded features must leave masked outputs unchanged
    model = TraceTransformer(TINY_TF)
    variables = model.init(jax.random.PRNGKey(0))
    cat = jnp.asarray(tiny_seqs.categorical)
    cont = jnp.asarray(tiny_seqs.continuous)
    mask = jnp.asarray(tiny_seqs.mask)
    span_p1, trace_p1 = model.score_spans(variables, cat, cont, mask)
    cat2 = jnp.where(mask[..., None], cat, 3)  # scramble padding
    cont2 = jnp.where(mask[..., None], cont, 9.9)
    span_p2, trace_p2 = model.score_spans(variables, cat2, cont2, mask)
    np.testing.assert_allclose(np.where(tiny_seqs.mask, span_p1, 0),
                               np.where(tiny_seqs.mask, span_p2, 0),
                               atol=1e-5)
    np.testing.assert_allclose(trace_p1, trace_p2, atol=1e-5)


# ----------------------------------------------------------- autoencoder
def test_autoencoder_scores_and_training(tiny_seqs):
    import optax
    model = SpanAutoencoder(TINY_AE)
    variables = model.init(jax.random.PRNGKey(0))
    cat = jnp.asarray(tiny_seqs.categorical % 64)  # clamp to tiny vocab
    cont = jnp.asarray(tiny_seqs.continuous)
    mask = jnp.asarray(tiny_seqs.mask)
    err, trace_err = model.score_spans(variables, cat, cont, mask)
    assert err.shape == tiny_seqs.mask.shape
    assert (np.asarray(err)[~tiny_seqs.mask] == 0).all()  # padding scores 0

    tx = optax.adam(3e-3)
    opt_state = tx.init(variables)

    @jax.jit
    def step(variables, opt_state):
        loss, grads = jax.value_and_grad(model.loss_fn)(
            variables, cat, cont, mask)
        updates, opt_state = tx.update(grads, opt_state)
        return optax.apply_updates(variables, updates), opt_state, loss

    losses = []
    for _ in range(20):
        variables, opt_state, loss = step(variables, opt_state)
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.9


def test_models_init_with_attr_slots():
    # regression: init sample width must match featurizer attr_slots
    batch = synthesize_traces(4, seed=0)
    f = featurize(batch, FeaturizerConfig(attr_slots=4))
    seqs = assemble_sequences(batch, f, max_len=16)
    tf = TraceTransformer(TransformerConfig(
        attr_slots=4, d_model=32, n_heads=2, n_layers=1, d_ff=64,
        max_len=16, dtype=jnp.float32))
    v = tf.init(jax.random.PRNGKey(0))
    span_p, _ = tf.score_spans(v, jnp.asarray(seqs.categorical),
                               jnp.asarray(seqs.continuous),
                               jnp.asarray(seqs.mask))
    assert span_p.shape == seqs.mask.shape
    ae = SpanAutoencoder(AutoencoderConfig(
        attr_slots=4, d_model=32, n_heads=2, n_layers=1, d_ff=64,
        max_len=16, dtype=jnp.float32, service_vocab=64, name_vocab=64,
        attr_vocab=64))
    va = ae.init(jax.random.PRNGKey(1))
    err, _ = ae.score_spans(va, jnp.asarray(seqs.categorical % 64),
                            jnp.asarray(seqs.continuous),
                            jnp.asarray(seqs.mask))
    assert err.shape == seqs.mask.shape


def test_pad_traces_buckets_round_up():
    batch = synthesize_traces(9, seed=0)  # 9 traces, bucket of 4 -> T=12
    seqs = assemble_sequences(batch, max_len=8, pad_traces_to=4)
    assert seqs.mask.shape[0] == 12
    assert not seqs.mask[9:].any()


def test_autoencoder_bottleneck_no_identity_map():
    # with a trace-level bottleneck, corrupting one span's identity must raise
    # that span's reconstruction error after training on clean repeats
    import optax
    model = SpanAutoencoder(TINY_AE)
    variables = model.init(jax.random.PRNGKey(0))
    batch = synthesize_traces(16, seed=5)
    f = featurize(batch, FeaturizerConfig(service_vocab=64, name_vocab=64))
    seqs = assemble_sequences(batch, f, max_len=16)
    cat = jnp.asarray(seqs.categorical)
    cont = jnp.asarray(seqs.continuous)
    mask = jnp.asarray(seqs.mask)
    tx = optax.adam(3e-3)
    opt_state = tx.init(variables)

    @jax.jit
    def step(variables, opt_state):
        loss, grads = jax.value_and_grad(model.loss_fn)(
            variables, cat, cont, mask)
        updates, opt_state = tx.update(grads, opt_state)
        return optax.apply_updates(variables, updates), opt_state, loss

    for _ in range(60):
        variables, opt_state, _ = step(variables, opt_state)
    err_clean, _ = model.score_spans(variables, cat, cont, mask)
    # corrupt one real span: swap in a wrong service id + absurd duration
    t, l = map(int, np.argwhere(seqs.mask)[3])
    cat_bad = cat.at[t, l, 0].set((int(cat[t, l, 0]) + 17) % 64)
    cont_bad = cont.at[t, l, 0].set(15.0)
    err_bad, _ = model.score_spans(variables, cat_bad, cont_bad, mask)
    assert float(err_bad[t, l]) > float(err_clean[t, l]) * 1.5


def test_pack_sequences_density_and_fidelity():
    from odigos_tpu.features import pack_sequences
    batch = synthesize_traces(50, seed=3)
    f = featurize(batch)
    packed = pack_sequences(batch, f, max_len=64)
    # every span packed exactly once, no truncation
    kept = packed.span_index[packed.mask]
    assert len(kept) == len(batch)
    assert len(np.unique(kept)) == len(batch)
    # density beats naive padding substantially
    from odigos_tpu.features import assemble_sequences
    seqs = assemble_sequences(batch, f, max_len=64)
    naive_density = seqs.mask.sum() / seqs.mask.size
    assert packed.density() > naive_density * 2
    # features at packed slots match source rows
    r, l = np.argwhere(packed.mask)[7]
    row = packed.span_index[r, l]
    np.testing.assert_array_equal(packed.categorical[r, l], f.categorical[row])
    # segments within a row are contiguous and start at 1
    segs = packed.segments[0][packed.mask[0]]
    assert segs[0] == 1 and (np.diff(segs) >= 0).all()


def test_pack_sequences_splits_long_traces():
    from odigos_tpu.features import pack_sequences
    b = SpanBatchBuilder()
    for i in range(40):
        b.add_span(trace_id=5, span_id=i + 1, parent_span_id=1 if i else 0,
                   name="op", service="s", start_unix_nano=i,
                   end_unix_nano=i + 1)
    packed = pack_sequences(b.build(), max_len=16)
    kept = packed.span_index[packed.mask]
    assert len(kept) == 40  # nothing dropped; trace split into 3 chunks


def test_score_packed_matches_unpacked_attention():
    # a single trace packed alone in a row must score identically to the
    # padded path (same attention pattern)
    from odigos_tpu.features import pack_sequences
    batch = synthesize_traces(1, seed=4)
    f = featurize(batch)
    seqs = assemble_sequences(batch, f, max_len=16)
    packed = pack_sequences(batch, f, max_len=16)
    model = TraceTransformer(TINY_TF)
    v = model.init(jax.random.PRNGKey(0))
    span_p, _ = model.score_spans(v, jnp.asarray(seqs.categorical),
                                  jnp.asarray(seqs.continuous),
                                  jnp.asarray(seqs.mask))
    packed_p = model.score_packed(v, jnp.asarray(packed.categorical),
                                  jnp.asarray(packed.continuous),
                                  jnp.asarray(packed.segments),
                                  jnp.asarray(packed.positions))
    # align by span_index
    a = np.zeros(len(batch)); b_ = np.zeros(len(batch))
    a[seqs.span_index[seqs.mask]] = np.asarray(span_p)[seqs.mask]
    b_[packed.span_index[packed.mask]] = np.asarray(packed_p)[packed.mask]
    np.testing.assert_allclose(a, b_, atol=1e-5)


def test_score_packed_segment_isolation():
    # two traces packed in one row must not attend to each other: scores of
    # trace A unchanged whether B shares the row or not
    from odigos_tpu.features import pack_sequences, PackedSequences
    batch_a = synthesize_traces(1, seed=5)
    f_a = featurize(batch_a)
    pa = pack_sequences(batch_a, f_a, max_len=32)
    model = TraceTransformer(TransformerConfig(
        d_model=32, n_heads=2, n_layers=1, d_ff=64, max_len=32,
        dtype=jnp.float32))
    v = model.init(jax.random.PRNGKey(0))
    alone = model.score_packed(v, jnp.asarray(pa.categorical),
                               jnp.asarray(pa.continuous),
                               jnp.asarray(pa.segments),
                               jnp.asarray(pa.positions))
    n_a = int(pa.mask.sum())
    # hand-pack trace B after A in the same row
    cat = pa.categorical.copy(); cont = pa.continuous.copy()
    segs = pa.segments.copy(); poss = pa.positions.copy()
    k = min(32 - n_a, n_a)
    cat[0, n_a:n_a + k] = cat[0, :k]
    cont[0, n_a:n_a + k] = cont[0, :k]
    segs[0, n_a:n_a + k] = 2
    poss[0, n_a:n_a + k] = np.arange(k)
    shared = model.score_packed(v, jnp.asarray(cat), jnp.asarray(cont),
                                jnp.asarray(segs), jnp.asarray(poss))
    np.testing.assert_allclose(np.asarray(alone)[0, :n_a],
                               np.asarray(shared)[0, :n_a], atol=1e-5)


# ----------------------------- the encoder block's fusion boundary
# EncoderBlock parts its feed-forward's two products with
# jax.lax.optimization_barrier (the identity; ISSUE 30): what reaches the
# compiler, that it differentiates, and that no score moves by it.
TINY_BLOCKS = {
    "encoder": (dict(n_layers=3), 3),
    "decoder": (dict(n_layers=2, block="decoder", passes=2), 0),
}


def tiny_block_model(**over):
    return TraceTransformer(dataclasses.replace(
        TINY_TF, **{"n_layers": 3, **over}))


def without_boundary(monkeypatch):
    monkeypatch.setattr(jax.lax, "optimization_barrier", lambda x: x)


@pytest.mark.parametrize("kind", sorted(TINY_BLOCKS))
def test_lowered_program_parts_the_encoder_blocks_feed_forward(kind):
    over, want = TINY_BLOCKS[kind]
    model = tiny_block_model(**over)
    variables = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    rows = jax.ShapeDtypeStruct((4, 16), jnp.int32)
    text = jax.jit(model._score_packed_impl).lower(
        variables, jax.ShapeDtypeStruct((4, 16, 5), jnp.int32),
        jax.ShapeDtypeStruct((4, 16, 3), jnp.float32), rows,
        rows).as_text(debug_info=True)
    assert text.count("stablehlo.optimization_barrier") == want
    named = [line for line in text.splitlines()
             if line.startswith("#loc") and "optimization_barrier" in line]
    assert len(named) == want
    assert all('/mlp/optimization_barrier"' in line for line in named)


def test_boundary_differentiates_as_the_identity(tiny_seqs, monkeypatch):
    rng = np.random.default_rng(0)
    args = (jnp.asarray(tiny_seqs.categorical),
            jnp.asarray(tiny_seqs.continuous), jnp.asarray(tiny_seqs.mask),
            jnp.asarray((rng.random(tiny_seqs.mask.shape) < 0.2)
                        & tiny_seqs.mask),
            jnp.asarray(rng.random(tiny_seqs.n_traces) < 0.5))
    model = tiny_block_model()
    variables = model.init(jax.random.PRNGKey(0))
    grad = jax.jit(jax.grad(model.loss_fn))
    assert "optimization_barrier" in grad.lower(variables, *args).as_text()
    with_it = grad(variables, *args)
    without_boundary(monkeypatch)
    grad = jax.jit(jax.grad(tiny_block_model().loss_fn))
    assert "optimization_barrier" not in grad.lower(
        variables, *args).as_text()
    without = grad(variables, *args)
    moved = 0.0
    for got, want in zip(jax.tree.leaves(with_it), jax.tree.leaves(without)):
        assert np.isfinite(got).all()
        np.testing.assert_array_equal(got, want)
        moved += float(jnp.abs(got).sum())
    assert moved > 0


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_served_scores_do_not_move_with_the_boundary(dtype, monkeypatch):
    from odigos_tpu.serving import EngineConfig, ScoringEngine

    def served():
        eng = ScoringEngine(EngineConfig(
            model="transformer", max_len=16, trace_bucket=8,
            bucket_ladder=2, model_config=dataclasses.replace(
                TINY_TF, n_layers=3, dtype=dtype))).start()
        try:
            return [eng.score_sync(synthesize_traces(n, seed=n),
                                   timeout_s=120.0) for n in (6, 20)]
        finally:
            eng.shutdown()

    with_it = served()
    without_boundary(monkeypatch)
    for got, want in zip(with_it, served()):
        assert len(got) and np.isfinite(got).all()
        np.testing.assert_array_equal(got, want)


class TestQuantizedScorer:
    """int8 W8A8 serving path (models/quantized.py): parity with the float
    path on the same checkpoint, and engine integration."""

    def test_score_parity_with_float_path(self):
        import jax
        import jax.numpy as jnp

        from odigos_tpu.features import featurize, pack_sequences
        from odigos_tpu.models import TraceTransformer, TransformerConfig
        from odigos_tpu.models.quantized import QuantizedTraceScorer
        from odigos_tpu.pdata import synthesize_traces

        model = TraceTransformer(TransformerConfig(
            d_model=128, d_ff=256, n_layers=2, dtype=jnp.float32))
        variables = model.init(jax.random.PRNGKey(0))
        batch = synthesize_traces(64, seed=3)
        feats = featurize(batch)
        p = pack_sequences(batch, feats, max_len=32, pad_rows_to=32)
        args = (jnp.asarray(p.categorical), jnp.asarray(p.continuous),
                jnp.asarray(p.segments), jnp.asarray(p.positions))
        f = np.asarray(model.score_packed(variables, *args))
        q = np.asarray(QuantizedTraceScorer(model, variables)
                       .score_packed(*args))
        m = p.mask
        assert np.abs(f[m] - q[m]).max() < 0.05, \
            "int8 probabilities diverge from float path"

    def test_engine_quantized_flag(self):
        from odigos_tpu.pdata import synthesize_traces
        from odigos_tpu.serving import EngineConfig, ScoringEngine

        eng = ScoringEngine(EngineConfig(
            model="transformer", quantized=True, max_len=32,
            trace_bucket=32)).start()
        try:
            batch = synthesize_traces(20, seed=1)
            scores = eng.score_sync(batch, timeout_s=120.0)
            assert scores is not None and len(scores) == len(batch)
            assert ((scores >= 0) & (scores <= 1)).all()
        finally:
            eng.shutdown()

    def test_quantized_flag_refused_for_other_models(self):
        import pytest as _pytest

        from odigos_tpu.serving import EngineConfig, ScoringEngine

        with _pytest.raises(ValueError, match="transformer"):
            ScoringEngine(EngineConfig(model="autoencoder",
                                       quantized=True))
