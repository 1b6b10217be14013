"""The encoder block's attention over packed rows as one Pallas kernel
(``models/attention_kernel.py``), run here in the Pallas interpreter: the
kernel against flax's attention on packed rows, the block's TPU branch
against flax's whole sublayer, a row that holds no span finite, the
parameter tree and the CPU's numbers left as flax's, the shapes the
kernel takes and the ones it sends to XLA, the decoder programs without
it, and what a ``tpu/score`` span says of it. The compiles for a described
v5e are in ``test_moe_decoder.py``, with the other compiles for the chip."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

from benchmark.tests.conftest import arch_case
from odigos_tpu.models import attention_kernel, layers
from odigos_tpu.models.transformer import TraceTransformer, TransformerConfig
from odigos_tpu.pdata import synthesize_traces
from odigos_tpu.serving import EngineConfig, ScoringEngine
from odigos_tpu.training import make_model_config

L = 64


def packed_rows(rows: int, L: int = L) -> np.ndarray:
    """Segments of ``rows`` packed rows: several traces in a row, a row of
    one span, a row padded after 23 spans, a row of one trace filling it,
    and empty rows from the fifth on."""
    seg = np.zeros((rows, L), np.int32)
    cuts = [0, L // 4, L // 2 + 3, L - 5]
    for i, (a, b) in enumerate(zip(cuts, cuts[1:] + [L])):
        seg[0, a:b] = i + 1
    seg[1, 0] = 1
    seg[2, :23] = 2
    seg[2, :9] = 1
    if rows > 3:
        seg[3, :] = 7
    return seg


def flax_core(q, k, v, seg, heads):
    """flax's attention in float32 over the same rows and mask."""
    T, L, D = q.shape
    real = seg > 0
    mask = ((seg[..., None] == seg[..., None, :]) & real[..., None]
            & real[..., None, :])[:, None]
    split = [jnp.asarray(a, jnp.float32).reshape(T, L, heads, D // heads)
             for a in (q, k, v)]
    return np.asarray(nn.dot_product_attention(*split, mask=mask)
                      ).reshape(T, L, D)


@pytest.mark.parametrize("heads, d_head, rows, L", [
    (16, 80, 6, 64),     # vit-h14's heads: a head across two lane tiles
    (4, 64, 6, 64),      # the flagship's
    (2, 64, 8, 32),      # rows of 32: four rows a group of 128
])
def test_the_kernel_equals_flax_attention_on_packed_rows(heads, d_head,
                                                         rows, L):
    D = heads * d_head
    r = np.random.default_rng(heads * rows)
    q, k, v = (jnp.asarray(r.normal(size=(rows, L, D)), jnp.bfloat16)
               for _ in range(3))
    seg = packed_rows(rows, L)
    out = np.asarray(attention_kernel.packed_attention(
        q, k, v, jnp.asarray(seg), heads, interpret=True), np.float32)
    want = flax_core(q, k, v, seg, heads)
    real = seg > 0
    assert np.isfinite(out).all()
    np.testing.assert_allclose(out[real], want[real], atol=3e-2, rtol=0)
    tb = attention_kernel.block_rows(rows, L, D)
    # a block of rows that holds no span writes zeros
    live = real.reshape(rows // tb, -1).any(axis=1)
    for b in np.flatnonzero(~live):
        assert (out[b * tb:(b + 1) * tb] == 0).all()
    assert (~live).any() == (rows == 6)


@pytest.mark.parametrize("heads, d_head", [(16, 80), (4, 64)])
def test_the_tpu_branch_is_flax_attention_within_bfloat16(heads, d_head):
    """``attention_kernel.self_attention`` (projections as one product
    each, the kernel, the output projection) over the parameters flax's
    ``MultiHeadDotProductAttention`` made, against that module."""
    D, rows = heads * d_head, 4
    r = np.random.default_rng(d_head)
    h = jnp.asarray(r.normal(size=(rows, L, D)), jnp.bfloat16)
    seg = jnp.asarray(packed_rows(rows))
    mha = nn.MultiHeadDotProductAttention(num_heads=heads,
                                          dtype=jnp.bfloat16)
    params = mha.init(jax.random.PRNGKey(1), h[:1], h[:1])["params"]
    mask = ((seg[..., None] == seg[..., None, :]) & (seg > 0)[..., None]
            & (seg > 0)[..., None, :])[:, None]
    want = np.asarray(mha.apply({"params": params}, h, h, mask=mask),
                      np.float32)
    got = np.asarray(attention_kernel.self_attention(
        h, seg, params, heads=heads, dtype=jnp.bfloat16,
        interpret=True), np.float32)
    real = np.asarray(seg) > 0
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got[real], want[real],
                               atol=0.02 * np.abs(want).max(), rtol=0)


def tiny(**over) -> TraceTransformer:
    return TraceTransformer(TransformerConfig(**{
        "d_model": 256, "n_heads": 4, "n_layers": 2, "d_ff": 512,
        "max_len": L, **over}))


def packed_args(rows: int, seed: int = 0):
    r = np.random.default_rng(seed)
    seg = packed_rows(rows)
    pos = np.where(seg > 0, np.arange(L), 0).astype(np.int32)
    return (jnp.asarray(r.integers(0, 4, (rows, L, 5)), jnp.int32),
            jnp.asarray(r.normal(size=(rows, L, 3)), jnp.float32),
            jnp.asarray(seg), jnp.asarray(pos))


def take_tpu_branch(monkeypatch):
    """``jax.lax.platform_dependent`` taking its TPU branch, the kernel in
    the interpreter: what the chip runs, here."""
    def tpu(*args, tpu, default):
        return tpu(*args, interpret=True)
    monkeypatch.setattr(jax.lax, "platform_dependent", tpu)


def test_trace_logits_stay_finite_with_trailing_empty_rows(monkeypatch):
    """The whole encoder with the kernel: rows 4 to 7 hold no span (their
    block is skipped and reads zeros), and the trace head, which pools
    ``h * mask`` over every row, stays finite; the real spans score as
    flax's attention scores them, within bfloat16."""
    model = tiny()
    variables = model.init(jax.random.PRNGKey(5))
    cat, cont, seg, pos = packed_args(8)
    mask = seg > 0

    def apply():
        return model.module.apply(variables, cat, cont, mask,
                                  positions=pos, segments=seg)

    flax_span, _ = apply()
    take_tpu_branch(monkeypatch)
    span, trace = apply()
    assert np.isfinite(np.asarray(span)).all()
    assert np.isfinite(np.asarray(trace)).all()
    real = np.asarray(mask)
    np.testing.assert_allclose(np.asarray(span)[real],
                               np.asarray(flax_span)[real], atol=0.1)


class ParentBlock(nn.Module):
    """The encoder block as it was before the kernel: flax's attention
    over the mask, the same feed-forward."""

    d_model: int
    n_heads: int
    d_ff: int
    dtype: object = jnp.bfloat16

    @nn.compact
    def __call__(self, x, attn_mask):
        with jax.named_scope("attn"):
            h = nn.LayerNorm(dtype=self.dtype)(x)
            h = nn.MultiHeadDotProductAttention(
                num_heads=self.n_heads, dtype=self.dtype,
                dropout_rate=0.0, deterministic=True,
            )(h, h, mask=attn_mask)
            x = x + h
        with jax.named_scope("mlp"):
            h = nn.LayerNorm(dtype=self.dtype)(x)
            h = nn.Dense(self.d_ff, dtype=self.dtype)(h)
            h = jax.lax.optimization_barrier(h)
            h = nn.gelu(h)
            h = nn.Dense(self.d_model, dtype=self.dtype)(h)
            return x + h


@pytest.fixture(scope="module", params=[(1280, 16), (256, 4)])
def two_blocks(request):
    d, heads = request.param
    seg = jnp.asarray(packed_rows(6))
    x = jnp.asarray(np.random.default_rng(d).normal(size=(6, L, d)),
                    jnp.float32)
    mask = ((seg[..., None] == seg[..., None, :]) & (seg > 0)[..., None]
            & (seg > 0)[..., None, :])[:, None]
    new = layers.EncoderBlock(d, heads, 256)
    old = ParentBlock(d, heads, 256)
    key = jax.random.PRNGKey(11)
    return (new, new.init(key, x, mask, True, seg),
            old, old.init(key, x, mask), x, mask, seg)


def test_the_parameter_tree_is_flax_attentions(two_blocks):
    """Paths, shapes, dtypes and the values the same key draws: what the
    reference's weight pairs, the sharding rules and a checkpoint read."""
    _, new_vars, _, old_vars, *_ = two_blocks
    new = jax.tree_util.tree_leaves_with_path(new_vars)
    old = jax.tree_util.tree_leaves_with_path(old_vars)
    assert [(jax.tree_util.keystr(p), a.shape, a.dtype) for p, a in new] \
        == [(jax.tree_util.keystr(p), a.shape, a.dtype) for p, a in old]
    assert all(np.array_equal(a, b) for (_, a), (_, b) in zip(new, old))
    assert "MultiHeadDotProductAttention_0" in new_vars["params"]


def test_the_cpus_dispatch_is_flax_attention_bit_for_bit(two_blocks):
    new, new_vars, old, old_vars, x, mask, seg = two_blocks
    assert layers.kernel_attends(jnp.bfloat16, 6, L, x.shape[-1],
                                 new.n_heads)
    got = new.apply(new_vars, x, mask, True, seg)
    want = old.apply(old_vars, x, mask)
    assert np.array_equal(np.asarray(got), np.asarray(want))


def lowered_for_tpu(model: TraceTransformer, rows: int, L: int = L,
                    segments: bool = True) -> str:
    """The packed scoring program (or the unpacked ``apply`` where
    ``segments`` is False) lowered for the TPU, abstract parameters: no
    chip and no compiler needed."""
    variables = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    S = jax.ShapeDtypeStruct
    cat, cont = S((rows, L, 5), jnp.int32), S((rows, L, 3), jnp.float32)
    row = S((rows, L), jnp.int32)
    if segments:
        fn = model._score_packed_counted_impl if model.cfg.routed \
            else model._score_packed_impl
        args = (variables, cat, cont, row, row)
    else:
        fn, args = model.apply, (variables, cat, cont, S((rows, L), bool))
    return jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text(debug_info=True)


@pytest.mark.parametrize("over", [
    dict(), dict(d_model=1280, n_heads=16, n_layers=3)])
def test_the_encoder_lowers_one_kernel_a_layer_under_attn(over):
    """Lowered for the TPU, every layer calls the kernel's sublayer
    (``attention_kernel.self_attention``) from its ``attn`` scope, and the
    layers share one lowering of it (one Pallas body)."""
    model = tiny(**over)
    text = lowered_for_tpu(model, 8)
    assert text.count("tpu_custom_call") == 1
    calls = [line for line in text.splitlines()
             if line.lstrip().startswith("%")
             and "func.call @self_attention(" in line]
    assert len(calls) == model.cfg.n_layers
    scoped = [line for line in text.splitlines()
              if line.startswith("#loc") and "jit(self_attention)" in line]
    assert len(scoped) == model.cfg.n_layers
    assert all("/attn/" in line for line in scoped)


@pytest.mark.parametrize("over, rows, L_, segments", [
    (dict(dtype=jnp.float32), 8, L, True),
    (dict(d_model=192, n_heads=3), 8, L, True),      # no whole lane tiles
    (dict(max_len=48), 8, 48, True),                  # a trace would cross
    (dict(), 3, L, True),                             # no whole groups
    (dict(), 8, L, False),                            # unpacked rows
], ids=["float32", "width", "row_length", "odd_rows", "unpacked"])
def test_the_predicate_sends_other_shapes_to_xla(over, rows, L_, segments):
    """Each input the kernel does not take keeps XLA's attention: no
    Pallas call in the program lowered for the TPU."""
    model = tiny(**over)
    c = model.cfg
    assert not (segments and layers.kernel_attends(
        c.dtype, rows, L_, c.d_model, c.n_heads))
    assert "tpu_custom_call" not in lowered_for_tpu(model, rows, L_,
                                                    segments)


def test_a_mesh_that_splits_more_than_rows_keeps_xla():
    """Each device attends its own rows under ``mesh {data: n}``; a
    ``model`` axis over 1 splits the heads, which a Pallas kernel cannot
    take."""
    assert layers.kernel_attends(jnp.bfloat16, 8, L, 256, 4,
                                 {"data": 2, "model": 1})
    assert not layers.kernel_attends(jnp.bfloat16, 8, L, 256, 4,
                                     {"data": 2, "model": 2})
    # 8 rows over 8 devices: one row of 64 each, no whole group of 128
    assert not layers.kernel_attends(jnp.bfloat16, 8, L, 256, 4,
                                     {"data": 8})


@pytest.mark.parametrize("arch", [
    "looped_decoder", "moe_decoder", "latent_moe_decoder"])
def test_a_decoder_blocks_program_holds_no_attention_kernel(arch):
    """The decoder blocks keep ``layers.attention`` (XLA's): lowered for the
    TPU, no ``packed_attention`` call, whatever Pallas kernels their route
    has."""
    small = {**arch_case(arch)[1].SMALL, "dtype": "bfloat16"}
    model = TraceTransformer(make_model_config("transformer", small))
    text = lowered_for_tpu(model, 8, small["max_len"])
    assert "packed_attention" not in text
    assert model.attention_blocks(8, small["max_len"], "tpu") is None


def test_rows_skipped_counts_the_blocks_that_hold_no_span():
    # vit-h14's paced call: 110 real rows of the 256-row rung, blocks of 8
    assert attention_kernel.block_rows(256, L, 1280) == 8
    assert attention_kernel.rows_skipped(256, 110, 8) == 256 - 112
    assert attention_kernel.rows_skipped(512, 512, 8) == 0
    assert attention_kernel.rows_skipped(512, 0, 8) == 512
    # each of two devices its own 128 rows: 110 real on the first
    assert attention_kernel.rows_skipped(256, 110, 8, shards=2) \
        == (128 - 112) + 128
    model = tiny(d_model=1280, n_heads=16)
    assert model.attention_blocks(256, L, "tpu") == (8, 1)
    assert model.attention_blocks(256, L, "tpu", {"data": 2}) == (8, 2)
    assert model.attention_blocks(256, L, "cpu") is None


def test_score_spans_say_whether_the_kernel_ran_and_what_it_skipped():
    """On the CPU the program holds no kernel, and every ``tpu/score``
    span of the packed route says so."""
    from odigos_tpu.selftelemetry.tracer import tracer

    eng = ScoringEngine(EngineConfig(
        model="transformer", model_config=tiny().cfg, max_len=L,
        trace_bucket=8, bucket_ladder=2)).start()
    try:
        tracer.ring.drain()
        for seed in (1, 2):
            b = synthesize_traces(12, seed=seed)
            assert len(eng.score_sync(b, timeout_s=60.0)) == len(b)
        spans = [s for s in tracer.ring.snapshot() if s.name == "tpu/score"]
        assert len(spans) == 2
        for sp in spans:
            assert sp.attrs["attn.kernel"] is False
            assert sp.attrs["attn.rows_skipped"] == 0
            assert "rows.real" in sp.attrs
    finally:
        eng.shutdown()
