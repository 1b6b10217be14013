"""Trace transformer classifier — the flagship model (BASELINE config #5).

DeepTraLog-style: a bidirectional transformer over the span sequence of one
trace, emitting a per-span anomaly logit and a per-trace logit (masked
mean-pool head). Trained supervised on injected-fault traces
(odigos_tpu.train.faults), served by the scoring engine at ≥1M spans/s/chip
in bfloat16, data-parallel across the mesh (odigos_tpu.parallel).

Default dims are MXU-shaped: d_model 256, d_ff 1024, heads 4 — all multiples
of the 128-lane tile.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn

from . import jitstats
from .layers import BLOCK_PARTS, Encoder, LoopedDecoder

# Shape-bucketing strategy per jitted scoring entry point (the package
# hygiene test asserts every jit path in models/ and parallel/ declares
# one — an undeclared path is an unbounded-recompile hazard at serving
# rates). Values are documentation; the mechanisms live where named.
SHAPE_BUCKETING = {
    "score_spans": "leading trace axis padded by the engine's BucketLadder "
                   "(serving.engine) or a fixed trace_bucket multiple; "
                   "L/C fixed by TransformerConfig",
    "score_packed": "packed row axis padded by BucketLadder.round_rows "
                    "(geometric ladder over trace_bucket, warmed at "
                    "engine start); L/C fixed by TransformerConfig",
}


@dataclass(frozen=True)
class TransformerConfig:
    service_vocab: int = 512
    name_vocab: int = 2048
    attr_vocab: int = 4096
    attr_slots: int = 0  # must match FeaturizerConfig.attr_slots
    d_model: int = 256
    n_heads: int = 4
    n_layers: int = 4
    d_ff: int = 1024
    max_len: int = 64
    dtype: Any = jnp.bfloat16
    # the block kind (layers.BLOCK_PARTS). "encoder": pre-LN, bidirectional,
    # a learned table of max_len positions. "decoder": sandwich RMS norms,
    # rotary positions (no table: max_len bounds the row, not the model),
    # causal within a trace, SwiGLU, no biases, the stack of n_layers run
    # ``passes`` times over the same parameters with the final norm
    # closing every pass. The three keys below are the decoder block's.
    block: str = "encoder"
    passes: int = 1
    rope_theta: float = 10000.0
    norm_eps: float = 1e-6

    def __post_init__(self) -> None:
        if self.block not in BLOCK_PARTS:
            raise ValueError(f"unknown block kind {self.block!r} "
                             f"(known: {sorted(BLOCK_PARTS)})")
        if self.passes < 1 or (self.block == "encoder" and self.passes != 1):
            raise ValueError(f"passes {self.passes!r} with block "
                             f"{self.block!r}: only the decoder block's "
                             f"stack is looped, and at least once")

    @property
    def layer_applications(self) -> int:
        """Blocks a span passes through in one scoring call."""
        return self.passes * self.n_layers


class _TraceTransformerModule(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, categorical, continuous, mask, deterministic=True,
                 positions=None, segments=None):
        c = self.cfg
        if c.block == "encoder":
            backbone = Encoder(c.service_vocab, c.name_vocab, c.attr_vocab,
                               c.d_model, c.n_heads, c.n_layers, c.d_ff,
                               c.max_len, c.dtype, name="encoder")
        else:
            backbone = LoopedDecoder(
                c.service_vocab, c.name_vocab, c.attr_vocab, c.d_model,
                c.n_heads, c.n_layers, c.d_ff, c.passes, c.rope_theta,
                c.norm_eps, c.dtype, name="encoder")
        h = backbone(categorical, continuous, mask, deterministic,
                     positions=positions, segments=segments)
        with jax.named_scope("head"):
            span_logit = nn.Dense(1, dtype=jnp.float32,
                                  name="span_head")(h)[..., 0]
            denom = jnp.maximum(mask.sum(-1, keepdims=True), 1)
            pooled = (h * mask[..., None].astype(h.dtype)).sum(-2) / denom.astype(h.dtype)
            trace_logit = nn.Dense(1, dtype=jnp.float32,
                                   name="trace_head")(pooled)[..., 0]
        return span_logit, trace_logit


class TraceTransformer:
    """Functional wrapper: init / apply / score / loss, all jit-friendly.

    The scoring entrypoint ``score_spans`` is what __graft_entry__.entry()
    exposes to the driver.
    """

    def __init__(self, config: TransformerConfig | None = None):
        self.cfg = config or TransformerConfig()
        self.module = _TraceTransformerModule(self.cfg)
        # packed-rows scoring (features.pack_sequences): block-diagonal
        # attention per trace chunk; returns (R, L) span probabilities.
        # Jitted per instance (it closes over this model's module), and
        # the jit object itself is the public entry so callers can also
        # lower it (models/costmodel.py). Inputs are NOT donated: on the
        # v5e XLA refused three of the four packed buffers (shapes differ
        # from the output) and the fourth bought nothing measurable.
        score_packed = jax.jit(self._score_packed_impl)
        self.score_packed = jitstats.track_jit("transformer.score_packed",
                                               score_packed)

    def init(self, rng: jax.Array, sample_cat=None, sample_cont=None,
             sample_mask=None):
        c = self.cfg
        if sample_cat is None:
            from ..features.featurizer import CAT_FIELDS, CONT_FIELDS
            width = len(CAT_FIELDS) + c.attr_slots
            sample_cat = jnp.zeros((1, c.max_len, width), jnp.int32)
            sample_cont = jnp.zeros((1, c.max_len, len(CONT_FIELDS)),
                                    jnp.float32)
            sample_mask = jnp.ones((1, c.max_len), bool)
        return self.module.init(rng, sample_cat, sample_cont, sample_mask)

    def apply(self, variables, categorical, continuous, mask,
              deterministic=True):
        return self.module.apply(variables, categorical, continuous, mask,
                                 deterministic)

    @partial(jax.jit, static_argnums=0)
    def score_spans(self, variables, categorical, continuous, mask):
        """(T, L) per-span anomaly probability + (T,) per-trace probability."""
        span_logit, trace_logit = self.apply(
            variables, categorical, continuous, mask)
        with jax.named_scope("head"):
            return jax.nn.sigmoid(span_logit), jax.nn.sigmoid(trace_logit)

    def _score_packed_impl(self, variables, categorical, continuous,
                           segments, positions):
        """The traced body of ``score_packed``. The per-row trace head is
        meaningless under packing and skipped."""
        mask = segments > 0
        span_logit, _ = self.module.apply(
            variables, categorical, continuous, mask,
            positions=positions, segments=segments)
        with jax.named_scope("head"):
            return jax.nn.sigmoid(span_logit)

    def loss_fn(self, variables, categorical, continuous, mask,
                span_labels, trace_labels, rngs=None):
        """Masked BCE on spans + BCE on traces (equal weight)."""
        span_logit, trace_logit = self.module.apply(
            variables, categorical, continuous, mask, deterministic=rngs is None,
            rngs=rngs)
        span_bce = optax_sigmoid_bce(span_logit, span_labels)
        m = mask.astype(jnp.float32)
        span_loss = (span_bce * m).sum() / jnp.maximum(m.sum(), 1.0)
        # all-padding rows (dp padding, trace-count buckets) must not train
        # the trace head: weight by per-trace validity
        valid = mask.any(-1).astype(jnp.float32)
        trace_bce = optax_sigmoid_bce(trace_logit, trace_labels)
        trace_loss = (trace_bce * valid).sum() / jnp.maximum(valid.sum(), 1.0)
        return span_loss + trace_loss


# compile accounting for the class-level jitted scoring entry (shared by
# every instance; __dict__ access skips any descriptor binding)
jitstats.track_jit("transformer.score_spans",
                   TraceTransformer.__dict__["score_spans"])


def optax_sigmoid_bce(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Numerically-stable sigmoid binary cross-entropy."""
    labels = labels.astype(jnp.float32)
    return jnp.maximum(logits, 0) - logits * labels + jnp.log1p(
        jnp.exp(-jnp.abs(logits)))
