"""Trace transformer classifier — the flagship model (BASELINE config #5).

DeepTraLog-style: a bidirectional transformer over the span sequence of one
trace, emitting a per-span anomaly logit and a per-trace logit (masked
mean-pool head). Trained supervised on injected-fault traces
(odigos_tpu.train.faults), served by the scoring engine at ≥1M spans/s/chip
in bfloat16, data-parallel across the mesh (odigos_tpu.parallel).

Default dims are MXU-shaped: d_model 256, d_ff 1024, heads 4 — all multiples
of the 128-lane tile.

``TransformerConfig.block`` picks one of five block kinds (``layers.py``
``BLOCK_PARTS``): the default pre-LN bidirectional ``encoder``; the
``decoder`` block whose stack is looped ``passes`` times on the device; the
routed ``moe`` block (grouped query heads, rotary positions and a
window layer by layer, a router ahead of attention over ``n_experts``
experts of which a span takes ``experts_per_span``, parameters held in
``param_dtype``); and the latent routed ``latent_moe`` block (latent
attention through ``q_rank`` and ``kv_rank`` with one rotary key a span, a
sigmoid router with a selection bias over SiLU-gated experts beside
``shared_experts`` shared ones, the first ``dense_layers`` layers dense at
``d_ff``); and the post-norm routed ``postnorm_moe`` block (an RMS norm
on each sublayer's output and none ahead of it, grouped query heads whose
queries and keys are normed head by head, rotary and a window layer by
layer, the latent block's sigmoid router, shared expert and dense leading
layers, and a device that holds ``experts_held`` of the router's
``n_experts`` from ``first_expert_held`` on). A routed kind's
``score_packed_counted`` also returns what the router assigned and how
many rows its route moved.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn

from . import attention_kernel, jitstats
from .layers import (BLOCK_PARTS, ROWS_AXIS, Encoder, LatentMoeDecoder,
                     LoopedDecoder, MoeDecoder, PostNormMoeDecoder,
                     kernel_attends)

# Shape-bucketing strategy per jitted scoring entry point (the package
# hygiene test asserts every jit path in models/ and parallel/ declares
# one — an undeclared path is an unbounded-recompile hazard at serving
# rates). Values are documentation; the mechanisms live where named.
SHAPE_BUCKETING = {
    "score_packed_counted": "a routed block's score_packed with its "
                            "counts: the same rows, the same ladder",
    "score_spans": "leading trace axis padded by the engine's BucketLadder "
                   "(serving.engine) or a fixed trace_bucket multiple; "
                   "L/C fixed by TransformerConfig",
    "score_packed": "packed row axis padded by BucketLadder.round_rows "
                    "(geometric ladder over trace_bucket, warmed at "
                    "engine start); L/C fixed by TransformerConfig",
}


# expert assignments of real spans, as a routed model's own top-k counted
# them on the device (spans x experts a span x layers a call); of a
# device that holds a share of a layer's experts, those its experts took
EXPERT_ASSIGNMENTS_METRIC = "odigos_anomaly_expert_assignments_total"
# every choice the router made for real spans, held here or not (spans x
# experts a span x routed layers), where the device holds a share
EXPERT_CHOICES_METRIC = "odigos_anomaly_expert_choices_total"


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
    # closing every pass. "moe": the routed block (pre-norm RMS residuals,
    # causal within a trace, no biases, each layer applied once): n_heads
    # query heads of head_dim over n_kv_heads key/value heads, a router
    # ahead of attention sending each span to experts_per_span of
    # n_experts ReLU-gated experts d_expert wide (d_ff is unused), layer i
    # rotating its queries and keys where rope_layout[i] and cutting its
    # attention to the last ``window`` spans where window_layout[i], the
    # parameters held in param_dtype. "latent_moe": the latent routed
    # block (pre-norm RMS residuals, causal within a trace, each layer
    # applied once and every layer rotary): n_heads heads whose queries
    # come through a q_rank chain and whose keys and values through one
    # kv_rank latent a span, a head's query and key qk_nope_dim unrotated
    # columns beside qk_rope_dim rotary ones (the rotary key one a span,
    # shared by the heads), its value v_dim wide; the first dense_layers
    # layers with a SwiGLU d_ff wide, the rest routing each span by
    # sigmoid scores plus a selection bias to experts_per_span of
    # n_experts SiLU-gated experts d_expert wide, weighted by the unbiased
    # scores normalised and times route_scale, beside shared_experts
    # experts every span takes. "postnorm_moe": the post-norm routed block
    # (no norm ahead of a sublayer, an RMS norm on each sublayer's output
    # before the residual add, causal within a trace, each layer applied
    # once): the routed block's grouped heads, rope_layout, window_layout
    # and window, each head's query and key RMS-normed over its head_dim
    # columns before the rotary (a layer without rotary has no
    # positions), the latent block's dense_layers, d_ff, sigmoid router
    # with its selection bias over n_experts, route_scale and
    # shared_experts, the router reading the raw residual stream; this
    # device holds experts_held of the n_experts (0: all of them) from
    # first_expert_held on, and computes the part of the feed-forward
    # they give (expert parallelism's share of a layer); weight_seed, where
    # set, is the one key every weight is drawn from, whatever key init is
    # handed: a deployment that serves one checkpoint, not a draw a run.
    # passes is the
    # decoder block's; rope_theta and norm_eps are the decoder and the
    # routed blocks'; _BLOCK_KEYS says which of the keys from n_kv_heads
    # down each routed block takes.
    block: str = "encoder"
    passes: int = 1
    rope_theta: float = 10000.0
    norm_eps: float = 1e-6
    n_kv_heads: int = 0
    head_dim: int = 0
    n_experts: int = 0
    experts_per_span: int = 0
    d_expert: int = 0
    rope_layout: tuple[int, ...] = ()
    window_layout: tuple[int, ...] = ()
    window: int = 0
    param_dtype: Any = jnp.float32
    q_rank: int = 0
    kv_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_dim: int = 0
    shared_experts: int = 0
    dense_layers: int = 0
    route_scale: float = 1.0
    experts_held: int = 0
    first_expert_held: int = 0
    weight_seed: int | None = None

    def __post_init__(self) -> None:
        if self.block not in BLOCK_PARTS:
            raise ValueError(f"unknown block kind {self.block!r} "
                             f"(known: {sorted(BLOCK_PARTS)})")
        if self.passes < 1 or (self.block != "decoder" and self.passes != 1):
            raise ValueError(f"passes {self.passes!r} with block "
                             f"{self.block!r}: only the decoder block's "
                             f"stack is looped, and at least once")
        for key in ("rope_layout", "window_layout"):  # hashable from JSON
            object.__setattr__(self, key, tuple(getattr(self, key)))
        stray = {k: getattr(self, k) for k, blocks in _BLOCK_KEYS.items()
                 if self.block not in blocks
                 and getattr(self, k) != getattr(type(self), k)}
        if stray:
            kinds = sorted({b for k in stray for b in _BLOCK_KEYS[k]})
            raise ValueError(f"{stray} with block {self.block!r}: these "
                             f"keys are the routed block's "
                             f"({', '.join(kinds)})")
        faults = {"moe": self._moe_faults,
                  "latent_moe": self._latent_moe_faults,
                  "postnorm_moe": self._postnorm_moe_faults}.get(
            self.block, list)()
        if faults:
            raise ValueError(f"block {self.block!r} does not compose: "
                             + "; ".join(faults))

    def _moe_faults(self) -> list[str]:
        return self._grouped_faults() + self._expert_faults()

    def _grouped_faults(self) -> list[str]:
        """Of grouped query heads laid out layer by layer (rotary and
        window), what does not compose."""
        faults = []
        if not (self.n_kv_heads > 0 and self.head_dim > 0
                and self.n_heads % max(self.n_kv_heads, 1) == 0):
            faults.append(f"n_heads {self.n_heads} is not a multiple of "
                          f"n_kv_heads {self.n_kv_heads}, or head_dim "
                          f"{self.head_dim} is not set")
        if self.head_dim % 2:
            faults.append(f"head_dim {self.head_dim} is odd: rotary "
                          f"positions pair its columns")
        if not len(self.rope_layout) == len(self.window_layout) \
                == self.n_layers:
            faults.append(f"rope_layout ({len(self.rope_layout)}) and "
                          f"window_layout ({len(self.window_layout)}) "
                          f"each state all n_layers {self.n_layers}")
        if any(self.window_layout) and self.window < 1:
            faults.append(f"window {self.window} with a layer that has one")
        return faults

    def _expert_faults(self) -> list[str]:
        if 0 < self.experts_per_span <= self.n_experts \
                and self.d_expert >= 1:
            return []
        return [f"experts_per_span {self.experts_per_span} of n_experts "
                f"{self.n_experts}, d_expert {self.d_expert}"]

    def _latent_moe_faults(self) -> list[str]:
        faults = []
        sizes = {k: getattr(self, k) for k in (
            "q_rank", "kv_rank", "qk_nope_dim", "qk_rope_dim", "v_dim")}
        if min(sizes.values()) < 1:
            faults.append(f"latent attention needs every one of {sizes} "
                          f"set")
        if self.qk_rope_dim % 2:
            faults.append(f"qk_rope_dim {self.qk_rope_dim} is odd: rotary "
                          f"positions pair its columns")
        return faults + self._sigmoid_faults()

    def _sigmoid_faults(self) -> list[str]:
        """Of a stack of leading dense layers and routed ones under the
        sigmoid router beside shared experts, what does not compose."""
        faults = self._expert_faults()
        if not 0 <= self.dense_layers < self.n_layers:
            faults.append(f"dense_layers {self.dense_layers} of n_layers "
                          f"{self.n_layers} leaves no routed layer (a "
                          f"stack with none is the decoder block's)")
        if self.shared_experts < 0 or not self.route_scale > 0:
            faults.append(f"shared_experts {self.shared_experts}, "
                          f"route_scale {self.route_scale}")
        return faults

    def _postnorm_moe_faults(self) -> list[str]:
        faults = self._grouped_faults() + self._sigmoid_faults()
        first, held = self.held_experts
        if not (held >= 1 and first >= 0
                and first + held <= self.n_experts):
            faults.append(f"experts_held {self.experts_held} from "
                          f"first_expert_held {first} do not lie within "
                          f"n_experts {self.n_experts}")
        return faults

    @property
    def held_experts(self) -> tuple[int, int]:
        """(the first expert held on this device, how many): all of
        ``n_experts`` unless ``experts_held`` says a share."""
        return self.first_expert_held, self.experts_held or self.n_experts

    @property
    def shares_experts(self) -> bool:
        """Whether this device holds a share of each routed layer's
        experts and not all of them."""
        return self.block == "postnorm_moe" \
            and self.held_experts[1] < self.n_experts

    @property
    def routed(self) -> bool:
        """Whether the block routes spans to experts (and so counts its
        assignments on the device)."""
        return self.block in ("moe", "latent_moe", "postnorm_moe")

    @property
    def layer_applications(self) -> int:
        """Blocks a span passes through in one scoring call."""
        return self.passes * self.n_layers

    @property
    def span_attrs(self) -> dict[str, Any]:
        """What every ``tpu/score`` span says of the model behind the
        call: the block kind and how many blocks a span passes through;
        of a routed block also its experts and how many a span takes; of
        the routed and the post-norm routed blocks how many layers
        rotate and how many have a window, of the latent routed block its
        attention's ranks, of both sigmoid-routed blocks their shared
        experts and how many leading layers are dense, and of the
        post-norm routed block which of the router's experts this device
        holds."""
        attrs = {"model.block": self.block, "model.passes": self.passes,
                 "model.layer_applications": self.layer_applications}
        if self.routed:
            attrs.update({"model.experts": self.n_experts,
                          "model.experts_per_span": self.experts_per_span})
        if self.block in ("moe", "postnorm_moe"):
            attrs.update({
                "model.layers_rotary": sum(map(bool, self.rope_layout)),
                "model.layers_window": sum(map(bool, self.window_layout))})
        if self.block == "latent_moe":
            attrs.update({"model.attention": "latent",
                          "model.q_rank": self.q_rank,
                          "model.kv_rank": self.kv_rank})
        if self.block in ("latent_moe", "postnorm_moe"):
            attrs.update({"model.shared_experts": self.shared_experts,
                          "model.layers_dense": self.dense_layers})
        if self.block == "postnorm_moe":
            first, held = self.held_experts
            attrs.update({"model.experts_held": held,
                          "model.first_expert_held": first})
        return attrs

    @property
    def call_counters(self) -> dict[str, str]:
        """Which of the numbers a call counts on the device
        (``score_packed_counted``, by span attribute name) is added to
        which counter; nothing for a block that counts nothing."""
        if self.shares_experts:
            return {"moe.assignments": EXPERT_ASSIGNMENTS_METRIC,
                    "moe.choices": EXPERT_CHOICES_METRIC}
        if self.routed:
            return {"moe.assignments": EXPERT_ASSIGNMENTS_METRIC}
        return {}


# the routed blocks' own keys and the blocks that take each; any other
# block refuses one that is set
_BLOCK_KEYS = {
    **{k: ("latent_moe", "moe", "postnorm_moe") for k in (
        "n_experts", "experts_per_span", "d_expert", "param_dtype")},
    **{k: ("moe", "postnorm_moe") for k in (
        "n_kv_heads", "head_dim", "rope_layout", "window_layout", "window")},
    **{k: ("latent_moe",) for k in (
        "q_rank", "kv_rank", "qk_nope_dim", "qk_rope_dim", "v_dim")},
    **{k: ("latent_moe", "postnorm_moe") for k in (
        "shared_experts", "dense_layers", "route_scale")},
    **{k: ("postnorm_moe",) for k in (
        "experts_held", "first_expert_held", "weight_seed")}}


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
        elif c.block == "decoder":
            backbone = LoopedDecoder(
                c.service_vocab, c.name_vocab, c.attr_vocab, c.d_model,
                c.n_heads, c.n_layers, c.d_ff, c.passes, c.rope_theta,
                c.norm_eps, c.dtype, name="encoder")
        elif c.block == "moe":
            backbone = MoeDecoder(
                c.service_vocab, c.name_vocab, c.attr_vocab, c.d_model,
                c.n_heads, c.n_kv_heads, c.head_dim, c.n_experts,
                c.experts_per_span, c.d_expert, c.rope_layout,
                c.window_layout, c.window, c.rope_theta, c.norm_eps,
                c.dtype, c.param_dtype, name="encoder")
        elif c.block == "postnorm_moe":
            first, held = c.held_experts
            backbone = PostNormMoeDecoder(
                c.service_vocab, c.name_vocab, c.attr_vocab, c.d_model,
                c.n_heads, c.n_kv_heads, c.head_dim, c.n_layers,
                c.dense_layers, c.d_ff, c.n_experts, held, first,
                c.experts_per_span, c.d_expert, c.shared_experts,
                c.route_scale, c.rope_layout, c.window_layout, c.window,
                c.rope_theta, c.norm_eps, c.dtype, c.param_dtype,
                name="encoder")
        else:
            backbone = LatentMoeDecoder(
                c.service_vocab, c.name_vocab, c.attr_vocab, c.d_model,
                c.n_heads, c.n_layers, c.dense_layers, c.q_rank, c.kv_rank,
                c.qk_nope_dim, c.qk_rope_dim, c.v_dim, c.d_ff, c.n_experts,
                c.experts_per_span, c.d_expert, c.shared_experts,
                c.route_scale, c.rope_theta, c.norm_eps, c.dtype,
                c.param_dtype, name="encoder")
        h = backbone(categorical, continuous, mask, deterministic,
                     positions=positions, segments=segments)
        if c.routed:
            # (routed layers, n_experts) assignments of the call and the
            # rows its route moved, for whoever applies with the
            # collection mutable (score_packed_counted)
            h, load, moved = h
            if not self.is_initializing():    # params alone are variables
                self.sow("moe", "load", load)
                self.sow("moe", "rows_moved", moved)
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
        # a block that counts on the device what a call did (a routed
        # block: assignments by layer and expert) has a second entry that
        # returns the counts beside the scores, one program and one fetch;
        # None for a block that counts nothing. The engine runs this one
        # where there is one.
        self.score_packed_counted = None
        if self.cfg.routed:
            score_packed_counted = jax.jit(self._score_packed_counted_impl)
            self.score_packed_counted = jitstats.track_jit(
                "transformer.score_packed_counted", score_packed_counted)

    def init(self, rng: jax.Array, sample_cat=None, sample_cont=None,
             sample_mask=None):
        """The model's variables drawn from ``rng``, or from
        ``PRNGKey(weight_seed)`` where the configuration fixes one."""
        c = self.cfg
        if c.weight_seed is not None:
            rng = jax.random.PRNGKey(c.weight_seed)
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

    def _score_packed_counted_impl(self, variables, categorical, continuous,
                                   segments, positions):
        """``score_packed`` and, from the router's own top-k, what the
        call's real spans were assigned: how many assignments in all
        (spans x experts_per_span x routed layers), the busiest (layer,
        expert)'s over the mean one's, and the fewest experts that hold a
        span in any routed layer (a collapsed routing reads
        experts_per_span here); and the rows the route moved, counted by
        the path that moved them (two a real assignment where the TPU's
        kernels move them, two a slot's choice, real or not, where XLA's
        gathers do), under the names they have on the ``tpu/score``
        span. Where the device holds a share of each routed layer's
        experts, the assignments, the busiest and the fewest busy are
        those of the held experts, and two more say what the router
        chose over all of them: ``moe.choices`` (real spans x
        experts_per_span x routed layers, held here or not) and
        ``moe.held_share``, the assignments over the choices."""
        mask = segments > 0
        (span_logit, _), state = self.module.apply(
            variables, categorical, continuous, mask,
            positions=positions, segments=segments, mutable=["moe"])
        (load,) = state["moe"]["load"]
        (moved,) = state["moe"]["rows_moved"]
        with jax.named_scope("head"):
            total = load.sum()
            scores = jax.nn.sigmoid(span_logit)
            counts = {
                "moe.assignments": total,
                "moe.load_max_over_mean":
                    load.max() * load.size / jnp.maximum(total, 1),
                "moe.experts_busy_min": (load > 0).sum(axis=-1).min(),
                "moe.rows_moved": moved}
            if self.cfg.shares_experts:
                choices = mask.sum(dtype=jnp.int32) * (
                    self.cfg.experts_per_span * load.shape[0])
                counts["moe.choices"] = choices
                counts["moe.held_share"] = total / jnp.maximum(choices, 1)
            return scores, counts

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

    def attention_blocks(self, rows: int, L: int, platform: str,
                         mesh_shape: dict | None = None,
                         ) -> tuple[int, int] | None:
        """How the encoder block's ``attention_kernel`` cuts a call of
        ``rows`` packed rows of ``L`` on ``platform`` under a mesh of
        ``mesh_shape``: (rows a block, devices along the rows), or None
        where the executable holds no kernel, by the rule the block
        dispatches on (``layers.kernel_attends``)."""
        c = self.cfg
        if c.block != "encoder" or not kernel_attends(
                c.dtype, rows, L, c.d_model, c.n_heads, mesh_shape,
                platform):
            return None
        shards = (mesh_shape or {}).get(ROWS_AXIS, 1)
        return attention_kernel.block_rows(rows // shards, L,
                                           c.d_model), shards


# compile accounting for the class-level jitted scoring entry (shared by
# every instance; __dict__ access skips any descriptor binding)
jitstats.track_jit("transformer.score_spans",
                   TraceTransformer.__dict__["score_spans"])


def optax_sigmoid_bce(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Numerically-stable sigmoid binary cross-entropy."""
    labels = labels.astype(jnp.float32)
    return jnp.maximum(logits, 0) - logits * labels + jnp.log1p(
        jnp.exp(-jnp.abs(logits)))
