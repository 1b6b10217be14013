"""Shared flax modules: span embedding trunk + the transformer's five
block kinds (``BLOCK_PARTS``): the pre-LN bidirectional encoder block under
a learned position table; the decoder block (sandwich RMS norms, rotary
positions, causal attention within a trace, SwiGLU, no biases) whose stack
runs ``passes`` times over the same parameters as a loop on the device; the
routed block (pre-norm residuals, grouped query heads over fewer
key/value heads, rotary positions and a window layer by layer, a router
ahead of attention that sends each span to ``experts_per_span`` of
``n_experts`` ReLU-gated experts, parameters held in bfloat16); and the
latent routed block (latent attention: low-rank queries, one compressed
key/value and one rotary key a span shared by every head; a sigmoid router
with a selection bias over SiLU-gated experts beside a shared expert; the
stack's leading layers dense); and the post-norm routed block (grouped
query heads normed head by head, rotary positions and a window layer by
layer, an RMS norm on each sublayer's output and none ahead of it, the
latent block's sigmoid router, shared expert and dense leading layers,
and a device that may hold a share of a layer's experts). The routed
blocks run one dispatch, ``routed_experts``, each under its own rule of
choice (``top_softmax``, ``biased_sigmoid``).

MXU discipline (see /opt/skills/guides/pallas_guide.md and SURVEY.md env
notes): feature dims multiples of 128, bfloat16 activations with float32
params, no data-dependent shapes — everything here jits to static-shape
einsums that XLA tiles onto the systolic array.
"""

from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax.sharding import PartitionSpec

from ..features.featurizer import CAT_FIELDS
from . import attention_kernel, route_kernels


class SpanEmbedder(nn.Module):
    """Embeds the featurizer's categorical/continuous columns into d_model.

    Column layout follows features.featurizer.CAT_FIELDS:
      0 service, 1 name, 2 kind, 3 status, 4 parent_service, 5.. attr slots.
    parent_service shares the service table (same id space); attr slots share
    one attr table and are summed.
    """

    service_vocab: int
    name_vocab: int
    attr_vocab: int
    d_model: int
    dtype: Any = jnp.bfloat16
    # how the tables and the continuous projection are drawn: flax's own
    # defaults, under which a wide model's input is the projection of the
    # log-duration column alone (a table's row has variance 1 / d_model an
    # element, the projection's a third of a column that reads 3 to 10).
    # A block that routes on its raw input draws them otherwise
    # (``MoeDecoder``).
    table_init: Any = nn.linear.default_embed_init
    cont_init: Any = nn.linear.default_kernel_init

    @nn.compact
    def __call__(self, categorical: jnp.ndarray,
                 continuous: jnp.ndarray) -> jnp.ndarray:
        d = self.d_model

        def table(rows: int, name: str) -> nn.Embed:
            return nn.Embed(rows, d, dtype=self.dtype, name=name,
                            embedding_init=self.table_init)

        svc_table = table(self.service_vocab, "service_embed")
        x = svc_table(categorical[..., 0])
        x += table(self.name_vocab, "name_embed")(categorical[..., 1])
        x += table(8, "kind_embed")(categorical[..., 2])
        x += table(4, "status_embed")(categorical[..., 3])
        x += svc_table(categorical[..., 4])  # parent edge, shared table
        n_attr = categorical.shape[-1] - len(CAT_FIELDS)
        if n_attr > 0:
            attr_table = table(self.attr_vocab, "attr_embed")
            x += attr_table(categorical[..., len(CAT_FIELDS):]).sum(axis=-2)
        x += nn.Dense(d, dtype=self.dtype, name="cont_proj",
                      kernel_init=self.cont_init)(
            continuous.astype(self.dtype))
        return x


class EncoderBlock(nn.Module):
    """Pre-LN bidirectional transformer block (``encoder_attention``)."""

    d_model: int
    n_heads: int
    d_ff: int
    dtype: Any = jnp.bfloat16
    dropout: float = 0.0

    @nn.compact
    def __call__(self, x: jnp.ndarray, attn_mask: jnp.ndarray,
                 deterministic: bool = True, segments=None) -> jnp.ndarray:
        # attn_mask: (T, 1, L, L) bool, True where attention is allowed
        with jax.named_scope("attn"):
            h = nn.LayerNorm(dtype=self.dtype)(x)
            h = encoder_attention(
                h, attn_mask, segments, n_heads=self.n_heads,
                dtype=self.dtype, dropout=self.dropout,
                deterministic=deterministic)
            x = x + h
        with jax.named_scope("mlp"):
            h = nn.LayerNorm(dtype=self.dtype)(x)
            h = nn.Dense(self.d_ff, dtype=self.dtype)(h)
            # a fusion boundary (the identity), where XLA itself parts
            # the two products of a large call: on a small one (256 rows
            # of 64 at ViT-H widths) it nests the first in the second's
            # fusion, and the pair runs at half the share of peak
            h = jax.lax.optimization_barrier(h)
            h = nn.gelu(h)
            h = nn.Dense(self.d_model, dtype=self.dtype)(h)
            return x + h


# the parts of a scoring call, as ``jax.named_scope`` writes them into
# every operation's metadata (and so into the device trace): the
# benchmark's step_attn_ms / step_mlp_ms / step_rest_ms fold operations
# by the first of these names on their path. Scopes only: flax's
# parameter paths do not see them.
PARTS = ("embed", "attn_mask", "attn", "mlp", "final_norm", "head")
# the scopes of each block kind (``TransformerConfig.block``). The decoder
# block's RMS norms (four a block, one closing each pass) are a part of
# their own, ``norm``: element-wise and bandwidth-bound, 4 n_layers + 1 a
# pass, where the encoder's LayerNorms sit inside ``attn`` and ``mlp``.
# The routed block adds ``route``: the router's product, the top-k and its
# softmax, the sort by expert, the gather into expert order and the
# weighted combine back; its ``mlp`` is the experts' grouped products, the
# gate's activation and its multiply alone. The latent routed block adds
# two more, siblings of the rest: ``latent`` (the four low-rank products
# that make queries, keys and values, the two latent norms, the rotary of
# the rotary columns, the shared key's broadcast and the concatenations;
# its ``attn`` is the core and the output product alone) and ``dense``
# (the shared expert of a routed layer and a dense layer's feed-forward).
# The post-norm routed block has ``dense`` too and adds ``qk``: the
# per-head RMS norms of queries and keys and the rotary of the layers that
# have it, between the projections and the core (both ``attn``).
BLOCK_PARTS = {
    "encoder": PARTS,
    "decoder": ("embed", "attn_mask", "attn", "mlp", "norm", "head"),
    "moe": ("embed", "attn_mask", "attn", "route", "mlp", "norm", "head"),
    "latent_moe": ("embed", "attn_mask", "latent", "attn", "route", "mlp",
                   "dense", "norm", "head"),
    "postnorm_moe": ("embed", "attn_mask", "qk", "attn", "route", "mlp",
                     "dense", "norm", "head"),
}


class Encoder(nn.Module):
    """Embedding trunk + positional embedding + N encoder blocks."""

    service_vocab: int
    name_vocab: int
    attr_vocab: int
    d_model: int
    n_heads: int
    n_layers: int
    d_ff: int
    max_len: int
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, categorical, continuous, mask,
                 deterministic: bool = True,
                 positions: jnp.ndarray | None = None,
                 segments: jnp.ndarray | None = None) -> jnp.ndarray:
        """``segments`` (row-local trace ids, 0 = padding) switches attention
        to block-diagonal — the packed-sequences path (features.pack_sequences)
        that keeps MXU density high regardless of trace length distribution.
        ``positions`` overrides the positional-embedding index (within-trace
        position for packed rows)."""
        with jax.named_scope("embed"):
            x = SpanEmbedder(self.service_vocab, self.name_vocab,
                             self.attr_vocab, self.d_model, self.dtype,
                             name="embed")(categorical, continuous)
            L = categorical.shape[-2]
            pos_ids = positions if positions is not None else jnp.arange(L)
            pos = nn.Embed(self.max_len, self.d_model, dtype=self.dtype,
                           name="pos_embed")(pos_ids)
            x = x + pos
            x = x * mask[..., None].astype(self.dtype)
        with jax.named_scope("attn_mask"):
            if segments is not None:
                attn_mask = ((segments[..., None] == segments[..., None, :])
                             & mask[..., None] & mask[..., None, :])[:, None]
            else:
                attn_mask = (mask[:, None, None, :] & mask[:, None, :, None])
        for i in range(self.n_layers):
            x = EncoderBlock(self.d_model, self.n_heads, self.d_ff,
                             self.dtype, name=f"block_{i}")(
                x, attn_mask, deterministic, segments)
        with jax.named_scope("final_norm"):
            return nn.LayerNorm(dtype=self.dtype, name="final_ln")(x)


def rotary_tables(positions: jnp.ndarray, head_dim: int, theta: float,
                  dtype: Any) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(cos, sin), each (..., 1, head_dim): the rotate-half rotary angles
    of ``positions``, angle i of a position p being p * theta^(-2i /
    head_dim), laid out twice over the head so that ``rotate`` pairs
    column i with column i + head_dim / 2."""
    half = head_dim // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2 / head_dim)
    angles = positions[..., None].astype(jnp.float32) * inv_freq
    angles = jnp.concatenate([angles, angles], axis=-1)[..., None, :]
    return jnp.cos(angles).astype(dtype), jnp.sin(angles).astype(dtype)


def rotate(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray) -> jnp.ndarray:
    """Rotary position embedding of (..., heads, head_dim) queries or keys."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def causal_mask(mask: jnp.ndarray, positions: jnp.ndarray,
                segments: jnp.ndarray | None) -> jnp.ndarray:
    """(T, L, L) bool: span i may attend to span j where both are real,
    j is no later than i in its trace and (packed rows) both are of one
    trace."""
    allowed = mask[..., None] & mask[..., None, :] \
        & (positions[..., None] >= positions[..., None, :])
    if segments is not None:
        allowed &= segments[..., None] == segments[..., None, :]
    return allowed


def attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
              mask: jnp.ndarray, dtype: Any) -> jnp.ndarray:
    """Dot-product attention of both decoder kinds, the softmax in
    float32: ``q`` (..., L, heads, head_dim), ``k`` and ``v`` (..., L,
    kv_heads, head_dim). With fewer key/value heads than query heads,
    query head g reads key/value head g // (heads // kv_heads)."""
    group = q.shape[-2] // k.shape[-2]
    if group > 1:
        k, v = (jnp.repeat(u, group, axis=-2) for u in (k, v))
    return nn.dot_product_attention(
        q, k, v, mask=mask, deterministic=True, dtype=dtype,
        force_fp32_for_softmax=True)


class DecoderBlock(nn.Module):
    """Decoder block with sandwich norms: an RMS norm before and after each
    sublayer, rotary positions on queries and keys, SwiGLU feed-forward, no
    bias anywhere. ``attn_mask`` carries the causal order."""

    d_model: int
    n_heads: int
    d_ff: int
    dtype: Any = jnp.bfloat16
    norm_eps: float = 1e-6

    @nn.compact
    def __call__(self, x: jnp.ndarray, attn_mask: jnp.ndarray,
                 cos: jnp.ndarray, sin: jnp.ndarray) -> jnp.ndarray:
        def dense(features: int, name: str) -> nn.Dense:
            return nn.Dense(features, use_bias=False, dtype=self.dtype,
                            name=name)

        def norm(name: str, h: jnp.ndarray) -> jnp.ndarray:
            with jax.named_scope("norm"):
                return nn.RMSNorm(epsilon=self.norm_eps, dtype=self.dtype,
                                  name=name)(h)

        heads = x.shape[:-1] + (self.n_heads, self.d_model // self.n_heads)
        h = norm("attn_norm", x)
        with jax.named_scope("attn"):
            q = rotate(dense(self.d_model, "q_proj")(h).reshape(heads),
                       cos, sin)
            k = rotate(dense(self.d_model, "k_proj")(h).reshape(heads),
                       cos, sin)
            v = dense(self.d_model, "v_proj")(h).reshape(heads)
            h = attention(q, k, v, attn_mask, self.dtype)
            h = dense(self.d_model, "o_proj")(h.reshape(x.shape))
        x = x + norm("attn_out_norm", h)
        h = norm("mlp_norm", x)
        with jax.named_scope("mlp"):
            h = nn.silu(dense(self.d_ff, "gate_proj")(h)) \
                * dense(self.d_ff, "up_proj")(h)
            h = dense(self.d_model, "down_proj")(h)
        return x + norm("mlp_out_norm", h)


class _DecoderPass(nn.Module):
    """One pass: every block in turn, closed by the final norm. The body
    of ``LoopedDecoder``'s loop (a scan's: carry in, (carry, None) out)."""

    d_model: int
    n_heads: int
    n_layers: int
    d_ff: int
    dtype: Any
    norm_eps: float

    @nn.compact
    def __call__(self, x, attn_mask, cos, sin):
        for i in range(self.n_layers):
            x = DecoderBlock(self.d_model, self.n_heads, self.d_ff,
                             self.dtype, self.norm_eps,
                             name=f"block_{i}")(x, attn_mask, cos, sin)
        with jax.named_scope("norm"):
            x = nn.RMSNorm(epsilon=self.norm_eps, dtype=self.dtype,
                           name="final_rms")(x)
        return x, None


class LoopedDecoder(nn.Module):
    """Embedding trunk + a stack of decoder blocks run ``passes`` times
    over the same parameters. The loop is on the device (``nn.scan`` with
    the parameters broadcast): the compiled program holds the stack once,
    whatever ``passes`` is. Positions are rotary, from ``positions``, so
    they restart with each trace of a packed row; attention is causal
    within a trace (``segments`` and ``positions`` together)."""

    service_vocab: int
    name_vocab: int
    attr_vocab: int
    d_model: int
    n_heads: int
    n_layers: int
    d_ff: int
    passes: int
    rope_theta: float
    norm_eps: float
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, categorical, continuous, mask,
                 deterministic: bool = True,
                 positions: jnp.ndarray | None = None,
                 segments: jnp.ndarray | None = None) -> jnp.ndarray:
        with jax.named_scope("embed"):
            x = SpanEmbedder(self.service_vocab, self.name_vocab,
                             self.attr_vocab, self.d_model, self.dtype,
                             name="embed")(categorical, continuous)
            x = x * mask[..., None].astype(self.dtype)
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(mask.shape[-1]),
                                         mask.shape)
        with jax.named_scope("attn_mask"):
            attn_mask = causal_mask(mask, positions, segments)[:, None]
        with jax.named_scope("attn"):
            cos, sin = rotary_tables(positions, self.d_model // self.n_heads,
                                     self.rope_theta, self.dtype)
        # the parameters are made outside the loop, by one plain pass (same
        # paths, same keys): under the scan, flax would trace their
        # initializers and the whole forward into one program of the
        # model's size, where eager initialization holds a kernel at a time
        loop = _DecoderPass if self.is_initializing() else nn.scan(
            _DecoderPass, variable_broadcast="params",
            split_rngs={"params": False}, in_axes=nn.broadcast,
            length=self.passes)
        x, _ = loop(self.d_model, self.n_heads, self.n_layers, self.d_ff,
                    self.dtype, self.norm_eps, name="stack")(
            x, attn_mask, cos, sin)
        return x


def _rounded(draw):
    """``draw`` in float32, rounded once to the dtype the parameter is held
    in: the same numbers whatever that dtype is, where a draw in bfloat16
    is another stream."""
    def init(key, shape, dtype=jnp.float32):
        return draw(key, shape, jnp.float32).astype(dtype)

    return init


def rounded_lecun(batch_axis: tuple[int, ...] = ()):
    """Lecun-normal over the kernel's own fan-in, ``_rounded``.
    ``batch_axis`` names the axes that count no fan (an expert axis)."""
    return _rounded(nn.initializers.variance_scaling(
        1.0, "fan_in", "truncated_normal", batch_axis=batch_axis))


class Kernel(nn.Module):
    """A kernel without its product, for a caller that multiplies by it
    otherwise than a ``Dense`` would (grouped by expert, or by parts):
    (fan_in, fan_out), or one for each expert (n_experts, fan_in,
    fan_out), under the parameter path and from the key a ``Dense`` of
    that name would have."""

    shape: tuple[int, ...]
    param_dtype: Any

    @nn.compact
    def __call__(self) -> jnp.ndarray:
        experts = tuple(range(len(self.shape) - 2))   # count no fan
        return self.param("kernel", rounded_lecun(batch_axis=experts),
                          self.shape, self.param_dtype)


def expert_kernels(n_experts: int, d_model: int, d_expert: int,
                   param_dtype: Any, dtype: Any) -> list[jnp.ndarray]:
    """The three kernels of a routed block's experts (gate, up, down) as
    parameters of the calling module, in the activations' ``dtype``."""
    return [Kernel((n_experts, a, b), param_dtype, name=name)().astype(dtype)
            for name, a, b in (("experts_gate", d_model, d_expert),
                               ("experts_up", d_model, d_expert),
                               ("experts_down", d_expert, d_model))]


# rows a tile of the grouped kernels: the assignments are padded up to
# whole tiles (rows that belong to no expert)
GROUP_ROWS = 512


def _whole_tile(n: int, most: int = 1280) -> int:
    """The widest tile of a grouped product along an axis of ``n``
    columns: the largest multiple of 128 up to ``most`` that divides
    ``n``, so that no tile is part empty (1280 of 2560, 768 of 768; 1024
    of 2048 and 768 of 1536, where tiles of 1280 worked 2560 columns of
    each and the three products ran at half their share of the peak:
    PERF.md section 6, PR 37); ``n`` up to ``most`` itself where no such
    multiple divides it."""
    return max((t for t in range(128, min(n, most) + 1, 128) if n % t == 0),
               default=min(n, most))


def _experts_ragged(x, gate, up, down, load, act=nn.relu):
    """The experts' three grouped products over rows sorted by expert,
    ``load[e]`` rows for expert e: act(x Wg) * (x Wu), then Wd."""
    y = act(jax.lax.ragged_dot(x, gate, load)) \
        * jax.lax.ragged_dot(x, up, load)
    return jax.lax.ragged_dot(y, down, load)


def _experts_gmm(x, gate, up, down, load, act=nn.relu,
                 interpret: bool = False):
    """The same three products as Pallas grouped-matmul kernels
    (``megablox.gmm``), which the TPU runs at two thirds of its peak where
    the kernel XLA makes of ``ragged_dot`` ran at 42% (v5e, 196,608 rows x
    2560 x 768; PERF.md section 6, PR 34), and which keeps its scope in
    the device trace. It visits the tiles that hold an expert's rows and
    no others: rows past the last expert are left as they were."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    def product(lhs, rhs):
        tiles = (GROUP_ROWS,) + tuple(_whole_tile(n) for n in rhs.shape[1:])
        return gmm(lhs, rhs, load, preferred_element_type=lhs.dtype,
                   tiling=tiles, interpret=interpret)

    return product(act(product(x, gate)) * product(x, up), down)


def top_softmax(logits: jnp.ndarray, k: int,
                ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The routed block's rule: the ``k`` largest ``logits`` (spans,
    n_experts; float32) first, the softmax over those k. Returns which
    experts a span takes and how it weighs them, both (spans, k)."""
    with jax.named_scope("route"):
        top, which = jax.lax.top_k(logits, k)
        return which, jax.nn.softmax(top, axis=-1)


def biased_sigmoid(logits: jnp.ndarray, bias: jnp.ndarray, k: int,
                   scale: float) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The latent routed block's rule: a span's scores are the sigmoids of
    its ``logits``; it takes the ``k`` experts of the largest score plus
    ``bias`` (n_experts,), and weighs them by their unbiased scores,
    normalised over the k and scaled: the bias chooses, it does not
    weigh. All float32. Returns (which, weight), both (spans, k)."""
    with jax.named_scope("route"):
        score = jax.nn.sigmoid(logits)
        _, which = jax.lax.top_k(score + bias.astype(score.dtype), k)
        chosen = jnp.take_along_axis(score, which, axis=-1)
        return which, scale * chosen / jnp.sum(chosen, axis=-1,
                                               keepdims=True)


def _into_expert_order(h, order, k):
    """The span rows ``h`` (spans, d) in expert order by XLA's gather, up
    to whole tiles of the grouped products. Its table is (spans, d), which
    the chip holds close: every slot's k rows at memory speed, those that
    hold no span too."""
    with jax.named_scope("route"):
        whole = -order.shape[0] % GROUP_ROWS
        return h[jnp.pad(order, (0, whole)) // k]


def _back_by_gather(h, order, weight, real, load, gate, up, down, act,
                    experts):
    """The grouped products ``experts`` over the rows of ``h`` in expert
    order, and their rows back to span order by XLA's gather, weighed and
    summed: every slot's k rows ride both gathers, those that hold no span
    too. ``real`` is (spans,) or, under a held share, (spans, k): a
    choice it leaves out comes back zero. Returns (spans, d) in
    ``h.dtype`` and the rows moved."""
    spans, k = weight.shape
    y = _into_expert_order(h, order, k)
    with jax.named_scope("mlp"):
        y = experts(y, gate, up, down, load, act)
    with jax.named_scope("route"):
        # back to span order with the k-th choices as the leading axis,
        # (k, spans, d): summed over whole slabs, where (spans, k, d)
        # would pad k up to a tile's 8 rows
        y = y[jnp.argsort(order).reshape(spans, k).T]
        # a row past the last expert's holds whatever the grouped product
        # left there: its weight is zero, and it is taken out whole so
        # that it cannot be a NaN either
        keep = real[None, :, None] if real.ndim == 1 \
            else real.T[:, :, None]
        y = jnp.where(keep, y, 0).astype(jnp.float32)
        out = jnp.sum(y * weight.T[:, :, None], axis=0)
    return out.astype(h.dtype), jnp.int32(2 * order.shape[0])


def _back_by_dma(h, order, weight, real, load, gate, up, down, act,
                 interpret: bool = False):
    """The ``megablox.gmm`` products over the rows of ``h`` in expert
    order, and their rows back by the DMA kernels of
    ``route_kernels.combine``: a real assignment's row is moved once, a
    slot that holds no span's not at all. ``real`` is (spans,), and the
    rows go in by XLA's gather, every slot's k; or, under a held share,
    (spans, k): a choice of an expert held elsewhere is then a slot that
    holds nothing, and the rows go in by DMA too, a held choice's once
    (``route_kernels.into_expert_order``: the rows ``_into_expert_order``
    gives up to the last held choice, and no other). Returns (spans, d)
    in ``h.dtype`` and the rows moved."""
    spans, k = weight.shape
    if real.ndim == 1:
        y, moved_in = _into_expert_order(h, order, k), order.shape[0]
    else:
        with jax.named_scope("route"):
            moved_in = jnp.sum(real, dtype=jnp.int32)
            rows = -(-order.shape[0] // GROUP_ROWS) * GROUP_ROWS
            y = route_kernels.into_expert_order(h, order, k, moved_in, rows,
                                                interpret)
    with jax.named_scope("mlp"):
        y = _experts_gmm(y, gate, up, down, load, act, interpret)
    with jax.named_scope("route"):
        n_real = jnp.sum(real, dtype=jnp.int32) * (k if real.ndim == 1
                                                    else 1)
        out = route_kernels.combine(y, order, n_real, weight, real, h.dtype,
                                    interpret)
    return out, moved_in + n_real


# Shape-bucketing strategy of the jitted function here (the package hygiene
# test asserts every jit path in models/ declares one)
SHAPE_BUCKETING = {
    "routed_experts": "traced inside the scoring entry that calls it, on "
                      "that call's rows: the engine's BucketLadder rungs",
}


# jitted so that a stack's layers share one trace and one lowering: the
# route's kernels take seconds a layer to lower, and a call's set-up pays
# every lowering of its program
@partial(jax.jit, static_argnames=("act", "first"))
def routed_experts(h: jnp.ndarray, which: jnp.ndarray, weight: jnp.ndarray,
                   real: jnp.ndarray, gate: jnp.ndarray, up: jnp.ndarray,
                   down: jnp.ndarray, act=nn.relu, first: int | None = None,
                   ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """The routed feed-forward over ``h`` (spans, d): each real span goes
    to the k experts ``which`` (spans, k) names, weighted by ``weight``
    (spans, k; float32), whatever rule chose them (``top_softmax``,
    ``biased_sigmoid``); ``act`` is the gate's activation. The experts
    run as grouped products over the assignments sorted by expert: no
    span is dropped and there is no capacity. A slot that holds no span
    (``real`` False) is sorted past the last expert, so it enters no
    product, and comes back zero. On the TPU, where ``route_kernels``
    takes the rows' dtype and width (``_back_by_dma``), the rows go into
    expert order by XLA's gather (``_into_expert_order``), or by DMA, a
    held choice's alone, under a held share (below), and come back by
    DMA; elsewhere they go and come back by XLA's gathers.

    ``first`` is for a device that holds a share of a layer's experts
    (expert parallelism): the router chose over all of them, ``which``
    names them so, and this device holds the ``gate.shape[0]`` experts
    from ``first`` on. A choice of an expert held elsewhere becomes a
    slot that holds nothing, as a padding slot is: sorted past the last
    held expert and weighed zero, so the output is the part of the
    feed-forward that the held experts give, under the weights the
    router gave over all k choices. None: every expert is held here.

    Returns (spans, d), the assignments each held expert took,
    (gate.shape[0],) int32, and the rows the route moved, int32."""
    spans, k = which.shape
    n_experts = gate.shape[0]
    with jax.named_scope("route"):
        if first is not None:
            # a choice, not a span, holds an assignment here or not
            which = which - first
            real = real[:, None] & (which >= 0) & (which < n_experts)
            weight = weight * real
            expert = jnp.where(real, which, n_experts).reshape(-1)
        else:
            weight = weight * real[:, None]
            expert = jnp.where(real[:, None], which, n_experts).reshape(-1)
        order = jnp.argsort(expert, stable=True)
        load = jnp.sum(expert[:, None] == jnp.arange(n_experts), axis=0,
                       dtype=jnp.int32)
    tpu = partial(_back_by_dma, act=act) if route_kernels.supported(h) \
        else partial(_back_by_gather, act=act, experts=_experts_gmm)
    out, moved = jax.lax.platform_dependent(
        h, order, weight, real, load, gate, up, down, tpu=tpu,
        default=partial(_back_by_gather, act=act, experts=_experts_ragged))
    return out, load, moved


def routed_counts(counts) -> tuple[jnp.ndarray, jnp.ndarray]:
    """What a stack's routed layers counted, each ``(load, moved)``:
    the loads one row a layer, (routed layers, n_experts), and the rows
    the route moved in all."""
    loads, moved = zip(*counts)
    return jnp.stack(loads), sum(moved)


# the mesh axis a plan splits the packed rows over (parallel/sharding.py)
ROWS_AXIS = attention_kernel.ROWS_AXIS


def each_device_its_rows(route):
    """``route(h, which, weight, real, gate, up, down) -> (out, load,
    moved)`` (``routed_experts``) run by
    each device on its own rows where the mesh in context splits the rows
    (a plan traces its call inside its mesh): the grouped products are
    Pallas kernels on the TPU, which the partitioner cannot split
    ("Mosaic kernels cannot be automatically partitioned"), and a span's
    experts need no other device's rows, the kernels being whole on every
    device. The loads and the rows moved are summed over the devices. With
    no mesh in context, or one device along the axis, ``route`` itself."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.shape.get(ROWS_AXIS, 1) == 1:
        return route

    def local(*args):
        out, *counts = route(*args)
        return out, *(jax.lax.psum(c, ROWS_AXIS) for c in counts)

    rows, whole = PartitionSpec(ROWS_AXIS), PartitionSpec()
    return jax.shard_map(local, in_specs=(rows,) * 4 + (whole,) * 3,
                         out_specs=(rows, whole, whole), check_vma=False)


class MoeBlock(nn.Module):
    """Routed decoder block: pre-norm residuals (two RMS norms), grouped
    query heads, rotary positions where ``rope`` says, and in place of a
    dense feed-forward ``n_experts`` ReLU-gated experts of width
    ``d_expert`` of which a span takes ``experts_per_span``. The router
    reads the block's raw input, ahead of the norm and the attention; its
    product, the top-k and the softmax over the chosen run in float32.
    No bias anywhere; parameters are held in ``param_dtype``."""

    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    n_experts: int
    experts_per_span: int
    d_expert: int
    rope: bool
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16
    norm_eps: float = 1e-6

    @nn.compact
    def __call__(self, x: jnp.ndarray, mask: jnp.ndarray,
                 attn_mask: jnp.ndarray, cos: jnp.ndarray,
                 sin: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
        def dense(features: int, name: str, dtype: Any = self.dtype,
                  **kw) -> nn.Dense:
            return nn.Dense(features, use_bias=False, dtype=dtype,
                            param_dtype=self.param_dtype,
                            kernel_init=rounded_lecun(), name=name, **kw)

        def norm(name: str, h: jnp.ndarray) -> jnp.ndarray:
            with jax.named_scope("norm"):
                return nn.RMSNorm(epsilon=self.norm_eps, dtype=self.dtype,
                                  param_dtype=self.param_dtype,
                                  name=name)(h)

        with jax.named_scope("route"):
            logits = dense(self.n_experts, "router", jnp.float32,
                           precision=jax.lax.Precision.HIGHEST)(x)
        h = norm("attn_norm", x)
        with jax.named_scope("attn"):
            q_shape = x.shape[:-1] + (self.n_heads, self.head_dim)
            kv_shape = x.shape[:-1] + (self.n_kv_heads, self.head_dim)
            q = dense(self.n_heads * self.head_dim, "q_proj")(h)
            k = dense(self.n_kv_heads * self.head_dim, "k_proj")(h)
            v = dense(self.n_kv_heads * self.head_dim, "v_proj")(h)
            q, k = q.reshape(q_shape), k.reshape(kv_shape)
            if self.rope:
                q, k = rotate(q, cos, sin), rotate(k, cos, sin)
            h = attention(q, k, v.reshape(kv_shape), attn_mask, self.dtype)
            h = dense(self.d_model, "o_proj")(
                h.reshape(x.shape[:-1] + (self.n_heads * self.head_dim,)))
        x = x + h
        h = norm("mlp_norm", x)
        kernels = expert_kernels(self.n_experts, self.d_model, self.d_expert,
                                 self.param_dtype, self.dtype)
        which, weight = top_softmax(logits.reshape(-1, self.n_experts),
                                    self.experts_per_span)
        h, *counts = each_device_its_rows(routed_experts)(
            h.reshape(-1, self.d_model), which, weight, mask.reshape(-1),
            *kernels)
        return x + h.reshape(x.shape), counts


# How the routed stack's embedder is drawn: each table at unit variance
# an element (a lookup is a product with a one-hot row, one input active),
# the continuous projection at unit norm a column (variance 1 / d_model an
# element). A router reads its layer's raw input and a top-k is blind to a
# positive scale: under flax's defaults that input is one direction (the
# log-duration column's) scaled by each span's log-duration, and every
# span of a call takes the same experts in every layer (PERF.md section
# 6, PR 34). Drawn so, a span's identity (service, operation, kind,
# status, parent) leads its input, as a token's does in a language model.
ROUTED_EMBED_INITS = (
    nn.initializers.normal(1.0),
    nn.initializers.variance_scaling(1.0, "fan_out", "normal"))


def window_masks(mask: jnp.ndarray, positions: jnp.ndarray,
                 segments: jnp.ndarray | None, window: int,
                 ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The two masks of a stack whose layers attend to the whole trace or
    to its last ``window`` spans, each (T, 1, L, L), built once: causal
    within a trace (``causal_mask``), and that cut to pairs fewer than
    ``window`` positions apart. A layer takes the one its layout names."""
    with jax.named_scope("attn_mask"):
        whole = causal_mask(mask, positions, segments)
        near = positions[..., None] - positions[..., None, :] < window
        return whole[:, None], (whole & near)[:, None]


class MoeDecoder(nn.Module):
    """Embedding trunk + a stack of routed blocks, each applied once.
    ``rope_layout`` and ``window_layout`` say layer by layer whether the
    block rotates its queries and keys and whether its attention is cut to
    the last ``window`` spans of the trace: the two masks are built once
    and each block takes its own. Attention is causal within a trace, as
    the looped stack's. Returns the normed output, (n_layers, n_experts)
    the assignments each expert of each layer took, and the rows the
    route moved (``routed_counts``)."""

    service_vocab: int
    name_vocab: int
    attr_vocab: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    n_experts: int
    experts_per_span: int
    d_expert: int
    rope_layout: tuple[int, ...]
    window_layout: tuple[int, ...]
    window: int
    rope_theta: float
    norm_eps: float
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, categorical, continuous, mask,
                 deterministic: bool = True,
                 positions: jnp.ndarray | None = None,
                 segments: jnp.ndarray | None = None):
        with jax.named_scope("embed"):
            x = SpanEmbedder(self.service_vocab, self.name_vocab,
                             self.attr_vocab, self.d_model, self.dtype,
                             *ROUTED_EMBED_INITS,
                             name="embed")(categorical, continuous)
            x = x * mask[..., None].astype(self.dtype)
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(mask.shape[-1]),
                                         mask.shape)
        masks = window_masks(mask, positions, segments, self.window)
        with jax.named_scope("attn"):
            cos, sin = rotary_tables(positions, self.head_dim,
                                     self.rope_theta, self.dtype)
        counts = []
        for i, (rope, window) in enumerate(zip(self.rope_layout,
                                               self.window_layout)):
            x, count = MoeBlock(
                self.d_model, self.n_heads, self.n_kv_heads, self.head_dim,
                self.n_experts, self.experts_per_span, self.d_expert,
                bool(rope), self.dtype, self.param_dtype, self.norm_eps,
                name=f"block_{i}")(x, mask, masks[bool(window)], cos, sin)
            counts.append(count)
        with jax.named_scope("norm"):
            x = nn.RMSNorm(epsilon=self.norm_eps, dtype=self.dtype,
                           param_dtype=self.param_dtype,
                           name="final_rms")(x)
        return (x, *routed_counts(counts))


# scale of the selection bias's draw (normal, float32, rounded once to the
# dtype it is held in). A trained checkpoint's bias is what its balancing
# left; at zero, which is where training starts it, the mechanism would
# not run. The fourth and fifth of 64 sigmoid scores of unit-variance
# logits lie 0.018 apart in the mean: at 0.02 the bias is the size of the
# gap it arbitrates and moves four choices in ten. It is drawn blind to
# an expert's load, so it spreads nothing: at 0.05 the first routed layer
# of the published cut kept 31 of 64 experts busy where 41 are at zero
# and 37-38 at 0.02 (PERF.md section 6, PR 37).
SELECTION_BIAS_SCALE = 0.02


# a SwiGLU's three kernels: a dense layer's, and the shared expert's
# beside the routed ones
DENSE = ("gate_proj", "up_proj", "down_proj")
SHARED = ("shared_gate", "shared_up", "shared_down")


def swiglu(dense, h: jnp.ndarray, width: int, d_model: int,
           names: tuple[str, str, str] = DENSE) -> jnp.ndarray:
    """(silu(h Wg) * (h Wu)) Wd, ``width`` wide, under scope ``dense``:
    a dense layer's feed-forward or a shared expert, each kernel made by
    ``dense(features, name)`` in the calling module."""
    gate, up, down = names
    with jax.named_scope("dense"):
        h = nn.silu(dense(width, gate)(h)) * dense(width, up)(h)
        return dense(d_model, down)(h)


def sigmoid_experts(module: nn.Module, dense, h: jnp.ndarray,
                    mask: jnp.ndarray, n_experts: int, k: int,
                    d_expert: int, scale: float, held: int | None = None,
                    first: int | None = None) -> tuple[jnp.ndarray, Any]:
    """The routed experts of a layer under ``biased_sigmoid``, as parameters
    of ``module`` (the selection bias its own first parameter, beside
    ``dense``'s router and the expert kernels): the router's ``n_experts``
    logits read from ``h`` in float32, k chosen by score plus bias and
    weighed by score, and the SiLU-gated experts run by
    ``routed_experts``. ``held`` experts from ``first`` on are this
    device's share (None: all ``n_experts``). Returns (h's shape) and the
    route's counts."""
    d = h.shape[-1]
    with jax.named_scope("route"):
        logits = dense(n_experts, "router", jnp.float32,
                       precision=jax.lax.Precision.HIGHEST)(h)
        bias = module.param("router_bias",
                            _rounded(nn.initializers.normal(
                                SELECTION_BIAS_SCALE)),
                            (n_experts,), module.param_dtype)
    kernels = expert_kernels(n_experts if held is None else held, d,
                             d_expert, module.param_dtype, module.dtype)
    which, weight = biased_sigmoid(logits.reshape(-1, n_experts), bias, k,
                                   scale)
    y, *counts = each_device_its_rows(
        partial(routed_experts, act=nn.silu, first=first))(
        h.reshape(-1, d), which, weight, mask.reshape(-1), *kernels)
    return y.reshape(h.shape), counts


class LatentMoeBlock(nn.Module):
    """Latent routed decoder block: pre-norm residuals (two RMS norms).

    Attention is latent: queries through a rank-``q_rank`` chain with an
    RMS norm in its middle, keys and values through one compressed
    ``kv_rank`` latent a span (normed, then expanded to every head's
    ``qk_nope_dim`` unrotated key columns and ``v_dim`` value columns)
    beside one rotary key of ``qk_rope_dim`` columns that all heads
    share; a head's query and key are [unrotated | rotary]. Nothing is
    cached and nothing absorbed into the output product: a row's keys and
    values live for one call.

    The feed-forward of a ``routed`` layer is ``n_experts`` SiLU-gated
    experts of width ``d_expert`` of which a span takes
    ``experts_per_span`` by ``biased_sigmoid`` (the router reads the
    normed input; its product, the sigmoid and the top-k run in float32)
    plus, where ``shared_experts`` > 0, one SiLU-gated expert of width
    ``shared_experts * d_expert`` that every span takes; that of a layer
    that is not routed is one SwiGLU of width ``d_ff``, and it returns no
    counts. No bias but the router's selection bias; parameters are held
    in ``param_dtype``."""

    d_model: int
    n_heads: int
    q_rank: int
    kv_rank: int
    qk_nope_dim: int
    qk_rope_dim: int
    v_dim: int
    d_ff: int
    n_experts: int
    experts_per_span: int
    d_expert: int
    shared_experts: int
    route_scale: float
    routed: bool
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16
    norm_eps: float = 1e-6

    @nn.compact
    def __call__(self, x: jnp.ndarray, mask: jnp.ndarray,
                 attn_mask: jnp.ndarray, cos: jnp.ndarray,
                 sin: jnp.ndarray) -> tuple[jnp.ndarray, Any]:
        def dense(features: int, name: str, dtype: Any = self.dtype,
                  **kw) -> nn.Dense:
            return nn.Dense(features, use_bias=False, dtype=dtype,
                            param_dtype=self.param_dtype,
                            kernel_init=rounded_lecun(), name=name, **kw)

        def rms(name: str, h: jnp.ndarray) -> jnp.ndarray:
            return nn.RMSNorm(epsilon=self.norm_eps, dtype=self.dtype,
                              param_dtype=self.param_dtype, name=name)(h)

        def norm(name: str, h: jnp.ndarray) -> jnp.ndarray:
            with jax.named_scope("norm"):
                return rms(name, h)

        def expand(latent: jnp.ndarray, name: str, first: int,
                   second: int) -> tuple[jnp.ndarray, jnp.ndarray]:
            """Out of a latent to every head's ``first`` and ``second``
            columns, (..., H, first) and (..., H, second): one kernel
            (rank, H x (first + second)), a head's columns side by side
            as published, cut here and not in its product, so that no
            (spans, H, first + second) value is made to be sliced."""
            w = Kernel((latent.shape[-1], H * (first + second)),
                       self.param_dtype, name=name)()
            w = w.astype(self.dtype).reshape(-1, H, first + second)
            return tuple(jnp.einsum("...r,rhd->...hd", latent, part)
                         for part in (w[..., :first], w[..., first:]))

        H, d_n, d_r, d_v = (self.n_heads, self.qk_nope_dim,
                            self.qk_rope_dim, self.v_dim)
        h = norm("attn_norm", x)
        with jax.named_scope("latent"):
            q_n, q_r = expand(
                rms("q_a_norm", dense(self.q_rank, "q_a_proj")(h)),
                "q_b_proj", d_n, d_r)
            q = jnp.concatenate([q_n, rotate(q_r, cos, sin)], axis=-1)
            kv = dense(self.kv_rank + d_r, "kv_a_proj")(h)
            # one rotary key a span, the same for every head
            k_r = rotate(kv[..., None, self.kv_rank:], cos, sin)
            k_n, v = expand(rms("kv_a_norm", kv[..., :self.kv_rank]),
                            "kv_b_proj", d_n, d_v)
            k = jnp.concatenate(
                [k_n, jnp.broadcast_to(k_r, k_n.shape[:-1] + (d_r,))],
                axis=-1)
        with jax.named_scope("attn"):
            h = attention(q, k, v, attn_mask, self.dtype)
            h = dense(self.d_model, "o_proj")(
                h.reshape(x.shape[:-1] + (H * d_v,)))
        x = x + h
        h = norm("mlp_norm", x)
        if not self.routed:
            return x + swiglu(dense, h, self.d_ff, self.d_model), None
        y, counts = sigmoid_experts(
            self, dense, h, mask, self.n_experts, self.experts_per_span,
            self.d_expert, self.route_scale)
        x = x + y
        if self.shared_experts:
            x = x + swiglu(dense, h, self.shared_experts * self.d_expert,
                           self.d_model, SHARED)
        return x, counts


class LatentMoeDecoder(nn.Module):
    """Embedding trunk + a stack of latent routed blocks, each applied
    once: the first ``dense_layers`` with a dense feed-forward, the rest
    routed. Every layer rotates its rotary columns; attention is causal
    within a trace. Returns the normed output, (routed layers, n_experts)
    the assignments each expert of each routed layer took, and the rows
    the route moved (``routed_counts``)."""

    service_vocab: int
    name_vocab: int
    attr_vocab: int
    d_model: int
    n_heads: int
    n_layers: int
    dense_layers: int
    q_rank: int
    kv_rank: int
    qk_nope_dim: int
    qk_rope_dim: int
    v_dim: int
    d_ff: int
    n_experts: int
    experts_per_span: int
    d_expert: int
    shared_experts: int
    route_scale: float
    rope_theta: float
    norm_eps: float
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, categorical, continuous, mask,
                 deterministic: bool = True,
                 positions: jnp.ndarray | None = None,
                 segments: jnp.ndarray | None = None):
        with jax.named_scope("embed"):
            x = SpanEmbedder(self.service_vocab, self.name_vocab,
                             self.attr_vocab, self.d_model, self.dtype,
                             *ROUTED_EMBED_INITS,
                             name="embed")(categorical, continuous)
            x = x * mask[..., None].astype(self.dtype)
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(mask.shape[-1]),
                                         mask.shape)
        with jax.named_scope("attn_mask"):
            attn_mask = causal_mask(mask, positions, segments)[:, None]
        with jax.named_scope("latent"):
            cos, sin = rotary_tables(positions, self.qk_rope_dim,
                                     self.rope_theta, self.dtype)
        counts = []
        for i in range(self.n_layers):
            x, count = LatentMoeBlock(
                self.d_model, self.n_heads, self.q_rank, self.kv_rank,
                self.qk_nope_dim, self.qk_rope_dim, self.v_dim, self.d_ff,
                self.n_experts, self.experts_per_span, self.d_expert,
                self.shared_experts, self.route_scale,
                i >= self.dense_layers, self.dtype, self.param_dtype,
                self.norm_eps, name=f"block_{i}")(x, mask, attn_mask, cos,
                                                  sin)
            if count is not None:
                counts.append(count)
        with jax.named_scope("norm"):
            x = nn.RMSNorm(epsilon=self.norm_eps, dtype=self.dtype,
                           param_dtype=self.param_dtype,
                           name="final_rms")(x)
        return (x, *routed_counts(counts))


class PostNormMoeBlock(nn.Module):
    """Post-norm routed decoder block: no norm ahead of a sublayer, an RMS
    norm on each sublayer's output before it joins the residual stream.

    Attention has ``n_heads`` query heads of ``head_dim`` over
    ``n_kv_heads`` key/value heads; each head's query and key is RMS-normed
    over its own columns (one scale of ``head_dim`` for all query heads,
    one for all key heads) and then rotated where ``rope`` says: a layer
    without it has no positions at all (its mask, the caller's, carries
    the causal order).

    The feed-forward of a ``routed`` layer reads the raw residual stream:
    ``sigmoid_experts`` over ``n_experts`` SiLU-gated experts of width
    ``d_expert``, of which this device holds ``experts_held`` from
    ``first_expert_held`` on (the router and the weights over all k choices
    are the whole layer's; the held share's part is what is computed),
    plus, where ``shared_experts`` > 0, a shared expert every span takes;
    that of a layer that is not routed is one SwiGLU of width ``d_ff``,
    and it returns no counts. No bias but the router's selection bias;
    parameters are held in ``param_dtype``."""

    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    n_experts: int
    experts_held: int
    first_expert_held: int
    experts_per_span: int
    d_expert: int
    shared_experts: int
    route_scale: float
    rope: bool
    routed: bool
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16
    norm_eps: float = 1e-6

    @nn.compact
    def __call__(self, x: jnp.ndarray, mask: jnp.ndarray,
                 attn_mask: jnp.ndarray, cos: jnp.ndarray,
                 sin: jnp.ndarray) -> tuple[jnp.ndarray, Any]:
        def dense(features: int, name: str, dtype: Any = self.dtype,
                  **kw) -> nn.Dense:
            return nn.Dense(features, use_bias=False, dtype=dtype,
                            param_dtype=self.param_dtype,
                            kernel_init=rounded_lecun(), name=name, **kw)

        def rms(name: str, h: jnp.ndarray) -> jnp.ndarray:
            return nn.RMSNorm(epsilon=self.norm_eps, dtype=self.dtype,
                              param_dtype=self.param_dtype, name=name)(h)

        def norm(name: str, h: jnp.ndarray) -> jnp.ndarray:
            with jax.named_scope("norm"):
                return rms(name, h)

        H, K, hd = self.n_heads, self.n_kv_heads, self.head_dim
        with jax.named_scope("attn"):
            q = dense(H * hd, "q_proj")(x).reshape(x.shape[:-1] + (H, hd))
            k = dense(K * hd, "k_proj")(x).reshape(x.shape[:-1] + (K, hd))
            v = dense(K * hd, "v_proj")(x).reshape(x.shape[:-1] + (K, hd))
        with jax.named_scope("qk"):
            q, k = rms("q_norm", q), rms("k_norm", k)
            if self.rope:
                q, k = rotate(q, cos, sin), rotate(k, cos, sin)
        with jax.named_scope("attn"):
            h = attention(q, k, v, attn_mask, self.dtype)
            h = dense(self.d_model, "o_proj")(
                h.reshape(x.shape[:-1] + (H * hd,)))
        x = x + norm("attn_out_norm", h)
        if not self.routed:
            return x + norm("mlp_out_norm",
                            swiglu(dense, x, self.d_ff, self.d_model)), None
        f, counts = sigmoid_experts(
            self, dense, x, mask, self.n_experts, self.experts_per_span,
            self.d_expert, self.route_scale, self.experts_held,
            None if self.experts_held == self.n_experts
            else self.first_expert_held)
        if self.shared_experts:
            f = f + swiglu(dense, x, self.shared_experts * self.d_expert,
                           self.d_model, SHARED)
        return x + norm("mlp_out_norm", f), counts


class PostNormMoeDecoder(nn.Module):
    """Embedding trunk + a stack of post-norm routed blocks, each applied
    once: the first ``dense_layers`` with a dense feed-forward, the rest
    routed. ``rope_layout`` and ``window_layout`` say layer by layer
    whether the block rotates its queries and keys and whether its
    attention is cut to the last ``window`` spans of the trace
    (``window_masks``). Returns the normed output, (routed layers,
    experts_held) the assignments each held expert of each routed layer
    took, and the rows the route moved (``routed_counts``)."""

    service_vocab: int
    name_vocab: int
    attr_vocab: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    n_layers: int
    dense_layers: int
    d_ff: int
    n_experts: int
    experts_held: int
    first_expert_held: int
    experts_per_span: int
    d_expert: int
    shared_experts: int
    route_scale: float
    rope_layout: tuple[int, ...]
    window_layout: tuple[int, ...]
    window: int
    rope_theta: float
    norm_eps: float
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, categorical, continuous, mask,
                 deterministic: bool = True,
                 positions: jnp.ndarray | None = None,
                 segments: jnp.ndarray | None = None):
        with jax.named_scope("embed"):
            x = SpanEmbedder(self.service_vocab, self.name_vocab,
                             self.attr_vocab, self.d_model, self.dtype,
                             *ROUTED_EMBED_INITS,
                             name="embed")(categorical, continuous)
            x = x * mask[..., None].astype(self.dtype)
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(mask.shape[-1]),
                                         mask.shape)
        masks = window_masks(mask, positions, segments, self.window)
        with jax.named_scope("qk"):
            cos, sin = rotary_tables(positions, self.head_dim,
                                     self.rope_theta, self.dtype)
        counts = []
        for i, (rope, window) in enumerate(zip(self.rope_layout,
                                               self.window_layout)):
            x, count = PostNormMoeBlock(
                self.d_model, self.n_heads, self.n_kv_heads, self.head_dim,
                self.d_ff, self.n_experts, self.experts_held,
                self.first_expert_held, self.experts_per_span,
                self.d_expert, self.shared_experts, self.route_scale,
                bool(rope), i >= self.dense_layers, self.dtype,
                self.param_dtype, self.norm_eps, name=f"block_{i}")(
                x, mask, masks[bool(window)], cos, sin)
            if count is not None:
                counts.append(count)
        with jax.named_scope("norm"):
            x = nn.RMSNorm(epsilon=self.norm_eps, dtype=self.dtype,
                           param_dtype=self.param_dtype,
                           name="final_rms")(x)
        return (x, *routed_counts(counts))


def encoder_attention(h, attn_mask, segments, n_heads: int, dtype,
                      dropout: float, deterministic: bool):
    """The encoder block's attention sublayer after its LayerNorm, as flax's
    ``MultiHeadDotProductAttention`` (its parameters, under its name),
    over ``attn_mask`` (T, 1, L, L). Given ``segments`` (packed rows,
    row-local trace ids, 0 = padding) of a shape ``kernel_attends`` takes,
    and no dropout at work, the TPU runs it as
    ``attention_kernel.self_attention`` over the same parameters; every
    other platform runs flax's."""
    attention = nn.MultiHeadDotProductAttention(
        num_heads=n_heads, dtype=dtype, dropout_rate=dropout,
        deterministic=deterministic)
    out = attention(h, h, mask=attn_mask)
    mesh = jax.sharding.get_abstract_mesh()
    if segments is None or (dropout and not deterministic) \
            or not kernel_attends(dtype, *h.shape[:2], h.shape[-1], n_heads,
                                  None if mesh.empty else dict(mesh.shape)):
        return out
    return jax.lax.platform_dependent(
        h, segments, attention.variables["params"],
        tpu=partial(attention_kernel.self_attention, heads=n_heads,
                    dtype=dtype),
        default=lambda *_: out)


def kernel_attends(dtype: Any, rows: int, L: int, d_model: int, n_heads: int,
                   mesh_shape: dict | None = None,
                   platform: str = "tpu") -> bool:
    """Whether the encoder block's attention over ``rows`` packed rows
    runs as the ``attention_kernel`` on ``platform``: a TPU, the kernel
    takes the shape each device sees, and no mesh axis but the rows'
    splits the block (a Pallas kernel cannot be partitioned, and a
    ``model`` axis splits the heads). ``mesh_shape`` is the mesh's axis
    sizes, None for none."""
    mesh_shape = mesh_shape or {}
    shards = mesh_shape.get(ROWS_AXIS, 1)
    others = [n for axis, n in mesh_shape.items() if axis != ROWS_AXIS]
    return (platform == "tpu" and all(n == 1 for n in others)
            and rows % shards == 0
            and attention_kernel.supported(dtype, rows // shards, L,
                                           d_model, n_heads))
