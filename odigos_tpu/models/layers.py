"""Shared flax modules: span embedding trunk + the transformer's two block
kinds (``BLOCK_PARTS``): the pre-LN bidirectional encoder block under a
learned position table, and the decoder block (sandwich RMS norms, rotary
positions, causal attention within a trace, SwiGLU, no biases) whose stack
runs ``passes`` times over the same parameters as a loop on the device.

MXU discipline (see /opt/skills/guides/pallas_guide.md and SURVEY.md env
notes): feature dims multiples of 128, bfloat16 activations with float32
params, no data-dependent shapes — everything here jits to static-shape
einsums that XLA tiles onto the systolic array.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..features.featurizer import CAT_FIELDS


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

    @nn.compact
    def __call__(self, categorical: jnp.ndarray,
                 continuous: jnp.ndarray) -> jnp.ndarray:
        d = self.d_model
        svc_table = nn.Embed(self.service_vocab, d, dtype=self.dtype,
                             name="service_embed")
        x = svc_table(categorical[..., 0])
        x += nn.Embed(self.name_vocab, d, dtype=self.dtype,
                      name="name_embed")(categorical[..., 1])
        x += nn.Embed(8, d, dtype=self.dtype,
                      name="kind_embed")(categorical[..., 2])
        x += nn.Embed(4, d, dtype=self.dtype,
                      name="status_embed")(categorical[..., 3])
        x += svc_table(categorical[..., 4])  # parent edge, shared table
        n_attr = categorical.shape[-1] - len(CAT_FIELDS)
        if n_attr > 0:
            attr_table = nn.Embed(self.attr_vocab, d, dtype=self.dtype,
                                  name="attr_embed")
            x += attr_table(categorical[..., len(CAT_FIELDS):]).sum(axis=-2)
        x += nn.Dense(d, dtype=self.dtype, name="cont_proj")(
            continuous.astype(self.dtype))
        return x


class EncoderBlock(nn.Module):
    """Pre-LN bidirectional transformer block with padding mask."""

    d_model: int
    n_heads: int
    d_ff: int
    dtype: Any = jnp.bfloat16
    dropout: float = 0.0

    @nn.compact
    def __call__(self, x: jnp.ndarray, attn_mask: jnp.ndarray,
                 deterministic: bool = True) -> jnp.ndarray:
        # attn_mask: (T, 1, L, L) bool, True where attention is allowed
        with jax.named_scope("attn"):
            h = nn.LayerNorm(dtype=self.dtype)(x)
            h = nn.MultiHeadDotProductAttention(
                num_heads=self.n_heads, dtype=self.dtype,
                dropout_rate=self.dropout, deterministic=deterministic,
            )(h, h, mask=attn_mask)
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
BLOCK_PARTS = {
    "encoder": PARTS,
    "decoder": ("embed", "attn_mask", "attn", "mlp", "norm", "head"),
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
                x, attn_mask, deterministic)
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
            h = nn.dot_product_attention(
                q, k, v, mask=attn_mask, deterministic=True,
                dtype=self.dtype, force_fp32_for_softmax=True)
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
            attn_mask = mask[..., None] & mask[..., None, :] \
                & (positions[..., None] >= positions[..., None, :])
            if segments is not None:
                attn_mask &= segments[..., None] == segments[..., None, :]
            attn_mask = attn_mask[:, None]
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
