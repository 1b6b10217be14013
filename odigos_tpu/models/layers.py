"""Shared flax modules: span embedding trunk + transformer encoder blocks.

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
            h = nn.gelu(h)
            h = nn.Dense(self.d_model, dtype=self.dtype)(h)
            return x + h


# the parts of a scoring call, as ``jax.named_scope`` writes them into
# every operation's metadata (and so into the device trace): the
# benchmark's step_attn_ms / step_mlp_ms / step_rest_ms fold operations
# by the first of these names on their path. Scopes only: flax's
# parameter paths do not see them.
PARTS = ("embed", "attn_mask", "attn", "mlp", "final_norm", "head")


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
