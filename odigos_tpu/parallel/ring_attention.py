"""Ring attention — sequence-parallel exact attention for long traces.

The reference's "long context" analog is whole-trace processing: tail sampling
and servicegraph need every span of a trace on one replica (SURVEY.md §5.7).
Our model stage must score trace trees that can exceed one chip's memory at
batch scale, so attention over the span sequence is sharded on the "seq" mesh
axis: each device holds a block of the sequence; K/V blocks rotate around the
ring via ppermute while partial attention accumulates with a streaming
(flash-style) log-sum-exp — exact softmax attention, N_seq steps, each
overlapping compute with the ICI transfer.

Reference technique: Liu et al., "Ring Attention with Blockwise Transformers
for Near-Infinite Context" (arXiv:2310.01889). Implementation is original,
shaped for shard_map + ppermute.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

NEG_INF = -1e30

# Partition-spec declaration per sharded entry point (package-hygiene
# lint, ISSUE 7 satellite — an undeclared sharded site silently runs
# replicated): ring attention shards the SEQUENCE axis, nothing else.
PARTITION_SPECS = {
    "ring_attention": "q/k/v (B, L, H, D) and mask (B, L) sharded on "
                      "the 'seq' axis via shard_map in/out_specs; K/V "
                      "blocks rotate by ppermute, output sharded like q",
}


def _block_attention(q, k, v, kv_mask, scale):
    """One q-block x kv-block attention with streaming stats.

    q: (B, Lq, H, D), k/v: (B, Lk, H, D), kv_mask: (B, Lk) bool
    returns (unnormalized_out, row_max, row_sumexp)
    """
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    logits = jnp.where(kv_mask[:, None, None, :], logits, NEG_INF)
    m = logits.max(axis=-1)                      # (B, H, Lq)
    p = jnp.exp(logits - m[..., None])
    p = jnp.where(kv_mask[:, None, None, :], p, 0.0)
    s = p.sum(axis=-1)                           # (B, H, Lq)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v)      # (B, Lq, H, D)
    return o, m, s


def _ring_body(q, k, v, kv_mask, axis_name, scale):
    """Per-device body under shard_map: rotate K/V around the ring."""
    n = jax.lax.psum(1, axis_name)
    B, Lq, H, D = q.shape

    # accumulators start replicated; mark them device-varying over the ring
    # axis so the fori_loop carry type stays stable (shard_map vma typing)
    def _vary(x):
        return jax.lax.pcast(x, axis_name, to="varying")

    o = _vary(jnp.zeros((B, Lq, H, D), jnp.float32))
    m = _vary(jnp.full((B, H, Lq), NEG_INF, jnp.float32))
    s = _vary(jnp.zeros((B, H, Lq), jnp.float32))

    def step(i, carry):
        o, m, s, k, v, kv_mask = carry
        o_i, m_i, s_i = _block_attention(q, k, v, kv_mask, scale)
        # streaming softmax merge
        m_new = jnp.maximum(m, m_i)
        alpha = jnp.exp(m - m_new)
        beta = jnp.exp(m_i - m_new)
        o = o * alpha.transpose(0, 2, 1)[..., None] \
            + o_i * beta.transpose(0, 2, 1)[..., None]
        s = s * alpha + s_i * beta
        perm = [(j, (j + 1) % n) for j in range(n)]
        k = jax.lax.ppermute(k, axis_name, perm)
        v = jax.lax.ppermute(v, axis_name, perm)
        kv_mask = jax.lax.ppermute(kv_mask, axis_name, perm)
        return o, m_new, s, k, v, kv_mask

    o, m, s, *_ = jax.lax.fori_loop(
        0, n, step, (o, m, s, k.astype(jnp.float32),
                     v.astype(jnp.float32), kv_mask))
    return o / jnp.maximum(s, 1e-30).transpose(0, 2, 1)[..., None]


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   mask: jax.Array, mesh: Mesh,
                   axis_name: str = "seq") -> jax.Array:
    """Exact masked attention with the sequence axis sharded over ``mesh``.

    q/k/v: (B, L, H, D) with L divisible by mesh.shape[axis_name];
    mask: (B, L) bool padding mask. Returns (B, L, H, D) float32.
    """
    scale = 1.0 / (q.shape[-1] ** 0.5)
    body = partial(_ring_body, axis_name=axis_name, scale=scale)
    spec_qkv = P(None, axis_name, None, None)
    spec_mask = P(None, axis_name)
    fn = shard_map(
        body, mesh=mesh,
        in_specs=(spec_qkv, spec_qkv, spec_qkv, spec_mask),
        out_specs=spec_qkv,
    )
    return fn(q, k, v, mask)


def reference_attention(q, k, v, mask):
    """Single-device exact attention (test oracle)."""
    scale = 1.0 / (q.shape[-1] ** 0.5)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    logits = jnp.where(mask[:, None, None, :], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    p = jnp.where(mask[:, None, None, :], p, 0.0)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
