"""``encoder_preln``: the pre-LN transformer encoder, as the program's
``models/transformer.py`` builds it from ``d_model``, ``n_heads``,
``n_layers``, ``d_ff`` and ``max_len``.

The equations, after arXiv:2010.11929 section 3.1, with this system's
span embedder and span head (``benchmark/reference.py``) in place of
patches and class token::

    x0 = span_embedding(span) + E_pos[position]
    h  = LN(x);  q, k, v = h W_q + b_q, h W_k + b_k, h W_v + b_v
    a  = softmax(q k^T / sqrt(d_head), over the spans of the same trace)
    x  = x + (a v) W_o + b_o
    x  = x + gelu_tanh(LN(x) W_1 + b_1) W_2 + b_2          (each layer)
    score = span_head(LN(x))

Departures from the published description (the configuration's ``assumed``
says the same): a learned position table over the row's ``max_len``
positions where ViT has one over 257 patches; tanh GELU where ViT has erf;
LayerNorm epsilon 1e-6; attention within a trace's own spans (a row holds
several traces side by side) and no class token. Weights are what flax
makes from the seed: lecun-normal kernels, zero biases, unit LayerNorm
scales, each layer's made inside the jitted layer step, so that 2.5 GB of
float32 parameters never sit on the device at once.

``precision="fp8"`` is the control: the six matrix products of every layer
computed from inputs cast to float8 (``reference._matmul``), the
precision next below the configuration's bfloat16.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Iterable

import numpy as np

from benchmark import reference

LN_EPS = 1e-6
# the scopes models/layers.py PARTS writes, and the part each folds into
PARTS = {"embed": "rest", "attn_mask": "rest", "attn": "attn", "mlp": "mlp",
         "final_norm": "rest", "head": "rest"}
CONTROL = "fp8"


# ------------------------------------------------------------- operations


def flops_by_part(model: dict[str, Any], piece_lengths: Iterable[int],
                  ) -> dict[str, float]:
    """Operations for traces cut into pieces of these lengths (a trace of
    up to ``max_len`` spans is one piece), 2 a multiply-add, real spans
    only: ``attn`` is the four d x d projections a span and layer and the
    attention core over each piece's own length (q k^T and a v, each
    length^2 x d multiply-adds a layer); ``mlp`` the two d x d_ff
    products; ``rest`` the embedder's continuous projection and the span
    head."""
    d, ff, n = model["d_model"], model["d_ff"], model["n_layers"]
    pieces = list(piece_lengths)
    spans = sum(pieces)
    return {
        "attn": spans * 2.0 * n * 4 * d * d
        + sum(2.0 * n * 2 * p * p * d for p in pieces),
        "mlp": spans * 2.0 * n * 2 * d * ff,
        "rest": spans * 2.0 * (reference.CONT_WIDTH * d + d)}


# ---------------------------------------------------------------- weights


def layer_keys(seed: int, n_layers: int):
    """(n_layers, 6, 2) uint32: the keys of each block's six kernels
    (query, key, value, out, feed-forward in, feed-forward out)."""
    import jax
    import jax.numpy as jnp

    root = jax.random.PRNGKey(seed)
    out = []
    for i in range(n_layers):
        blk = ("encoder", f"block_{i}")
        mha = blk + ("MultiHeadDotProductAttention_0",)
        out.append(jnp.stack(
            [reference._param_key(root, mha + (nm,), 1)
             for nm in ("query", "key", "value", "out")]
            + [reference._param_key(root, blk + (nm,), 1)
               for nm in ("Dense_0", "Dense_1")]))
    return jnp.stack(out)


def block_weights(keys, d_model: int, d_ff: int) -> dict[str, Any]:
    """One block's parameters from its six kernel keys: lecun-normal
    kernels, zero biases, unit LayerNorm scales (what flax makes)."""
    import jax.numpy as jnp

    lecun, _ = reference._inits()
    f32 = jnp.float32
    d = d_model
    return {
        "wq": lecun(keys[0], (d, d), f32), "wk": lecun(keys[1], (d, d), f32),
        "wv": lecun(keys[2], (d, d), f32), "wo": lecun(keys[3], (d, d), f32),
        "w1": lecun(keys[4], (d, d_ff), f32),
        "w2": lecun(keys[5], (d_ff, d), f32),
        "bq": jnp.zeros((d,), f32), "bk": jnp.zeros((d,), f32),
        "bv": jnp.zeros((d,), f32), "bo": jnp.zeros((d,), f32),
        "b1": jnp.zeros((d_ff,), f32), "b2": jnp.zeros((d,), f32),
        "ln1_s": jnp.ones((d,), f32), "ln1_b": jnp.zeros((d,), f32),
        "ln2_s": jnp.ones((d,), f32), "ln2_b": jnp.zeros((d,), f32),
    }


# ---------------------------------------------------------------- forward


def _layer_norm(x, scale, bias):
    import jax.numpy as jnp

    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + LN_EPS) * scale + bias


def block_step(x, allowed, keys, *, n_heads: int, d_ff: int,
               precision: str):
    """One pre-LN encoder block over (rows, L, d) with its weights made
    here from ``keys``; ``allowed`` is (rows, L, L) bool."""
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST
    mm = reference._matmul(precision)
    rows, L, d = x.shape
    hd = d // n_heads
    w = block_weights(keys, d, d_ff)
    h = _layer_norm(x, w["ln1_s"], w["ln1_b"])
    q = (mm(h, w["wq"]) + w["bq"]).reshape(rows, L, n_heads, hd)
    k = (mm(h, w["wk"]) + w["bk"]).reshape(rows, L, n_heads, hd)
    v = (mm(h, w["wv"]) + w["bv"]).reshape(rows, L, n_heads, hd)
    s = jnp.einsum("rqhd,rkhd->rhqk", q, k, precision=hi) / np.sqrt(hd)
    s = jnp.where(allowed[:, None], s, jnp.finfo(jnp.float32).min)
    a = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("rhqk,rkhd->rqhd", a, v, precision=hi).reshape(rows, L, d)
    x = x + mm(o, w["wo"]) + w["bo"]
    h = _layer_norm(x, w["ln2_s"], w["ln2_b"])
    h = jax.nn.gelu(mm(h, w["w1"]) + w["b1"], approximate=True)
    return x + mm(h, w["w2"]) + w["b2"]


def encoder(seed: int, model: dict[str, Any], precision: str = "float32"):
    """The three steps of the forward pass, each jitted over one block of
    rows: ``embed(cat, cont, seg, pos) -> x``, ``stack(x, seg) -> x`` (every
    layer in turn) and ``head(x) -> scores``."""
    import jax
    import jax.numpy as jnp

    d, n_layers = int(model["d_model"]), int(model["n_layers"])
    outer = reference.outer_weights(seed, d, int(model["max_len"]))

    @jax.jit
    def embed(cat, cont, seg, pos):
        x = reference.span_embedding(outer, cat, cont) + outer["pos"][pos]
        return x * (seg > 0)[..., None]

    step = jax.jit(partial(block_step, n_heads=int(model["n_heads"]),
                           d_ff=int(model["d_ff"]), precision=precision))
    keys = layer_keys(seed, n_layers)

    def stack(x, seg):
        allowed = (seg[:, :, None] == seg[:, None, :]) \
            & (seg > 0)[:, :, None] & (seg > 0)[:, None, :]
        for i in range(n_layers):
            x = step(x, allowed, keys[i])
        return x

    @jax.jit
    def head(x):
        return reference.span_head(
            outer, _layer_norm(x, jnp.ones((d,)), jnp.zeros((d,))))

    return embed, stack, head


def scores(frames, seed: int, model: dict[str, Any],
           precision: str = "float32", block_rows: int = 256,
           ) -> list[np.ndarray]:
    """The reference's score of every span of every frame, as one float32
    array per frame in the frame's own span order."""
    embed, stack, head = encoder(seed, model, precision)
    return reference.score_rows(
        frames, int(model["max_len"]), block_rows,
        lambda cat, cont, seg, pos: head(stack(embed(cat, cont, seg, pos),
                                               seg)))
