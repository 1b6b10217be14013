"""``looped_decoder``: a stack of decoder layers run ``passes`` times over
the same weights, as the program's ``models/transformer.py`` builds it
under ``block: "decoder"`` from ``d_model``, ``n_heads``, ``n_layers``,
``d_ff``, ``passes``, ``rope_theta`` and ``norm_eps``.

The equations, after the looped language models of the Ouro family
(ByteDance, ``model_type`` ``ouro``: ``total_ut_steps`` passes over one
stack, sandwich norms), with this system's span embedder and span head
(``benchmark/reference.py``) in place of the vocabulary. ``x`` is a packed
row of spans, ``pos`` a span's place within its own trace, ``seg`` the
trace's number within the row::

    x0 = span_embedding(span)                       (no position table)
    for t in 1..passes:                             (same weights every t)
      for l in 1..n_layers:
        h = RMS1_l(x);  q, k, v = h Wq_l, h Wk_l, h Wv_l     (n_heads x d_head, no bias)
        q, k = rope(q, pos), rope(k, pos)           (theta rope_theta, rotate-half)
        a = softmax(q k^T / sqrt(d_head)  over j: seg_j = seg_i, pos_j <= pos_i)
        x = x + RMS2_l((a v) Wo_l)
        h = RMS3_l(x)
        x = x + RMS4_l((silu(h Wg_l) * (h Wu_l)) Wd_l)       (d_ff wide)
      x = RMSf(x)                                   (the final norm closes every pass)
    score = sigmoid(span_head(x))
    RMS(x) = x / sqrt(mean(x^2) + norm_eps) * g
    rope(u, p)_i = u_i cos(p w_i) - u_{i+d_head/2} sin(p w_i)          (i < d_head/2)
    rope(u, p)_{i+d_head/2} = u_{i+d_head/2} cos(p w_i) + u_i sin(p w_i),  w_i = theta^(-2i/d_head)

``early_exit_threshold`` is 1 as published: every span runs every pass and
the score is the last pass's. The exit gate (a projection of width 1 whose
value the output does not depend on at that threshold) is in neither
program nor reference.

Departures and assumptions (the configuration's ``assumed`` says the
same). The public ``config.json`` does not state where the norms sit, that
the projections have no bias, or the gate: the sandwich norms (one before
and one after each sublayer), the final norm inside the loop and bias-free
projections are the family's published description as this file knows it.
Rows hold ``max_len`` positions where the model allows 65,536; a trace
longer than a row is cut into pieces that attend within themselves, each
piece's positions starting at 0. Attention is within a trace's own spans (a
row holds several traces side by side). Weights are what flax makes from
the seed: lecun-normal kernels, unit norm scales, each layer's made inside
the jitted layer step, so that 9.9 GB of float32 parameters never sit on
the device at once.

``precision="fp8"`` is the control: the seven matrix products of every
layer application computed from inputs cast to float8
(``reference._matmul``), the precision next below the configuration's
bfloat16.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Iterable

import numpy as np

from benchmark import reference

# the scopes models/layers.py BLOCK_PARTS["decoder"] writes, and the part
# each folds into
PARTS = {"embed": "rest", "attn_mask": "rest", "attn": "attn", "mlp": "mlp",
         "norm": "norm", "head": "rest"}
CONTROL = "fp8"
KERNELS = ("q_proj", "k_proj", "v_proj", "o_proj", "gate_proj", "up_proj",
           "down_proj")


# ------------------------------------------------------------- operations


def flops_by_part(model: dict[str, Any], piece_lengths: Iterable[int],
                  ) -> dict[str, float]:
    """Operations for traces cut into pieces of these lengths (a trace of
    up to ``max_len`` spans is one piece), 2 a multiply-add, real spans
    only, every block term times ``passes``: ``attn`` is the four d x d
    projections a span and layer application and the attention core over
    the causal pairs of each piece (q k^T and a v, each p (p + 1) / 2 x d
    multiply-adds); ``mlp`` the three d x d_ff products; ``norm`` the 4 a
    layer application and 1 a pass RMS norms, 4 d operations a span each
    (d multiply-adds for the mean square, d multiplies by the root, d by
    the scale); ``rest`` the embedder's continuous projection and the span
    head."""
    d, ff, n = model["d_model"], model["d_ff"], model["n_layers"]
    passes = model["passes"]
    pieces = list(piece_lengths)
    spans = sum(pieces)
    return {
        "attn": passes * (spans * 2.0 * n * 4 * d * d
                          + sum(2.0 * n * 2 * (p * (p + 1) // 2) * d
                                for p in pieces)),
        "mlp": passes * spans * 2.0 * n * 3 * d * ff,
        "norm": passes * spans * (4 * n + 1) * 4.0 * d,
        "rest": spans * 2.0 * (reference.CONT_WIDTH * d + d)}


# ---------------------------------------------------------------- weights


def layer_keys(seed: int, n_layers: int):
    """(n_layers, 7, 2) uint32: the keys of each block's seven kernels, in
    ``KERNELS``' order."""
    import jax
    import jax.numpy as jnp

    root = jax.random.PRNGKey(seed)
    return jnp.stack([jnp.stack(
        [reference._param_key(root, ("encoder", "stack", f"block_{i}", nm), 1)
         for nm in KERNELS]) for i in range(n_layers)])


def block_weights(keys, d_model: int, d_ff: int) -> dict[str, Any]:
    """One block's kernels from its seven keys: lecun-normal, as flax
    makes them. The norms' scales are one and there is no bias."""
    import jax.numpy as jnp

    lecun, _ = reference._inits()
    d, f32 = d_model, jnp.float32
    shapes = ((d, d), (d, d), (d, d), (d, d), (d, d_ff), (d, d_ff), (d_ff, d))
    return {nm: lecun(keys[i], shape, f32)
            for i, (nm, shape) in enumerate(zip(KERNELS, shapes))}


# ---------------------------------------------------------------- forward


def _rms(x, eps: float):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)


def _rope(u, cos, sin):
    """Rotate-half rotary embedding of (rows, L, heads, d_head)."""
    import jax.numpy as jnp

    half = u.shape[-1] // 2
    a, b = u[..., :half], u[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def block_step(x, allowed, cos, sin, keys, *, n_heads: int, d_ff: int,
               eps: float, precision: str):
    """One decoder block over (rows, L, d) with its weights made here
    from ``keys``; ``allowed`` is (rows, L, L) bool, ``cos`` and ``sin``
    (rows, L, 1, d_head / 2)."""
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST
    mm = reference._matmul(precision)
    rows, L, d = x.shape
    hd = d // n_heads
    w = block_weights(keys, d, d_ff)
    h = _rms(x, eps)
    q = _rope(mm(h, w["q_proj"]).reshape(rows, L, n_heads, hd), cos, sin)
    k = _rope(mm(h, w["k_proj"]).reshape(rows, L, n_heads, hd), cos, sin)
    v = mm(h, w["v_proj"]).reshape(rows, L, n_heads, hd)
    s = jnp.einsum("rqhd,rkhd->rhqk", q, k, precision=hi) / np.sqrt(hd)
    s = jnp.where(allowed[:, None], s, jnp.finfo(jnp.float32).min)
    a = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("rhqk,rkhd->rqhd", a, v, precision=hi).reshape(rows, L, d)
    x = x + _rms(mm(o, w["o_proj"]), eps)
    h = _rms(x, eps)
    h = jax.nn.silu(mm(h, w["gate_proj"])) * mm(h, w["up_proj"])
    return x + _rms(mm(h, w["down_proj"]), eps)


def decoder(seed: int, model: dict[str, Any], precision: str = "float32"):
    """The three steps of the forward pass, each jitted over one block of
    rows: ``embed(cat, cont, seg) -> x``, ``stack(x, seg, pos) -> x``
    (every layer in turn and the final norm, ``passes`` times) and
    ``head(x) -> scores``."""
    import jax
    import jax.numpy as jnp

    d, n_layers = int(model["d_model"]), int(model["n_layers"])
    n_heads, passes = int(model["n_heads"]), int(model["passes"])
    eps, theta = float(model["norm_eps"]), float(model["rope_theta"])
    half = d // n_heads // 2
    outer = reference.outer_weights(seed, d, int(model["max_len"]))

    # ``outer`` goes in as an argument: closed over, its tables would be
    # constants of the executable, 59 MB of a compile cache a seed
    @jax.jit
    def embed_step(outer, cat, cont, seg):
        return reference.span_embedding(outer, cat, cont) \
            * (seg > 0)[..., None]

    @jax.jit
    def tables(seg, pos):
        real = seg > 0
        allowed = (seg[:, :, None] == seg[:, None, :]) \
            & real[:, :, None] & real[:, None, :] \
            & (pos[:, :, None] >= pos[:, None, :])
        angle = pos[..., None].astype(jnp.float32) * theta ** (
            -jnp.arange(half, dtype=jnp.float32) / half)
        return allowed, jnp.cos(angle)[:, :, None], jnp.sin(angle)[:, :, None]

    step = jax.jit(partial(block_step, n_heads=n_heads,
                           d_ff=int(model["d_ff"]), eps=eps,
                           precision=precision))
    final_norm = jax.jit(partial(_rms, eps=eps))
    keys = layer_keys(seed, n_layers)

    def stack(x, seg, pos):
        allowed, cos, sin = tables(seg, pos)
        for _ in range(passes):
            for i in range(n_layers):
                x = step(x, allowed, cos, sin, keys[i])
            x = final_norm(x)
        return x

    head_step = jax.jit(reference.span_head)
    return (partial(embed_step, outer), stack, partial(head_step, outer))


def scores(frames, seed: int, model: dict[str, Any],
           precision: str = "float32", block_rows: int = 384,
           ) -> list[np.ndarray]:
    """The reference's score of every span of every frame, as one float32
    array per frame in the frame's own span order. A block of 384 rows
    holds the eight frames of the cell's pool, so that each layer's
    weights are made once a layer application."""
    embed, stack, head = decoder(seed, model, precision)
    return reference.score_rows(
        frames, int(model["max_len"]), block_rows,
        lambda cat, cont, seg, pos: head(stack(embed(cat, cont, seg),
                                               seg, pos)))
