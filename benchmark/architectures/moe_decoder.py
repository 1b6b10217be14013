"""``moe_decoder``: a stack of routed decoder layers, as the program's
``models/transformer.py`` builds it under ``block: "moe"`` from ``d_model``,
``n_heads``, ``n_kv_heads``, ``head_dim``, ``n_layers``, ``n_experts``,
``experts_per_span``, ``d_expert``, ``rope_layout``, ``window_layout``,
``window``, ``rope_theta`` and ``norm_eps``.

The equations, after the SmallThinker family (PowerInfer,
``smallthinker_21b_instruct``: sparse ReLU-gated experts with the router
ahead of attention, grouped query heads, layers with rotary positions and
a sliding window beside layers with neither), with this system's span
embedder and span head (``benchmark/reference.py``) in place of the
vocabulary. ``x`` is a packed row of spans, ``pos`` a span's place within
its own trace, ``seg`` the trace's number within the row, ``l`` the
layer, ``H`` = ``n_heads``, ``K`` = ``n_kv_heads``, ``k`` =
``experts_per_span``::

    x = span_embedding(span)                         (no position table)
    for l in 0..n_layers-1:
      r = x Wr_l                                     (n_experts logits, from the layer's raw input:
                                                      the router sits ahead of norm and attention)
      h = RMS1_l(x);  q = h Wq_l (H x head_dim),  k, v = h Wk_l, h Wv_l (K x head_dim), no bias
      if rope_layout[l]:  q, k = rope(q, pos), rope(k, pos)      (theta rope_theta, rotate-half)
      allowed(i, j) = seg_j = seg_i and pos_j <= pos_i and (pos_i - pos_j < window if window_layout[l])
      a = softmax(q k^T / sqrt(head_dim)  over allowed);  query head g reads key/value head g // (H / K)
      x = x + (a v) Wo_l
      h = RMS2_l(x)
      E = the k largest of r;  w = softmax(r[E])     (the top-k first, the softmax over the chosen)
      x = x + sum_{e in E} w_e (relu(h Wg_{l,e}) * (h Wu_{l,e})) Wd_{l,e}      (d_expert wide)
    x = RMSf(x);  score = sigmoid(span_head(x))
    RMS(x) = x / sqrt(mean(x^2) + norm_eps) * g
    rope(u, p)_i = u_i cos(p w_i) - u_{i+head_dim/2} sin(p w_i)          (i < head_dim/2)
    rope(u, p)_{i+head_dim/2} = u_{i+head_dim/2} cos(p w_i) + u_i sin(p w_i),  w_i = theta^(-2i/head_dim)

Every expert is computed for every span here (a dense pass, n_experts /
k times the work) and weighted by ``w_e``, zero for an expert the span
did not choose: the plain way, where the program sorts the assignments by
expert and runs grouped products.

Departures and assumptions (the configuration's ``assumed`` says the
same). The public ``config.json`` does not state that the router reads
the layer's raw input and not its normed one, that the top-k comes before
the softmax (``moe_primary_router_apply_softmax`` and ``norm_topk_prob``
say only that both are there; a softmax over the chosen already sums to
one), the experts' activation, that nothing has a bias, or where the
norms sit: these follow the family's published description as this file
knows it. Rows hold ``max_len`` positions where the model allows 16,384;
a trace longer than a row is cut into pieces that attend within
themselves, each piece's positions starting at 0. Attention is within a
trace's own spans (a row holds several traces). The final norm closes the
stack as run, after ``n_layers`` layers, wherever the configuration cut
it. Weights are random from the seed: each kernel truncated lecun-normal
over its own fan-in (an expert's over ``d_model`` or ``d_expert``, the
expert axis counting no fan), drawn in float32 with the key flax hands
the program's parameter and rounded once to bfloat16, which is what the
program holds; unit norm scales; a layer's made inside the jitted layer
step, so that one layer's 1.6 GB of float32 kernels and never the stack
sit on the device.

``precision="fp8"`` is the control: the seven matrix products a span
passes through in a layer (q, k, v, out and an expert's three) computed
from inputs cast to float8 (``reference._matmul``), the precision next
below the configuration's bfloat16. The router's product stays float32 in
the control, as the configuration states it: the control then parts from
the sound run by its arithmetic and by the choices that arithmetic moves
downstream, not by a router nobody would build.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Iterable

import numpy as np

from benchmark import reference

# the scopes models/layers.py BLOCK_PARTS["moe"] writes, and the part each
# folds into
PARTS = {"embed": "rest", "attn_mask": "rest", "attn": "attn",
         "route": "route", "mlp": "mlp", "norm": "norm", "head": "rest"}
CONTROL = "fp8"
KERNELS = ("router", "q_proj", "k_proj", "v_proj", "o_proj",
           "experts_gate", "experts_up", "experts_down")


# ------------------------------------------------------------- operations


def _sizes(model: dict[str, Any]) -> tuple[int, ...]:
    return tuple(int(model[k]) for k in (
        "d_model", "n_heads", "n_kv_heads", "head_dim", "n_layers",
        "n_experts", "experts_per_span", "d_expert"))


def flops_by_part(model: dict[str, Any], piece_lengths: Iterable[int],
                  ) -> dict[str, float]:
    """Operations for traces cut into pieces of these lengths (a trace of
    up to ``max_len`` spans is one piece), 2 a multiply-add, real spans
    only: ``attn`` is the four projections a span and layer (d x H
    head_dim twice, d x K head_dim twice) and the attention core over the
    pairs the layer's mask allows (q k^T and a v, each H head_dim
    multiply-adds a pair; span i of a piece sees min(i + 1, window) spans
    in a layer with a window, i + 1 in one without); ``mlp`` the k
    experts a span takes, three d x d_expert products each; ``route`` the
    router's d x n_experts product; ``norm`` the 2 a layer and 1 final RMS
    norms, 4 d operations a span each; ``rest`` the embedder's continuous
    projection and the span head."""
    d, H, K, hd, n, E, k, f = _sizes(model)
    window = int(model["window"])
    pieces = list(piece_lengths)
    spans = sum(pieces)
    windowed = sum(bool(w) for w in model["window_layout"])

    def pairs(p: int, cut: bool) -> int:
        if not cut or p <= window:
            return p * (p + 1) // 2
        return window * (window + 1) // 2 + (p - window) * window

    core = sum(windowed * pairs(p, True) + (n - windowed) * pairs(p, False)
               for p in pieces)
    return {
        "attn": spans * 2.0 * n * (2 * d * H * hd + 2 * d * K * hd)
        + 2.0 * 2 * core * H * hd,
        "mlp": experts_flops(model, spans),
        "route": spans * 2.0 * n * d * E,
        "norm": spans * (2 * n + 1) * 4.0 * d,
        "rest": spans * 2.0 * (reference.CONT_WIDTH * d + d)}


def experts_flops(model: dict[str, Any], spans: int) -> float:
    """Operations of the experts' grouped products for this many real
    spans: k experts a span and layer, three d x d_expert products each,
    whatever implements them."""
    d, _, _, _, n, _, k, f = _sizes(model)
    return spans * 2.0 * n * k * 3 * d * f


def experts_bytes(model: dict[str, Any], spans: int, calls: int) -> float:
    """The least bytes those products move: in each call and layer every
    expert's three kernels read once (bfloat16, as held), and for each
    assignment its input row read and its output row written (d wide,
    bfloat16); what lies between the products need not leave the chip."""
    d, _, _, _, n, E, k, f = _sizes(model)
    return calls * n * 3.0 * E * d * f * 2 + spans * k * n * 2.0 * d * 2


# ---------------------------------------------------------------- weights


def outer_weights(seed: int, d_model: int, max_len: int) -> dict[str, Any]:
    """``reference.outer_weights`` with the embedder drawn as this
    architecture's program draws it: the four tables at unit variance an
    element, the continuous projection at variance 1 / d_model (unit norm
    a column), from the keys flax hands those parameters. The span head
    is the shared one."""
    import jax
    import jax.numpy as jnp

    init = jax.nn.initializers
    table = init.normal(1.0)
    column = init.variance_scaling(1.0, "fan_out", "normal")
    root = jax.random.PRNGKey(seed)
    outer = reference.outer_weights(seed, d_model, max_len)
    for ours, theirs in (("service", "service_embed"),
                         ("name", "name_embed"), ("kind", "kind_embed"),
                         ("status", "status_embed")):
        outer[ours] = table(
            reference._param_key(root, ("encoder", "embed", theirs), 1),
            outer[ours].shape, jnp.float32)
    outer["cont_w"] = column(
        reference._param_key(root, ("encoder", "embed", "cont_proj"), 1),
        outer["cont_w"].shape, jnp.float32)
    return outer


def layer_keys(seed: int, n_layers: int):
    """(n_layers, 8, 2) uint32: the keys of each block's eight kernels, in
    ``KERNELS``' order."""
    import jax
    import jax.numpy as jnp

    root = jax.random.PRNGKey(seed)
    return jnp.stack([jnp.stack(
        [reference._param_key(root, ("encoder", f"block_{i}", nm), 1)
         for nm in KERNELS]) for i in range(n_layers)])


def block_weights(keys, model: dict[str, Any]) -> dict[str, Any]:
    """One block's kernels from its eight keys, float32 values that
    bfloat16 holds exactly. The norms' scales are one and there is no
    bias."""
    import jax
    import jax.numpy as jnp

    d, H, K, hd, _, E, _, f = _sizes(model)
    lecun, _ = reference._inits()
    each = jax.nn.initializers.variance_scaling(
        1.0, "fan_in", "truncated_normal", batch_axis=(0,))
    shapes = ((d, E), (d, H * hd), (d, K * hd), (d, K * hd), (H * hd, d),
              (E, d, f), (E, d, f), (E, f, d))
    return {nm: (each if len(shape) == 3 else lecun)(
        keys[i], shape, jnp.float32).astype(jnp.bfloat16).astype(jnp.float32)
        for i, (nm, shape) in enumerate(zip(KERNELS, shapes))}


# ---------------------------------------------------------------- forward


def _rms(x, eps: float):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)


def _rope(u, cos, sin):
    """Rotate-half rotary embedding of (rows, L, heads, head_dim)."""
    import jax.numpy as jnp

    half = u.shape[-1] // 2
    a, b = u[..., :half], u[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def block_step(x, allowed, cos, sin, keys, *, model: dict[str, Any],
               rope: bool, precision: str):
    """One routed block over (rows, L, d) with its weights made here from
    ``keys``; ``allowed`` is (rows, L, L) bool (the layer's own mask),
    ``cos`` and ``sin`` (rows, L, 1, head_dim / 2)."""
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST
    mm = reference._matmul(precision)
    d, H, K, hd, _, E, k, _ = _sizes(model)
    eps = float(model["norm_eps"])
    rows, L, _ = x.shape
    w = block_weights(keys, model)
    logits = jnp.matmul(x, w["router"], precision=hi)
    h = _rms(x, eps)
    q = mm(h, w["q_proj"]).reshape(rows, L, H, hd)
    kk = mm(h, w["k_proj"]).reshape(rows, L, K, hd)
    v = mm(h, w["v_proj"]).reshape(rows, L, K, hd)
    if rope:
        q, kk = _rope(q, cos, sin), _rope(kk, cos, sin)
    q = q.reshape(rows, L, K, H // K, hd)     # head g = (g // (H/K), g % (H/K))
    s = jnp.einsum("rqgnd,rkgd->rgnqk", q, kk, precision=hi) / np.sqrt(hd)
    s = jnp.where(allowed[:, None, None], s, jnp.finfo(jnp.float32).min)
    a = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("rgnqk,rkgd->rqgnd", a, v, precision=hi)
    x = x + mm(o.reshape(rows, L, H * hd), w["o_proj"])
    h = _rms(x, eps).reshape(rows * L, d)
    top, chosen = jax.lax.top_k(logits.reshape(rows * L, E), k)
    weight = jax.nn.softmax(top, axis=-1)
    weight = weight / jnp.sum(weight, axis=-1, keepdims=True)

    def one_expert(acc, e):
        mine = jnp.sum(jnp.where(chosen == e, weight, 0.0), axis=-1)
        y = mm(jax.nn.relu(mm(h, w["experts_gate"][e]))
               * mm(h, w["experts_up"][e]), w["experts_down"][e])
        return acc + mine[:, None] * y, None

    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(h), jnp.arange(E))
    return x + y.reshape(rows, L, d)


def decoder(seed: int, model: dict[str, Any], precision: str = "float32"):
    """The three steps of the forward pass, each jitted over one block of
    rows: ``embed(cat, cont, seg) -> x``, ``stack(x, seg, pos) -> x``
    (every layer in turn, then the final norm) and ``head(x) -> scores``."""
    import jax
    import jax.numpy as jnp

    d, n_layers = int(model["d_model"]), int(model["n_layers"])
    eps, theta = float(model["norm_eps"]), float(model["rope_theta"])
    half, window = int(model["head_dim"]) // 2, int(model["window"])
    outer = outer_weights(seed, d, int(model["max_len"]))

    # ``outer`` goes in as an argument: closed over, its tables would be
    # constants of the executable
    @jax.jit
    def embed_step(outer, cat, cont, seg):
        return reference.span_embedding(outer, cat, cont) \
            * (seg > 0)[..., None]

    @jax.jit
    def tables(seg, pos):
        real = seg > 0
        apart = pos[:, :, None] - pos[:, None, :]
        whole = (seg[:, :, None] == seg[:, None, :]) \
            & real[:, :, None] & real[:, None, :] & (apart >= 0)
        angle = pos[..., None].astype(jnp.float32) * theta ** (
            -jnp.arange(half, dtype=jnp.float32) / half)
        return (whole, whole & (apart < window),
                jnp.cos(angle)[:, :, None], jnp.sin(angle)[:, :, None])

    steps = {bool(rope): jax.jit(partial(block_step, model=model,
                                         rope=bool(rope),
                                         precision=precision))
             for rope in model["rope_layout"]}
    final_norm = jax.jit(partial(_rms, eps=eps))
    keys = layer_keys(seed, n_layers)

    def stack(x, seg, pos):
        whole, near, cos, sin = tables(seg, pos)
        for i in range(n_layers):
            x = steps[bool(model["rope_layout"][i])](
                x, near if model["window_layout"][i] else whole,
                cos, sin, keys[i])
        return final_norm(x)

    head_step = jax.jit(reference.span_head)
    return (partial(embed_step, outer), stack, partial(head_step, outer))


def scores(frames, seed: int, model: dict[str, Any],
           precision: str = "float32", block_rows: int = 256,
           ) -> list[np.ndarray]:
    """The reference's score of every span of every frame, as one float32
    array per frame in the frame's own span order. 256 rows a block: a
    layer's weights are made once for 16 k spans, and a block's widest
    value, an expert's (spans, d_expert) products, stays tens of MB."""
    embed, stack, head = decoder(seed, model, precision)
    return reference.score_rows(
        frames, int(model["max_len"]), block_rows,
        lambda cat, cont, seg, pos: head(stack(embed(cat, cont, seg),
                                               seg, pos)))
