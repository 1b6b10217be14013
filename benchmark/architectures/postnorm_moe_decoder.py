"""``postnorm_moe_decoder``: a stack of post-norm routed decoder layers
behind leading dense ones, of which this device holds a share of each
routed layer's experts, as the program's ``models/transformer.py`` builds
it under ``block: "postnorm_moe"`` from ``d_model``, ``n_heads``,
``n_kv_heads``, ``head_dim``, ``n_layers``, ``dense_layers``, ``d_ff``,
``n_experts``, ``experts_held``, ``first_expert_held``,
``experts_per_span``, ``d_expert``, ``shared_experts``, ``route_scale``,
``rope_layout``, ``window_layout``, ``window``, ``rope_theta``,
``norm_eps`` and ``weight_seed``.

The equations, after K-EXAONE-236B-A23B (LGAI-EXAONE, ``model_type``
``exaone_moe``: the EXAONE 4 layer, whose attention and norms the
installed ``transformers`` has as ``Exaone4Attention`` and
``Exaone4DecoderLayer``, with the feed-forward of DeepSeek-V3's router
keys), with this system's span embedder and span head
(``benchmark/reference.py``) in place of the vocabulary. ``x`` is a packed
row of spans, ``pos`` a span's place within its own trace, ``seg`` the
trace's number within the row, ``l`` the layer, ``H`` = ``n_heads``,
``G`` = ``n_kv_heads``, ``d_h`` = ``head_dim``, ``E`` = ``n_experts``,
``k`` = ``experts_per_span``, ``s`` = ``route_scale``, ``W`` =
``window``, ``slide(l)`` = ``window_layout[l]`` (which is also where
``rope_layout`` rotates), ``held`` = experts ``[first_expert_held,
first_expert_held + experts_held)``::

    x = span_embedding(span)                                   (no position table)
    for l in 0..n_layers-1:
      q = x Wq_l -> (H, d_h);  k = x Wk_l -> (G, d_h);  v = x Wv_l -> (G, d_h)     (no norm ahead)
      q = RMSq_l(q);  k = RMSk_l(k)                            (one d_h-wide scale each, over each head's columns)
      if rope_layout[l]: q, k = rope(q, pos), rope(k, pos)     (rotate-half; a layer without it has no positions)
      allowed(i, j) = seg_j = seg_i and pos_j <= pos_i and (not slide(l) or pos_i - pos_j < W)
      a = softmax(q_h k_{h // (H/G)}^T / sqrt(d_h) over allowed) v_{h // (H/G)}  -> concat (H d_h) Wo_l
      x = x + RMSattn_l(a)
      if l < dense_layers:  f = (silu(x Wg_l) * (x Wu_l)) Wd_l                  (d_ff wide)
      else:
        p = sigmoid(x Wr_l)                                    (E scores, float32; the router reads the raw stream)
        C = the k largest of p + b_l                           (b_l: the selection bias; it chooses, it does not weigh)
        w_e = s * p_e / sum_{c in C} p_c                       (normalised over all k chosen, held or not)
        f = (silu(x Wsg_l) * (x Wsu_l)) Wsd_l                  (the shared expert, shared_experts x d_expert wide)
            + sum_{e in C and e in held} w_e (silu(x Wg_{l,e}) * (x Wu_{l,e})) Wd_{l,e}   (d_expert wide)
      x = x + RMSffn_l(f)
    x = RMSf(x);  score = sigmoid(span_head(x))
    RMS(x) = x / sqrt(mean(x^2) + norm_eps) * g
    rope(u, p)_i = u_i cos(p w_i) - u_{i+d_h/2} sin(p w_i)                (i < d_h/2)
    rope(u, p)_{i+d_h/2} = u_{i+d_h/2} cos(p w_i) + u_i sin(p w_i),  w_i = theta^(-2i/d_h)

The held share is expert parallelism's: the layer's experts are divided
over ``n_experts / experts_held`` devices, each with the whole attention,
router, selection bias, shared expert and dense layer, and this device
computes what its own experts give. The post-feed-forward norm takes that
partial sum, here and in the program alike, and the partial result is
what goes on to the next layer: the shares of a layer add up before that
norm, not after it (what a deployment's exchange would sum), so a layer's
whole feed-forward is the shared expert once plus the held parts of all
the shares. Each held expert is computed here for every span and weighted
by ``w_e``, zero for a span that did not choose it: the plain way, with no
sort, where the program sorts the held assignments by expert and runs
grouped products over them alone.

Departures and assumptions (the configuration's ``assumed`` says the
same). ``config.json`` states no norm placement: the norms after each
sublayer and none ahead of it, the per-head q and k norms before the
rotary and the global layers without positions follow ``Exaone4Attention``
and ``Exaone4DecoderLayer`` of the installed ``transformers`` (the dense
family). It names no ``topk_method``: the selection bias, which a
checkpoint's balancing left and which starts training at zero, is drawn
normal at 0.02 from the seed, as ``latent_moe_decoder``'s. ``n_group`` 1
and ``topk_group`` 1 make the router's group step a no-op, not built.
Rotary columns pair rotate-half. No multi-token-prediction module
(``num_nextn_predict_layers`` 1): it predicts a token of a vocabulary this
system does not have. Rows hold ``max_len`` positions where the model
allows 262,144, so a window of 128 never cuts a pair of a row of 64; a
trace longer than a row is cut into pieces that attend within themselves,
each piece's positions starting at 0. The final norm closes the stack as
run. Weights are random from the seed, or from ``weight_seed`` where the
configuration fixes one (the deployment's one checkpoint: the router's
choices, and so the share of them this device's experts take, follow the
weights and not the traffic's values): each kernel truncated lecun-normal
over its own fan-in (an expert's over ``d_model`` or ``d_expert``, the
expert axis counting no fan), drawn in float32 with the key flax hands the
program's parameter and rounded once to bfloat16, which is what the
program holds; unit norm scales; no bias in any projection; a layer's
made inside the jitted layer step, so that one layer's float32 kernels and
never the stack sit on the device. The embedder is drawn as
``moe_decoder``'s is (tables at unit variance, the continuous projection
at 1 / d_model).

``precision="fp8"`` is the control: every matrix product a span passes
through in a layer but the router's (q, k, v, out, an expert's three, the
shared expert's three, a dense layer's three) computed from inputs cast to
float8 (``reference._matmul``), the precision next below the
configuration's bfloat16. The router's product stays float32 in the
control, as the configuration states it.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Iterable

import numpy as np

from benchmark import reference
from benchmark.architectures.moe_decoder import _rms, _rope, outer_weights

# the scopes models/layers.py BLOCK_PARTS["postnorm_moe"] writes, and the
# part each folds into
PARTS = {"embed": "rest", "attn_mask": "rest", "qk": "qk", "attn": "attn",
         "route": "route", "mlp": "mlp", "dense": "dense", "norm": "norm",
         "head": "rest"}
CONTROL = "fp8"
# the draw of the selection bias (models/layers.py SELECTION_BIAS_SCALE)
BIAS_SCALE = 0.02
ATTENTION = ("q_proj", "k_proj", "v_proj", "o_proj")
# a layer's parameters the reference draws, by the layer's kind, in the
# order of its keys
KERNELS = {
    False: ATTENTION + ("gate_proj", "up_proj", "down_proj"),
    True: ATTENTION + ("router", "router_bias", "experts_gate",
                       "experts_up", "experts_down", "shared_gate",
                       "shared_up", "shared_down")}


# ------------------------------------------------------------- operations


def _sizes(model: dict[str, Any]) -> tuple[int, ...]:
    return tuple(int(model[k]) for k in (
        "d_model", "n_heads", "n_kv_heads", "head_dim", "n_experts",
        "experts_per_span", "d_expert"))


def held(model: dict[str, Any]) -> tuple[int, int]:
    """(the first expert held, how many): all of them unless
    ``experts_held`` says a share."""
    return (int(model.get("first_expert_held", 0)),
            int(model.get("experts_held", 0) or model["n_experts"]))


def _layers(model: dict[str, Any]) -> tuple[int, int]:
    """(dense layers, routed layers)."""
    dense = int(model["dense_layers"])
    return dense, int(model["n_layers"]) - dense


def flops_by_part(model: dict[str, Any], piece_lengths: Iterable[int],
                  ) -> dict[str, float]:
    """Operations for traces cut into pieces of these lengths (a trace of
    up to ``max_len`` spans is one piece), 2 a multiply-add, real spans
    only: ``attn`` is the four projections a span and layer (d x H d_h
    twice, d x G d_h twice) and the core over the pairs the layer's mask
    allows (q k^T and a v, each H d_h multiply-adds a pair; span i of a
    piece sees min(i + 1, W) spans in a layer with a window, i + 1 in one
    without); ``qk`` the per-head RMS norms of q and k, 4 operations an
    element, and in a rotary layer their rotation, 3; ``mlp`` the held
    experts' three d x d_expert products for **k x experts_held /
    n_experts** choices a span and routed layer: the even share, an
    estimate of work that depends on the weights (at random routing the
    held experts take from a third to three times it in a call; at the
    configuration's ``weight_seed`` 0 over the backlog traffic they take
    4.39% of the choices, ``moe.assignments`` over ``moe.choices`` on a
    v5e, so the even share counts their work 1.42 times), since the count
    of what they took (``moe.assignments`` on the program's ``tpu/score``
    span) is not among what the harness reads; ``dense`` a
    dense layer's three d x d_ff products and a routed layer's shared
    expert, three d x shared_experts d_expert; ``route`` the router's d x
    n_experts product; ``norm`` the 2 a layer and 1 final RMS norms, 4 d
    operations a span each; ``rest`` the embedder's continuous projection
    and the span head."""
    d, H, K, hd, E, k, f = _sizes(model)
    dense, routed = _layers(model)
    n, window = dense + routed, int(model["window"])
    pieces = list(piece_lengths)
    spans = sum(pieces)
    windowed = sum(bool(w) for w in model["window_layout"])
    rotary = sum(bool(r) for r in model["rope_layout"])

    def pairs(p: int, cut: bool) -> int:
        if not cut or p <= window:
            return p * (p + 1) // 2
        return window * (window + 1) // 2 + (p - window) * window

    core = sum(windowed * pairs(p, True) + (n - windowed) * pairs(p, False)
               for p in pieces)
    return {
        "attn": spans * 2.0 * n * (2 * d * H * hd + 2 * d * K * hd)
        + 2.0 * 2 * core * H * hd,
        "qk": spans * (4.0 * n + 3.0 * rotary) * (H + K) * hd,
        "mlp": experts_flops(model, spans),
        "dense": spans * 2.0 * 3 * d * (
            dense * int(model["d_ff"])
            + routed * int(model["shared_experts"]) * f),
        "route": spans * 2.0 * routed * d * E,
        "norm": spans * (2 * n + 1) * 4.0 * d,
        "rest": spans * 2.0 * (reference.CONT_WIDTH * d + d)}


def experts_flops(model: dict[str, Any], spans: int) -> float:
    """Operations of the held experts' grouped products for this many real
    spans at the even share: k x experts_held / n_experts choices a span
    and routed layer, three d x d_expert products each (an estimate:
    ``flops_by_part`` says why). The shared expert is not among them
    (part ``dense``)."""
    d, _, _, _, E, k, f = _sizes(model)
    _, routed = _layers(model)
    return spans * 2.0 * routed * k * held(model)[1] / E * 3 * d * f


def experts_bytes(model: dict[str, Any], spans: int, calls: int) -> float:
    """The least bytes those products move: in each call and routed layer
    every held expert's three kernels read once (bfloat16, as held), and
    for each assignment at the even share its input row read and its
    output row written (d wide, bfloat16)."""
    d, _, _, _, E, k, f = _sizes(model)
    _, routed = _layers(model)
    n_held = held(model)[1]
    return calls * routed * 3.0 * n_held * d * f * 2 \
        + spans * k * n_held / E * routed * 2.0 * d * 2


# ---------------------------------------------------------------- weights


def layer_keys(seed: int, layer: int, routed: bool):
    """(parameters, 2) uint32: the keys of block ``layer``'s parameters,
    in ``KERNELS[routed]``'s order. The selection bias is the block's own
    first parameter; every other is the first of a module of its name."""
    import jax
    import jax.numpy as jnp

    root, block = jax.random.PRNGKey(seed), ("encoder", f"block_{layer}")
    return jnp.stack([reference._param_key(
        root, block if nm == "router_bias" else block + (nm,), 1)
        for nm in KERNELS[routed]])


def block_weights(keys, model: dict[str, Any], routed: bool,
                  ) -> dict[str, Any]:
    """One block's parameters from its keys, float32 values that bfloat16
    holds exactly: the held experts' kernels alone, (experts_held, ...).
    The norms' scales are one and nothing else has a bias."""
    import jax
    import jax.numpy as jnp

    d, H, K, hd, E, _, f = _sizes(model)
    n_held = held(model)[1]
    wide, shared = int(model["d_ff"]), int(model["shared_experts"]) * f
    shapes = {"q_proj": (d, H * hd), "k_proj": (d, K * hd),
              "v_proj": (d, K * hd), "o_proj": (H * hd, d),
              "gate_proj": (d, wide), "up_proj": (d, wide),
              "down_proj": (wide, d), "router": (d, E),
              "router_bias": (E,), "experts_gate": (n_held, d, f),
              "experts_up": (n_held, d, f), "experts_down": (n_held, f, d),
              "shared_gate": (d, shared), "shared_up": (d, shared),
              "shared_down": (shared, d)}
    lecun, _ = reference._inits()
    inits = {1: jax.nn.initializers.normal(BIAS_SCALE), 2: lecun,
             3: jax.nn.initializers.variance_scaling(
                 1.0, "fan_in", "truncated_normal", batch_axis=(0,))}
    return {nm: inits[len(shapes[nm])](keys[i], shapes[nm], jnp.float32)
            .astype(jnp.bfloat16).astype(jnp.float32)
            for i, nm in enumerate(KERNELS[routed])
            if 0 not in shapes[nm]}


# ---------------------------------------------------------------- forward


def feed_forward(h, w: dict[str, Any], model: dict[str, Any], *,
                 routed: bool, precision: str = "float32",
                 shared: bool = True):
    """A layer's feed-forward over (spans, d), before its norm: a dense
    layer's SwiGLU, or the held experts' part (the router over all
    ``n_experts``, the weights over all k choices) plus, with ``shared``,
    the shared expert."""
    import jax
    import jax.numpy as jnp

    mm = reference._matmul(precision)

    def swiglu(gate, up, down):
        return mm(jax.nn.silu(mm(h, gate)) * mm(h, up), down)

    if not routed:
        return swiglu(w["gate_proj"], w["up_proj"], w["down_proj"])
    score = jax.nn.sigmoid(jnp.matmul(h, w["router"],
                                      precision=jax.lax.Precision.HIGHEST))
    _, chosen = jax.lax.top_k(score + w["router_bias"],
                              int(model["experts_per_span"]))
    weight = jnp.take_along_axis(score, chosen, axis=-1)
    weight = float(model["route_scale"]) * weight \
        / jnp.sum(weight, axis=-1, keepdims=True)
    first, n_held = held(model)

    def one_expert(acc, e):
        mine = jnp.sum(jnp.where(chosen == first + e, weight, 0.0), axis=-1)
        y = swiglu(w["experts_gate"][e], w["experts_up"][e],
                   w["experts_down"][e])
        return acc + mine[:, None] * y, None

    f, _ = jax.lax.scan(one_expert, jnp.zeros_like(h), jnp.arange(n_held))
    if shared and "shared_gate" in w:
        f = f + swiglu(w["shared_gate"], w["shared_up"], w["shared_down"])
    return f


def block_step(x, allowed, cos, sin, keys, *, model: dict[str, Any],
               routed: bool, rope: bool, precision: str):
    """One block over (rows, L, d) with its weights made here from
    ``keys``; ``allowed`` is (rows, L, L) bool (the layer's own mask),
    ``cos`` and ``sin`` (rows, L, 1, d_h / 2)."""
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST
    mm = reference._matmul(precision)
    d, H, K, hd, _, _, _ = _sizes(model)
    eps = float(model["norm_eps"])
    rows, L, _ = x.shape
    w = block_weights(keys, model, routed)
    q = _rms(mm(x, w["q_proj"]).reshape(rows, L, H, hd), eps)
    k = _rms(mm(x, w["k_proj"]).reshape(rows, L, K, hd), eps)
    v = mm(x, w["v_proj"]).reshape(rows, L, K, hd)
    if rope:
        q, k = _rope(q, cos, sin), _rope(k, cos, sin)
    q = q.reshape(rows, L, K, H // K, hd)     # head g = (g // (H/K), g % (H/K))
    s = jnp.einsum("rqgnd,rkgd->rgnqk", q, k, precision=hi) / np.sqrt(hd)
    s = jnp.where(allowed[:, None, None], s, jnp.finfo(jnp.float32).min)
    o = jnp.einsum("rgnqk,rkgd->rqgnd", jax.nn.softmax(s, axis=-1), v,
                   precision=hi)
    x = x + _rms(mm(o.reshape(rows, L, H * hd), w["o_proj"]), eps)
    f = feed_forward(x.reshape(rows * L, d), w, model, routed=routed,
                     precision=precision)
    return x + _rms(f, eps).reshape(rows, L, d)


def decoder(seed: int, model: dict[str, Any], precision: str = "float32"):
    """The three steps of the forward pass, each jitted over one block of
    rows: ``embed(cat, cont, seg) -> x``, ``stack(x, seg, pos) -> x``
    (every layer in turn, then the final norm) and ``head(x) -> scores``,
    with weights drawn from ``seed``, or from the configuration's
    ``weight_seed`` where it fixes one."""
    import jax
    import jax.numpy as jnp

    if model.get("weight_seed") is not None:
        seed = int(model["weight_seed"])

    d, n_layers = int(model["d_model"]), int(model["n_layers"])
    eps, theta = float(model["norm_eps"]), float(model["rope_theta"])
    half, window = int(model["head_dim"]) // 2, int(model["window"])
    dense = int(model["dense_layers"])
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

    kinds = [(i >= dense, bool(model["rope_layout"][i]))
             for i in range(n_layers)]
    steps = {kind: jax.jit(partial(block_step, model=model, routed=kind[0],
                                   rope=kind[1], precision=precision))
             for kind in set(kinds)}
    final_norm = jax.jit(partial(_rms, eps=eps))
    keys = [layer_keys(seed, i, i >= dense) for i in range(n_layers)]

    def stack(x, seg, pos):
        whole, near, cos, sin = tables(seg, pos)
        for i in range(n_layers):
            x = steps[kinds[i]](
                x, near if model["window_layout"][i] else whole,
                cos, sin, keys[i])
        return final_norm(x)

    head_step = jax.jit(reference.span_head)
    return (partial(embed_step, outer), stack, partial(head_step, outer))


def scores(frames, seed: int, model: dict[str, Any],
           precision: str = "float32", block_rows: int = 128,
           ) -> list[np.ndarray]:
    """The reference's score of every span of every frame, as one float32
    array per frame in the frame's own span order. 128 rows a block: a
    layer's weights are made once for 8 k spans, and a block's widest
    value, the dense layer's (spans, d_ff) products, stays under 1 GB."""
    embed, stack, head = decoder(seed, model, precision)
    return reference.score_rows(
        frames, int(model["max_len"]), block_rows,
        lambda cat, cont, seg, pos: head(stack(embed(cat, cont, seg),
                                               seg, pos)))
