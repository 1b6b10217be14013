"""``latent_moe_decoder``: a stack of latent-attention routed decoder
layers behind leading dense ones, as the program's ``models/transformer.py``
builds it under ``block: "latent_moe"`` from ``d_model``, ``n_heads``,
``n_layers``, ``dense_layers``, ``q_rank``, ``kv_rank``, ``qk_nope_dim``,
``qk_rope_dim``, ``v_dim``, ``d_ff``, ``n_experts``, ``experts_per_span``,
``d_expert``, ``shared_experts``, ``route_scale``, ``rope_theta`` and
``norm_eps``.

The equations, after GLM-4.7-Flash (zai-org, ``model_type``
``glm4_moe_lite``: the DeepSeek-V3 layer at other numbers), with this
system's span embedder and span head (``benchmark/reference.py``) in place
of the vocabulary. ``x`` is a packed row of spans, ``pos`` a span's place
within its own trace, ``seg`` the trace's number within the row, ``l`` the
layer, ``H`` = ``n_heads``, ``r_q`` = ``q_rank``, ``r_kv`` = ``kv_rank``,
``d_n`` = ``qk_nope_dim``, ``d_r`` = ``qk_rope_dim``, ``d_v`` = ``v_dim``,
``k`` = ``experts_per_span``, ``s`` = ``route_scale``::

    x = span_embedding(span)                               (no position table)
    for l in 0..n_layers-1:
      h  = RMS1_l(x)
      cq = RMSq_l(h Wqa_l)                                 (r_q)
      q  = cq Wqb_l  -> (H, d_n + d_r);  q = [q_n | rope(q_r, pos)]
      ckv, kr = split(h Wkva_l, [r_kv, d_r]);  kr = rope(kr, pos)          (one rotary key a span)
      kv = RMSkv_l(ckv) Wkvb_l -> (H, d_n + d_v);  k = [k_n | kr for every head];  v = kv[..., d_n:]
      allowed(i, j) = seg_j = seg_i and pos_j <= pos_i
      a  = softmax(q k^T / sqrt(d_n + d_r) over allowed)
      x  = x + (a v -> H d_v) Wo_l
      h  = RMS2_l(x)
      if l < dense_layers:   x = x + (silu(h Wg_l) * (h Wu_l)) Wd_l       (d_ff wide)
      else:
        p  = sigmoid(h Wr_l)                               (n_experts scores, from the normed input)
        E  = the k largest of p + b_l                      (b_l: the selection bias; it chooses, it does not weigh)
        w_e = s * p_e / sum_{e' in E} p_e'
        x  = x + sum_{e in E} w_e (silu(h Wg_{l,e}) * (h Wu_{l,e})) Wd_{l,e}   (d_expert wide)
               + (silu(h Wsg_l) * (h Wsu_l)) Wsd_l         (the shared expert, shared_experts x d_expert wide)
    x = RMSf(x);  score = sigmoid(span_head(x))
    RMS(x) = x / sqrt(mean(x^2) + norm_eps) * g
    rope(u, p)_i = u_i cos(p w_i) - u_{i+d_r/2} sin(p w_i)                (i < d_r/2)
    rope(u, p)_{i+d_r/2} = u_{i+d_r/2} cos(p w_i) + u_i sin(p w_i),  w_i = theta^(-2i/d_r)

Every expert is computed for every span here (a dense pass, n_experts /
k times the work) and weighted by ``w_e``, zero for an expert the span
did not choose: the plain way, where the program sorts the assignments by
expert and runs grouped products. Nothing is cached and nothing absorbed
into ``Wo``: a row's keys and values live for one call.

Departures and assumptions (the configuration's ``assumed`` says the
same). ``n_group`` 1 and ``topk_group`` 1 make the published router's
group step (the best ``topk_group`` of ``n_group`` groups of experts
first) a choice of the one group there is: a no-op, not built. The rotary
columns are laid out rotate-half (column i pairs with i + d_r / 2) where
the checkpoint interleaves them (2i with 2i + 1): a fixed permutation of
the rotary columns of ``Wqb`` and ``Wkva``, the same distribution under
random weights. No ``mscale`` on the softmax scale (``rope_scaling`` is
null). The selection bias, which a checkpoint's balancing left and which
starts training at zero, is drawn normal at 0.02 from the seed: at zero
the mechanism would not run. The multi-token-prediction module
(``num_nextn_predict_layers`` 1) predicts a token of a vocabulary this
system does not have and is not held. Rows hold ``max_len`` positions
where the model allows 202,752; a trace longer than a row is cut into
pieces that attend within themselves, each piece's positions starting at
0. Attention is within a trace's own spans (a row holds several traces).
The final norm closes the stack as run, after ``n_layers`` layers,
wherever the configuration cut it. Weights are random from the seed: each
kernel truncated lecun-normal over its own fan-in (an expert's over
``d_model`` or ``d_expert``, the expert axis counting no fan), drawn in
float32 with the key flax hands the program's parameter and rounded once
to bfloat16, which is what the program holds; unit norm scales; a layer's
made inside the jitted layer step, so that one layer's 2.5 GB of float32
kernels and never the stack sit on the device. The embedder is drawn as
``moe_decoder``'s is (tables at unit variance, the continuous projection
at 1 / d_model).

``precision="fp8"`` is the control: every matrix product a span passes
through in a layer but the router's (the four of the latent chains, out,
an expert's three, the shared expert's three, a dense layer's three)
computed from inputs cast to float8 (``reference._matmul``), the precision
next below the configuration's bfloat16. The router's product stays
float32 in the control, as the configuration states it: the control then
parts from the sound run by its arithmetic and by the choices that
arithmetic moves downstream, not by a router nobody would build.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Iterable

import numpy as np

from benchmark import reference
from benchmark.architectures.moe_decoder import _rms, _rope, outer_weights

# the scopes models/layers.py BLOCK_PARTS["latent_moe"] writes, and the
# part each folds into
PARTS = {"embed": "rest", "attn_mask": "rest", "latent": "latent",
         "attn": "attn", "route": "route", "mlp": "mlp", "dense": "dense",
         "norm": "norm", "head": "rest"}
CONTROL = "fp8"
# the draw of the selection bias (models/layers.py SELECTION_BIAS_SCALE)
BIAS_SCALE = 0.02
ATTENTION = ("q_a_proj", "q_b_proj", "kv_a_proj", "kv_b_proj", "o_proj")
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
        "d_model", "n_heads", "q_rank", "kv_rank", "qk_nope_dim",
        "qk_rope_dim", "v_dim"))


def _layers(model: dict[str, Any]) -> tuple[int, int]:
    """(dense layers, routed layers)."""
    dense = int(model["dense_layers"])
    return dense, int(model["n_layers"]) - dense


def flops_by_part(model: dict[str, Any], piece_lengths: Iterable[int],
                  ) -> dict[str, float]:
    """Operations for traces cut into pieces of these lengths (a trace of
    up to ``max_len`` spans is one piece), 2 a multiply-add, real spans
    only: ``latent`` is the four low-rank products a span and layer (d x
    r_q, r_q x H (d_n + d_r), d x (r_kv + d_r), r_kv x H (d_n + d_v)) and
    the two latent norms; ``attn`` the core over the pairs the causal mask
    allows (q k^T H (d_n + d_r) multiply-adds a pair, a v H d_v; span i of
    a piece sees i + 1 spans) and the output product H d_v x d; ``mlp``
    the k routed experts a span takes in a routed layer, three d x
    d_expert products each; ``dense`` a dense layer's three d x d_ff
    products and a routed layer's shared expert, three d x shared_experts
    d_expert; ``route`` the router's d x n_experts product; ``norm`` the 2
    a layer and 1 final RMS norms, 4 d operations a span each; ``rest``
    the embedder's continuous projection and the span head."""
    d, H, r_q, r_kv, d_n, d_r, d_v = _sizes(model)
    dense, routed = _layers(model)
    n = dense + routed
    f, E = int(model["d_expert"]), int(model["n_experts"])
    pieces = list(piece_lengths)
    spans = sum(pieces)
    pairs = sum(p * (p + 1) // 2 for p in pieces)
    return {
        "latent": spans * n * (2.0 * (d * r_q + r_q * H * (d_n + d_r)
                                      + d * (r_kv + d_r)
                                      + r_kv * H * (d_n + d_v))
                               + 4.0 * (r_q + r_kv)),
        "attn": 2.0 * n * pairs * H * (d_n + d_r + d_v)
        + spans * 2.0 * n * H * d_v * d,
        "mlp": experts_flops(model, spans),
        "dense": spans * 2.0 * 3 * d * (
            dense * int(model["d_ff"])
            + routed * int(model["shared_experts"]) * f),
        "route": spans * 2.0 * routed * d * E,
        "norm": spans * (2 * n + 1) * 4.0 * d,
        "rest": spans * 2.0 * (reference.CONT_WIDTH * d + d)}


def experts_flops(model: dict[str, Any], spans: int) -> float:
    """Operations of the routed experts' grouped products for this many
    real spans: k experts a span and routed layer, three d x d_expert
    products each, whatever implements them. The shared expert is not
    among them (part ``dense``)."""
    _, routed = _layers(model)
    return spans * 2.0 * routed * int(model["experts_per_span"]) * 3 \
        * int(model["d_model"]) * int(model["d_expert"])


def experts_bytes(model: dict[str, Any], spans: int, calls: int) -> float:
    """The least bytes those products move: in each call and routed layer
    every expert's three kernels read once (bfloat16, as held), and for
    each assignment its input row read and its output row written (d
    wide, bfloat16); what lies between the products need not leave the
    chip."""
    _, routed = _layers(model)
    d, f = int(model["d_model"]), int(model["d_expert"])
    return calls * routed * 3.0 * int(model["n_experts"]) * d * f * 2 \
        + spans * int(model["experts_per_span"]) * routed * 2.0 * d * 2


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
    holds exactly. The norms' scales are one and nothing else has a
    bias."""
    import jax
    import jax.numpy as jnp

    d, H, r_q, r_kv, d_n, d_r, d_v = _sizes(model)
    f, E = int(model["d_expert"]), int(model["n_experts"])
    wide, shared = int(model["d_ff"]), int(model["shared_experts"]) * f
    shapes = {"q_a_proj": (d, r_q), "q_b_proj": (r_q, H * (d_n + d_r)),
              "kv_a_proj": (d, r_kv + d_r),
              "kv_b_proj": (r_kv, H * (d_n + d_v)), "o_proj": (H * d_v, d),
              "gate_proj": (d, wide), "up_proj": (d, wide),
              "down_proj": (wide, d), "router": (d, E),
              "router_bias": (E,), "experts_gate": (E, d, f),
              "experts_up": (E, d, f), "experts_down": (E, f, d),
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


def block_step(x, allowed, cos, sin, keys, *, model: dict[str, Any],
               routed: bool, precision: str):
    """One block over (rows, L, d) with its weights made here from
    ``keys``; ``allowed`` is (rows, L, L) bool, ``cos`` and ``sin``
    (rows, L, 1, d_r / 2)."""
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST
    mm = reference._matmul(precision)
    d, H, r_q, r_kv, d_n, d_r, d_v = _sizes(model)
    eps = float(model["norm_eps"])
    rows, L, _ = x.shape
    w = block_weights(keys, model, routed)

    def swiglu(h, gate, up, down):
        return mm(jax.nn.silu(mm(h, gate)) * mm(h, up), down)

    h = _rms(x, eps)
    q = mm(_rms(mm(h, w["q_a_proj"]), eps), w["q_b_proj"])
    q = q.reshape(rows, L, H, d_n + d_r)
    q = jnp.concatenate([q[..., :d_n], _rope(q[..., d_n:], cos, sin)], -1)
    kv = mm(h, w["kv_a_proj"])
    kr = _rope(kv[:, :, None, r_kv:], cos, sin)          # (rows, L, 1, d_r)
    kv = mm(_rms(kv[..., :r_kv], eps), w["kv_b_proj"])
    kv = kv.reshape(rows, L, H, d_n + d_v)
    k = jnp.concatenate(
        [kv[..., :d_n], jnp.broadcast_to(kr, (rows, L, H, d_r))], -1)
    s = jnp.einsum("rqhd,rkhd->rhqk", q, k, precision=hi) \
        / np.sqrt(d_n + d_r)
    s = jnp.where(allowed[:, None], s, jnp.finfo(jnp.float32).min)
    o = jnp.einsum("rhqk,rkhd->rqhd", jax.nn.softmax(s, axis=-1),
                   kv[..., d_n:], precision=hi)
    x = x + mm(o.reshape(rows, L, H * d_v), w["o_proj"])
    h = _rms(x, eps).reshape(rows * L, d)
    if not routed:
        return x + swiglu(h, w["gate_proj"], w["up_proj"],
                          w["down_proj"]).reshape(rows, L, d)
    score = jax.nn.sigmoid(jnp.matmul(h, w["router"], precision=hi))
    _, chosen = jax.lax.top_k(score + w["router_bias"],
                              int(model["experts_per_span"]))
    weight = jnp.take_along_axis(score, chosen, axis=-1)
    weight = float(model["route_scale"]) * weight \
        / jnp.sum(weight, axis=-1, keepdims=True)

    def one_expert(acc, e):
        mine = jnp.sum(jnp.where(chosen == e, weight, 0.0), axis=-1)
        y = swiglu(h, w["experts_gate"][e], w["experts_up"][e],
                   w["experts_down"][e])
        return acc + mine[:, None] * y, None

    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(h),
                        jnp.arange(int(model["n_experts"])))
    if "shared_gate" in w:
        y = y + swiglu(h, w["shared_gate"], w["shared_up"],
                       w["shared_down"])
    return x + y.reshape(rows, L, d)


def decoder(seed: int, model: dict[str, Any], precision: str = "float32"):
    """The three steps of the forward pass, each jitted over one block of
    rows: ``embed(cat, cont, seg) -> x``, ``stack(x, seg, pos) -> x``
    (every layer in turn, then the final norm) and ``head(x) -> scores``."""
    import jax
    import jax.numpy as jnp

    d, n_layers = int(model["d_model"]), int(model["n_layers"])
    eps, theta = float(model["norm_eps"]), float(model["rope_theta"])
    half, dense = int(model["qk_rope_dim"]) // 2, int(model["dense_layers"])
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
        allowed = (seg[:, :, None] == seg[:, None, :]) \
            & real[:, :, None] & real[:, None, :] \
            & (pos[:, :, None] >= pos[:, None, :])
        angle = pos[..., None].astype(jnp.float32) * theta ** (
            -jnp.arange(half, dtype=jnp.float32) / half)
        return (allowed, jnp.cos(angle)[:, :, None],
                jnp.sin(angle)[:, :, None])

    steps = {routed: jax.jit(partial(block_step, model=model, routed=routed,
                                     precision=precision))
             for routed in (False, True)}
    final_norm = jax.jit(partial(_rms, eps=eps))
    keys = [layer_keys(seed, i, i >= dense) for i in range(n_layers)]

    def stack(x, seg, pos):
        allowed, cos, sin = tables(seg, pos)
        for i in range(n_layers):
            x = steps[i >= dense](x, allowed, cos, sin, keys[i])
        return final_norm(x)

    head_step = jax.jit(reference.span_head)
    return (partial(embed_step, outer), stack, partial(head_step, outer))


def scores(frames, seed: int, model: dict[str, Any],
           precision: str = "float32", block_rows: int = 256,
           ) -> list[np.ndarray]:
    """The reference's score of every span of every frame, as one float32
    array per frame in the frame's own span order. 256 rows a block: a
    layer's weights are made once for 16 k spans, and a block's widest
    value, the dense layer's (spans, d_ff) products, stays under 1 GB."""
    embed, stack, head = decoder(seed, model, precision)
    return reference.score_rows(
        frames, int(model["max_len"]), block_rows,
        lambda cat, cont, seg, pos: head(stack(embed(cat, cont, seg),
                                               seg, pos)))
