"""What the benchmark's tests need to know of ``postnorm_moe_decoder``
that the architecture's own file has no reason to export: the program's
model at a tiny size, which of the program's parameters each of the
reference's weights is, and hand counts of its operations."""

import numpy as np

# one dense layer ahead of three routed ones, two window layers with
# rotary and a global one without, 4 query heads over 2 key/value heads;
# this device holds 4 of the router's 16 experts, not the first 4, a
# shared expert beside them, a scaling that is not 1
SMALL = {"d_model": 64, "n_heads": 4, "n_kv_heads": 2, "head_dim": 16,
         "n_layers": 4, "max_len": 16, "block": "postnorm_moe",
         "dense_layers": 1, "d_ff": 96, "n_experts": 16, "experts_held": 4,
         "first_expert_held": 4, "experts_per_span": 4, "d_expert": 32,
         "shared_experts": 1, "route_scale": 2.5,
         "rope_layout": [1, 1, 1, 0], "window_layout": [1, 1, 1, 0],
         "window": 5, "rope_theta": 1e6, "norm_eps": 1e-5,
         "param_dtype": "bfloat16"}


def program(seed):
    """(model, variables): the program's own model of this architecture at
    ``SMALL``, float32 activations over the bfloat16 parameters;
    ``model.score_packed(variables, categorical, continuous, segments,
    positions)`` scores packed rows."""
    import jax

    from odigos_tpu.models.transformer import TraceTransformer
    from odigos_tpu.training import make_model_config

    model = TraceTransformer(make_model_config(
        "transformer", {**SMALL, "dtype": "float32"}))
    return model, model.init(jax.random.PRNGKey(seed))


def weight_pairs(arch, reference, params, seed):
    """(ours, theirs) for every kernel, table and selection bias: the
    reference's weight and the program's parameter it has to equal (a
    block's are bfloat16 in the program and the same values in float32
    here). There is no position table: the positions are rotary."""
    outer = arch.outer_weights(seed, SMALL["d_model"], SMALL["max_len"])
    enc = params["encoder"]
    assert "pos_embed" not in enc
    yield outer["service"], enc["embed"]["service_embed"]["embedding"]
    yield outer["name"], enc["embed"]["name_embed"]["embedding"]
    yield outer["kind"], enc["embed"]["kind_embed"]["embedding"]
    yield outer["status"], enc["embed"]["status_embed"]["embedding"]
    yield outer["cont_w"], enc["embed"]["cont_proj"]["kernel"]
    yield outer["head_w"], params["span_head"]["kernel"]
    for i in range(SMALL["n_layers"]):
        routed = i >= SMALL["dense_layers"]
        w = arch.block_weights(arch.layer_keys(seed, i, routed), SMALL,
                               routed)
        blk = enc[f"block_{i}"]
        assert set(arch.KERNELS[routed]) == {
            k for k in blk if k == "router_bias" or "kernel" in blk[k]}
        for name in arch.KERNELS[routed]:
            ours = blk[name] if name == "router_bias" \
                else blk[name]["kernel"]
            assert str(ours.dtype) == "bfloat16", name
            yield w[name], np.asarray(ours, np.float32)


# ---- hand counts, 2 operations a multiply-add
TINY = {"d_model": 8, "n_heads": 4, "n_kv_heads": 2, "head_dim": 2,
        "n_layers": 3, "max_len": 4, "block": "postnorm_moe",
        "dense_layers": 1, "d_ff": 10, "n_experts": 8, "experts_held": 4,
        "experts_per_span": 2, "d_expert": 6, "shared_experts": 1,
        "route_scale": 2.5, "rope_layout": [1, 1, 0],
        "window_layout": [1, 1, 0], "window": 2, "rope_theta": 1e6,
        "norm_eps": 1e-5}
# pieces of 3 spans and of 1, one dense and two routed layers, two window
# layers (window 2) and a global one. A span and layer, attention: q and
# out 8 x 4*2 = 64 MACs each, k and v 8 x 2*2 = 32 each, 192 MACs. The
# core: a piece of 3 sees 6 pairs in the global layer and 2 + 1 + 2 = 5
# under a window of 2, the piece of 1 one pair in each; a pair is 4*2 = 8
# MACs in q k^T and 8 in a v, 16. qk: (4 + 2) heads x 2 columns = 12
# elements, each 4 operations normed in every layer and 3 rotated in the
# two rotary layers. A routed layer: the router 8*8 = 64; the held share
# 2 x 4 / 8 = 1 choice a span of three 8*6 products, 144; the shared
# expert the same 144. The dense layer: three 8*10 products, 240. RMS
# norms: 2 a layer and the final one, 7, each 4 * 8 = 32 operations a
# span. Outside the stack: the continuous projection 3*8 and the span
# head 8 MACs a span.
HAND = {
    "pieces": [3, 1],
    "by_part": {"attn": 2 * 4 * 3 * 192 + 2 * 16 * (2 * (5 + 1) + (6 + 1)),
                "qk": 4 * (4 * 3 + 3 * 2) * 12,
                "mlp": 2 * 4 * 2 * 144,
                "dense": 2 * 4 * (240 + 2 * 144),
                "route": 2 * 4 * 2 * 64,
                "norm": 4 * 7 * 32,
                "rest": 2 * 4 * (24 + 8)},
}
# matrix products one span passes through at the cut the configuration
# runs: eight layers of attention (113,246,208 weights), a dense layer's
# 339,738,624, and in each of seven routed layers the router's 786,432,
# the shared expert's 37,748,736 and the held experts' at the even share,
# 8 x 8 / 128 of 37,748,736 (the core of one span and the norms add
# 0.03%)
PUBLISHED = [
    ({"d_model": 6144, "n_heads": 64, "n_kv_heads": 8, "head_dim": 128,
      "n_layers": 8, "dense_layers": 1, "d_ff": 18432, "n_experts": 128,
      "experts_held": 8, "first_expert_held": 0, "experts_per_span": 8,
      "d_expert": 2048, "shared_experts": 1, "route_scale": 2.5,
      "rope_layout": [1, 1, 1, 0] * 2, "window_layout": [1, 1, 1, 0] * 2,
      "window": 128}, 3.296e9),
]
