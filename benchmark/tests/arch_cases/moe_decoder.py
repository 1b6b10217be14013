"""What the benchmark's tests need to know of ``moe_decoder`` that the
architecture's own file has no reason to export: the program's model at a
tiny size, which of the program's parameters each of the reference's
weights is, and hand counts of its operations."""

import numpy as np

# two periods of the published layout (a layer without rotary positions
# and without window, then three with both), fewer key/value heads than
# heads, a head width that is not d_model / n_heads, a window shorter than
# the row
SMALL = {"d_model": 64, "n_heads": 4, "n_kv_heads": 2, "head_dim": 32,
         "n_layers": 8, "max_len": 16, "block": "moe", "n_experts": 8,
         "experts_per_span": 2, "d_expert": 32,
         "rope_layout": [0, 1, 1, 1, 0, 1, 1, 1],
         "window_layout": [0, 1, 1, 1, 0, 1, 1, 1], "window": 5,
         "rope_theta": 1.5e6, "norm_eps": 1e-6, "param_dtype": "bfloat16"}


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
    """(ours, theirs) for every kernel and table: the reference's weight
    and the program's parameter it has to equal (a block's are bfloat16
    in the program and the same values in float32 here). There is no
    position table: the positions are rotary, where a layer has any."""
    outer = arch.outer_weights(seed, SMALL["d_model"], SMALL["max_len"])
    enc = params["encoder"]
    assert "pos_embed" not in enc
    yield outer["service"], enc["embed"]["service_embed"]["embedding"]
    yield outer["name"], enc["embed"]["name_embed"]["embedding"]
    yield outer["kind"], enc["embed"]["kind_embed"]["embedding"]
    yield outer["status"], enc["embed"]["status_embed"]["embedding"]
    yield outer["cont_w"], enc["embed"]["cont_proj"]["kernel"]
    yield outer["head_w"], params["span_head"]["kernel"]
    keys = arch.layer_keys(seed, SMALL["n_layers"])
    for i in range(SMALL["n_layers"]):
        w = arch.block_weights(keys[i], SMALL)
        blk = enc[f"block_{i}"]
        assert set(arch.KERNELS) == {k for k in blk if "kernel" in blk[k]}
        for name in arch.KERNELS:
            ours = blk[name]["kernel"]
            assert str(ours.dtype) == "bfloat16", name
            yield w[name], np.asarray(ours, np.float32)


# ---- hand counts, 2 operations a multiply-add
TINY = {"d_model": 8, "n_heads": 4, "n_kv_heads": 2, "head_dim": 4,
        "n_layers": 3, "max_len": 4, "block": "moe", "n_experts": 4,
        "experts_per_span": 2, "d_expert": 6, "rope_layout": [0, 1, 1],
        "window_layout": [0, 1, 1], "window": 2, "rope_theta": 1.5e6,
        "norm_eps": 1e-6}
# pieces of 3 spans and of 1, 3 layers. A span and layer: q and out 2 *
# 8*16 = 256 MACs, k and v 2 * 8*8 = 128, 384 in all; the router 8*4 = 32;
# 2 experts of three 8*6 products, 288. The attention core of a piece of p
# spans, H head_dim = 16 MACs a pair in q k^T and 16 in a v: the layer
# without window sees p (p + 1) / 2 pairs (6 and 1), each of the two with
# a window of 2 sees min(i + 1, 2) of span i (1 + 2 + 2 = 5, and 1): 6 + 2
# * 5 = 16 pairs for the piece of 3, 3 for the piece of 1. RMS norms: 2 a
# layer and the final one, 7, each 4 * 8 = 32 operations a span. Outside
# the stack: the continuous projection 3*8 and the span head 8 MACs a span.
HAND = {
    "pieces": [3, 1],
    "by_part": {"attn": 2 * 4 * 3 * 384 + 2 * 2 * (16 + 3) * 16,
                "mlp": 2 * 4 * 3 * 288,
                "route": 2 * 4 * 3 * 32,
                "norm": 4 * 7 * 32,
                "rest": 2 * 4 * (24 + 8)},
}
# matrix products one span passes through at the cut the configuration
# runs: 12 layers of 20,971,520 attention weights, 163,840 of the router
# and 6 experts of 3 * 2560 * 768 = 5,898,240 (the attention core of one
# span and the norms add 0.02%)
PUBLISHED = [
    ({"d_model": 2560, "n_heads": 28, "n_kv_heads": 4, "head_dim": 128,
      "n_layers": 12, "n_experts": 64, "experts_per_span": 6,
      "d_expert": 768, "window": 4096,
      "window_layout": [0, 1, 1, 1] * 3}, 1.356e9),
]
