"""What the benchmark's tests need to know of ``latent_moe_decoder`` that
the architecture's own file has no reason to export: the program's model
at a tiny size, which of the program's parameters each of the reference's
weights is, and hand counts of its operations."""

import numpy as np

# one dense layer ahead of three routed ones; ranks and head widths that
# are not each other's multiples (a head's query 12 + 8, its value 10, 3
# heads), a shared expert, a scaling that is not 1
SMALL = {"d_model": 64, "n_heads": 3, "n_layers": 4, "max_len": 16,
         "block": "latent_moe", "dense_layers": 1, "q_rank": 24,
         "kv_rank": 20, "qk_nope_dim": 12, "qk_rope_dim": 8, "v_dim": 10,
         "d_ff": 96, "n_experts": 8, "experts_per_span": 2, "d_expert": 32,
         "shared_experts": 1, "route_scale": 1.8, "rope_theta": 1e6,
         "norm_eps": 1e-5, "param_dtype": "bfloat16"}


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
TINY = {"d_model": 8, "n_heads": 2, "n_layers": 3, "max_len": 4,
        "block": "latent_moe", "dense_layers": 1, "q_rank": 5, "kv_rank": 3,
        "qk_nope_dim": 4, "qk_rope_dim": 2, "v_dim": 3, "d_ff": 10,
        "n_experts": 4, "experts_per_span": 2, "d_expert": 6,
        "shared_experts": 1, "route_scale": 1.8, "rope_theta": 1e6,
        "norm_eps": 1e-5}
# pieces of 3 spans and of 1, one dense and two routed layers. A span and
# layer, latent: 8*5 into the query latent, 5 * 2*(4+2) = 60 out of it,
# 8 * (3+2) = 40 into the key/value latent and the rotary key, 3 * 2*(4+3)
# = 42 out of it, 182 MACs; the two latent norms 4 * (5 + 3) = 32
# operations. Attention: out 2*3 x 8 = 48 MACs a span and layer; the core
# of a piece of p spans sees p (p + 1) / 2 pairs (6 and 1), each 2 * (4+2)
# = 12 MACs in q k^T and 2 * 3 = 6 in a v. A routed layer: the router 8*4
# = 32; 2 experts of three 8*6 products, 288; the shared expert three 8*6
# products, 144. The dense layer: three 8*10 products, 240. RMS norms: 2 a
# layer and the final one, 7, each 4 * 8 = 32 operations a span. Outside
# the stack: the continuous projection 3*8 and the span head 8 MACs a span.
HAND = {
    "pieces": [3, 1],
    "by_part": {"latent": 4 * 3 * (2 * 182 + 32),
                "attn": 2 * 3 * (6 + 1) * 18 + 2 * 4 * 3 * 48,
                "mlp": 2 * 4 * 2 * 288,
                "dense": 2 * 4 * (240 + 2 * 144),
                "route": 2 * 4 * 2 * 32,
                "norm": 4 * 7 * 32,
                "rest": 2 * 4 * (24 + 8)},
}
# matrix products one span passes through at the cut the configuration
# runs: nine layers of 21,757,952 latent-attention weights, a dense layer's
# 62,914,560, and in each of eight routed layers the router's 131,072, the
# shared expert's 9,437,184 and 4 experts of 9,437,184 (the attention core
# of one span and the norms add 0.03%)
PUBLISHED = [
    ({"d_model": 2048, "n_heads": 20, "n_layers": 9, "dense_layers": 1,
      "q_rank": 768, "kv_rank": 512, "qk_nope_dim": 192, "qk_rope_dim": 64,
      "v_dim": 256, "d_ff": 10240, "n_experts": 64, "experts_per_span": 4,
      "d_expert": 1536, "shared_experts": 1}, 1.275e9),
]
