"""What the benchmark's tests need to know of ``looped_decoder`` that the
architecture's own file has no reason to export: the program's model at a
tiny size, which of the program's parameters each of the reference's
weights is, and hand counts of its operations."""

import numpy as np

SMALL = {"d_model": 64, "n_heads": 4, "n_layers": 2, "d_ff": 128,
         "max_len": 16, "block": "decoder", "passes": 4,
         "rope_theta": 1e6, "norm_eps": 1e-6}


def program(seed):
    """(model, variables): the program's own model of this architecture at
    ``SMALL`` in float32; ``model.score_packed(variables, categorical,
    continuous, segments, positions)`` scores packed rows."""
    import jax
    import jax.numpy as jnp

    from odigos_tpu.models.transformer import (TraceTransformer,
                                               TransformerConfig)

    model = TraceTransformer(TransformerConfig(dtype=jnp.float32, **SMALL))
    return model, model.init(jax.random.PRNGKey(seed))


def weight_pairs(arch, reference, params, seed):
    """(ours, theirs) for every kernel and table: the reference's weight
    and the program's parameter it has to equal. There is no position
    table: the positions are rotary."""
    outer = reference.outer_weights(seed, SMALL["d_model"], SMALL["max_len"])
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
        w = arch.block_weights(keys[i], SMALL["d_model"], SMALL["d_ff"])
        blk = enc["stack"][f"block_{i}"]
        assert set(arch.KERNELS) == {k for k in blk if "kernel" in blk[k]}
        for name in arch.KERNELS:
            yield w[name], np.asarray(blk[name]["kernel"])


# ---- hand counts, 2 operations a multiply-add
TINY = {"d_model": 8, "n_heads": 2, "n_layers": 3, "d_ff": 16, "max_len": 4,
        "block": "decoder", "passes": 2, "rope_theta": 1e6, "norm_eps": 1e-6}
# pieces of 3 spans and of 1, 3 layers, 2 passes: 6 layer applications. A
# span and layer application: q, k, v, out 4 * 8*8 = 256 MACs; gate, up,
# down 3 * 8*16 = 384 MACs. The attention core of a piece of p spans is
# causal, p (p + 1) / 2 pairs: 6 for the piece of 3 and 1 for the piece of
# 1, each 8 MACs in q k^T and 8 in a v. RMS norms: 4 a layer application
# and 1 a pass, 13 a pass, each 4 * 8 = 32 operations a span. Outside the
# loop: the continuous projection 3*8 and the span head 8 MACs a span.
HAND = {
    "pieces": [3, 1],
    "by_part": {"attn": 2 * 6 * (4 * 256 + 2 * 6 * 8 + 2 * 1 * 8),
                "mlp": 2 * 6 * 4 * 384,
                "norm": 2 * 4 * 13 * 32,
                "rest": 2 * 4 * (24 + 8)},
}
# matrix products one span passes through at published sizes: 48 layers of
# 4 * 2048^2 + 3 * 2048 * 5632 = 51.38 M weights, four times over (the
# attention core of one span and the norms add 0.03%)
PUBLISHED = [
    ({"d_model": 2048, "n_heads": 16, "n_layers": 48, "d_ff": 5632,
      "passes": 4}, 19.73e9),
]
