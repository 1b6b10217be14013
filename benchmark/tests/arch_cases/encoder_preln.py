"""What the benchmark's tests need to know of ``encoder_preln`` that the
architecture's own file has no reason to export: the program's model at a
tiny size, which of the program's parameters each of the reference's
weights is, and hand counts of its operations. A PR that adds an
architecture adds such a file beside it; ``test_reference.py`` and
``test_opcount.py`` run over every architecture a configuration names and
fail on one without."""

import numpy as np

SMALL = {"d_model": 64, "n_heads": 4, "n_layers": 2, "d_ff": 128,
         "max_len": 16}


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
    and the program's parameter it has to equal."""
    outer = reference.outer_weights(seed, SMALL["d_model"], SMALL["max_len"])
    enc = params["encoder"]
    yield outer["service"], enc["embed"]["service_embed"]["embedding"]
    yield outer["name"], enc["embed"]["name_embed"]["embedding"]
    yield outer["kind"], enc["embed"]["kind_embed"]["embedding"]
    yield outer["status"], enc["embed"]["status_embed"]["embedding"]
    yield outer["cont_w"], enc["embed"]["cont_proj"]["kernel"]
    yield outer["pos"], enc["pos_embed"]["embedding"]
    yield outer["head_w"], params["span_head"]["kernel"]
    keys = arch.layer_keys(seed, SMALL["n_layers"])
    for i in range(SMALL["n_layers"]):
        w = arch.block_weights(keys[i], SMALL["d_model"], SMALL["d_ff"])
        blk = enc[f"block_{i}"]
        mha = blk["MultiHeadDotProductAttention_0"]
        for ours, theirs in ((w["wq"], mha["query"]["kernel"]),
                             (w["wk"], mha["key"]["kernel"]),
                             (w["wv"], mha["value"]["kernel"]),
                             (w["wo"], mha["out"]["kernel"]),
                             (w["w1"], blk["Dense_0"]["kernel"]),
                             (w["w2"], blk["Dense_1"]["kernel"])):
            yield ours, np.asarray(theirs).reshape(ours.shape)


# ---- hand counts, 2 operations a multiply-add
TINY = {"d_model": 8, "n_heads": 2, "n_layers": 3, "d_ff": 16, "max_len": 4}
# pieces of 3 spans and of 1. A span and layer: q, k, v, out 4 * 8*8 = 256
# MACs, feed-forward 2 * 8*16 = 256 MACs. The attention core of a piece of
# p spans: q k^T p*p*8 MACs and a v p*p*8 MACs a layer. Outside the
# layers: the continuous projection 3*8 and the span head 8 MACs a span.
HAND = {
    "pieces": [3, 1],
    "by_part": {"attn": 2 * 3 * (4 * 256 + 2 * 9 * 8 + 2 * 1 * 8),
                "mlp": 2 * 3 * 4 * 256,
                "rest": 2 * 4 * (24 + 8)},
}
# matrix products one span passes through at published sizes (the
# attention core left out: one piece of one span adds 2 * 2 * n * d)
PUBLISHED = [
    ({"d_model": 1024, "n_heads": 16, "n_layers": 24, "d_ff": 4096}, 0.604e9),
    ({"d_model": 1280, "n_heads": 16, "n_layers": 32, "d_ff": 5120}, 1.258e9),
]
