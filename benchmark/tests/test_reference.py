"""The plain reference against the program's TraceTransformer at a tiny
size in float32: the same weights from the same seed, the same scores
for the same spans."""

import json
import os

import numpy as np
import pytest

from benchmark import gen, reference

HERE = os.path.dirname(os.path.abspath(__file__))
MODEL = {"d_model": 64, "n_heads": 4, "n_layers": 2, "d_ff": 128,
         "max_len": 16}
SEED = 2**31 + 11


@pytest.fixture(scope="module")
def pool():
    with open(os.path.join(HERE, "..", "traffic", "backlog.json")) as f:
        traffic = json.load(f)
    traffic.update(pool_frames=3, traces_per_frame=24)
    return gen.make_pool(traffic, 12345)


@pytest.fixture(scope="module")
def program():
    import jax
    import jax.numpy as jnp

    from odigos_tpu.models.transformer import (TraceTransformer,
                                               TransformerConfig)

    model = TraceTransformer(TransformerConfig(dtype=jnp.float32, **MODEL))
    return model, model.init(jax.random.PRNGKey(SEED))


def test_weights_are_what_flax_makes(program):
    _, variables = program
    p = variables["params"]
    outer = reference.outer_weights(SEED, MODEL["d_model"], MODEL["max_len"])
    enc = p["encoder"]
    for ours, theirs in (
            (outer["service"], enc["embed"]["service_embed"]["embedding"]),
            (outer["name"], enc["embed"]["name_embed"]["embedding"]),
            (outer["kind"], enc["embed"]["kind_embed"]["embedding"]),
            (outer["status"], enc["embed"]["status_embed"]["embedding"]),
            (outer["cont_w"], enc["embed"]["cont_proj"]["kernel"]),
            (outer["pos"], enc["pos_embed"]["embedding"]),
            (outer["head_w"], p["span_head"]["kernel"])):
        np.testing.assert_array_equal(np.asarray(ours), np.asarray(theirs))
    keys = reference.layer_keys(SEED, MODEL["n_layers"])
    d = MODEL["d_model"]
    for i in range(MODEL["n_layers"]):
        w = reference.block_weights(keys[i], d, MODEL["d_ff"])
        blk = enc[f"block_{i}"]
        mha = blk["MultiHeadDotProductAttention_0"]
        for ours, theirs in (
                (w["wq"], mha["query"]["kernel"]),
                (w["wk"], mha["key"]["kernel"]),
                (w["wv"], mha["value"]["kernel"]),
                (w["wo"], mha["out"]["kernel"]),
                (w["w1"], blk["Dense_0"]["kernel"]),
                (w["w2"], blk["Dense_1"]["kernel"])):
            np.testing.assert_array_equal(
                np.asarray(ours), np.asarray(theirs).reshape(ours.shape))
    # every bias the program makes is zero and every LayerNorm scale one,
    # which is what block_weights hands the reference
    import jax

    for path, leaf in jax.tree_util.tree_leaves_with_path(p):
        name = jax.tree_util.keystr(path)
        if name.endswith("['bias']"):
            assert not np.asarray(leaf).any(), name
        if name.endswith("['scale']"):
            assert (np.asarray(leaf) == 1).all(), name


def test_scores_match_the_program_in_float32(pool, program):
    from odigos_tpu.features import featurize
    from odigos_tpu.features.featurizer import pack_sequences

    model, variables = program
    ref = reference.scores(pool, SEED, MODEL, block_rows=32)
    for frame, want in zip(pool, ref):
        batch = gen.rekey(gen.to_request(frame), 7)
        packed = pack_sequences(batch, featurize(batch),
                                max_len=MODEL["max_len"])
        dev = np.asarray(model.score_packed(
            variables, packed.categorical, packed.continuous,
            packed.segments, packed.positions))
        got = np.zeros(len(batch), np.float32)
        got[packed.span_index[packed.mask]] = dev[packed.mask]
        assert np.abs(got - want).max() < 2e-6


def test_a_trace_longer_than_a_row_is_cut_like_the_program_cuts_it(program):
    """max_len 16 and a pool of frontend traces (17 spans each at depth
    6): both sides cut the trace into pieces that attend within
    themselves."""
    from odigos_tpu.features import featurize
    from odigos_tpu.features.featurizer import pack_sequences

    with open(os.path.join(HERE, "..", "traffic", "backlog.json")) as f:
        traffic = json.load(f)
    traffic.update(pool_frames=1, traces_per_frame=12)
    pool = gen.make_pool(traffic, 3)
    assert max(np.bincount(pool[0].trace)) > MODEL["max_len"]
    model, variables = program
    want = reference.scores(pool, SEED, MODEL, block_rows=32)[0]
    batch = gen.rekey(gen.to_request(pool[0]), 1)
    packed = pack_sequences(batch, featurize(batch), max_len=MODEL["max_len"])
    dev = np.asarray(model.score_packed(
        variables, packed.categorical, packed.continuous, packed.segments,
        packed.positions))
    got = np.zeros(len(batch), np.float32)
    got[packed.span_index[packed.mask]] = dev[packed.mask]
    assert np.abs(got - want).max() < 2e-6


def test_the_seed_changes_values_not_sizes():
    with open(os.path.join(HERE, "..", "traffic", "backlog.json")) as f:
        traffic = json.load(f)
    traffic.update(pool_frames=4, traces_per_frame=32)
    a, b = gen.make_pool(traffic, 1), gen.make_pool(traffic, 2**31 + 5)

    def sizes(pool):
        return sorted(int(c) for f in pool for c in np.bincount(f.trace))

    assert sizes(a) == sizes(b)
    assert not np.array_equal(a[0].start, b[0].start)
    c = gen.make_pool(traffic, 1)
    assert all(np.array_equal(x.end, y.end) and x.strings == y.strings
               for x, y in zip(a, c))
