"""The transformer scorer's fourth block kind (ISSUE 37): a latent routed
decoder block. Attention through latents (low-rank queries, one compressed
key/value a span, one rotary key a span shared by every head), a sigmoid
router whose selection bias chooses and never weighs, SiLU-gated experts
beside a shared expert, and a stack whose leading layer is dense. Held
here: the program against the benchmark's plain reference
(``benchmark/architectures/latent_moe_decoder.py``), one layer by hand,
what the bias, the scaling, the shared expert, the shared rotary key and
the padding promise, the size of the published cut, the configuration
against the catalog, the routes that refuse the block and those that serve
it, what the engine says of the model and of each call on its
``tpu/score`` spans, and the cell's own rehearsal. The compiles for a
described chip are in ``tests/test_moe_decoder.py``, the one file that
describes one."""

from __future__ import annotations

import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import gen, judge, reference, run
from benchmark.tests.conftest import arch_case, stood_in_trace  # noqa: F401
from odigos_tpu.features import featurize
from odigos_tpu.features.featurizer import pack_sequences
from odigos_tpu.models import layers
from odigos_tpu.models.layers import BLOCK_PARTS
from odigos_tpu.models.transformer import TraceTransformer
from odigos_tpu.pdata import synthesize_traces
from odigos_tpu.serving import EngineConfig, ScoringEngine
from odigos_tpu.training import make_model_config
from tests.test_moe_decoder import load, logit, packed_row

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 2**31 + 37
ARCH, CASE = arch_case("latent_moe_decoder")
SMALL = CASE.SMALL
CELL = "glm-4.7-flash.backlog"
ROUTED = SMALL["n_layers"] - SMALL["dense_layers"]


def latent(**over):
    model = TraceTransformer(make_model_config(
        "transformer", {**SMALL, "dtype": "float32", **over}))
    return model, model.init(jax.random.PRNGKey(SEED))


@pytest.fixture(scope="module")
def built():
    return latent()


# ------------------------------------------------- against the reference


def program_scores(model, variables, frame, serial):
    batch = gen.rekey(gen.to_request(frame), serial)
    packed = pack_sequences(batch, featurize(batch),
                            max_len=SMALL["max_len"])
    dev = np.asarray(model.score_packed(
        variables, packed.categorical, packed.continuous, packed.segments,
        packed.positions))
    got = np.zeros(len(batch), np.float32)
    got[packed.span_index[packed.mask]] = dev[packed.mask]
    return got, packed


def test_the_latent_block_matches_the_plain_reference(built):
    """Seeded weights, float32 over the bfloat16 parameters, to 1e-4 in
    the logit (float32 products in another order: sorted and grouped
    against every expert for every span), on frames whose traces of
    unequal length share rows and which cross ``block_rows``."""
    model, variables = built
    pool = gen.make_pool({**load("benchmark", "traffic", "backlog.json"),
                          "pool_frames": 2, "traces_per_frame": 24}, 77)
    want = ARCH.scores(pool, SEED, SMALL, block_rows=8)
    for serial, (frame, ref) in enumerate(zip(pool, want), start=1):
        got, packed = program_scores(model, variables, frame, serial)
        assert packed.n_rows > 8                    # crosses a block
        shared = [len(set(row[row > 0])) for row in packed.segments]
        assert max(shared) > 1                      # traces share a row
        lengths = np.bincount(frame.trace)
        assert len(set(lengths[lengths > 0])) > 1   # of unequal length
        assert np.abs(logit(got) - logit(ref)).max() < 1e-4


def test_the_weights_are_the_references_bit_for_bit(built):
    _, variables = built
    pairs = list(CASE.weight_pairs(ARCH, reference, variables["params"],
                                   SEED))
    assert len(pairs) == 6 + 8 * SMALL["dense_layers"] + 13 * ROUTED
    for ours, theirs in pairs:
        np.testing.assert_array_equal(np.asarray(ours), np.asarray(theirs))
    bias = variables["params"]["encoder"]["block_1"]["router_bias"]
    assert np.asarray(bias, np.float32).any()       # drawn, not zero
    assert np.asarray(bias, np.float32).std() == pytest.approx(
        layers.SELECTION_BIAS_SCALE, rel=0.6)
    assert ARCH.BIAS_SCALE == layers.SELECTION_BIAS_SCALE


def test_one_routed_layer_by_hand():
    """Float64 from the parameters: queries through their latent and its
    norm, one normed key/value latent expanded to every head, one rotary
    key a span for all heads, the rotary columns after the unrotated
    ones, the scale 1 / sqrt(d_n + d_r); the router reads the normed
    input, the bias chooses, the unbiased scores weigh, normalised and
    times 1.8; SiLU gates; the shared expert beside the chosen; the
    residuals are pre-norm and the final norm closes the stack."""
    model, variables = latent(n_layers=1, dense_layers=0)
    cat, cont, seg, pos = packed_row([9, 4])
    got = np.asarray(model.score_packed(variables, cat, cont, seg, pos))[0]
    p = jax.tree.map(lambda a: np.asarray(a, np.float64),
                     variables["params"])
    enc, blk = p["encoder"]["embed"], p["encoder"]["block_0"]
    c = cat[0]
    x = (enc["service_embed"]["embedding"][c[:, 0]]
         + enc["name_embed"]["embedding"][c[:, 1]]
         + enc["kind_embed"]["embedding"][c[:, 2]]
         + enc["status_embed"]["embedding"][c[:, 3]]
         + enc["service_embed"]["embedding"][c[:, 4]]
         + cont[0].astype(np.float64) @ enc["cont_proj"]["kernel"]
         + enc["cont_proj"]["bias"])
    real = seg[0] > 0
    x = x * real[:, None]
    eps = SMALL["norm_eps"]

    def rms(h):
        return h / np.sqrt((h * h).mean(-1, keepdims=True) + eps)

    def silu(u):
        return u / (1 + np.exp(-u))

    H, d_n, d_r, d_v, r_kv = (SMALL["n_heads"], SMALL["qk_nope_dim"],
                              SMALL["qk_rope_dim"], SMALL["v_dim"],
                              SMALL["kv_rank"])
    w = SMALL["rope_theta"] ** (-np.arange(d_r // 2) / (d_r // 2))
    ang = pos[0][:, None] * w

    def rope(u):                                   # (L, heads, d_r)
        a, b = u[..., :d_r // 2], u[..., d_r // 2:]
        cos, sin = np.cos(ang)[:, None], np.sin(ang)[:, None]
        return np.concatenate([a * cos - b * sin, b * cos + a * sin], -1)

    h = rms(x)
    q = (rms(h @ blk["q_a_proj"]["kernel"])
         @ blk["q_b_proj"]["kernel"]).reshape(-1, H, d_n + d_r)
    q = np.concatenate([q[..., :d_n], rope(q[..., d_n:])], -1)
    kva = h @ blk["kv_a_proj"]["kernel"]
    kr = rope(kva[:, None, r_kv:])[:, 0]           # one key a span
    kv = (rms(kva[:, :r_kv])
          @ blk["kv_b_proj"]["kernel"]).reshape(-1, H, d_n + d_v)
    allowed = (seg[0][:, None] == seg[0][None]) & real[:, None] \
        & real[None] & (pos[0][:, None] >= pos[0][None])
    o = np.zeros((len(x), H, d_v))
    for g in range(H):
        k_g = np.concatenate([kv[:, g, :d_n], kr], -1)
        s = q[:, g] @ k_g.T / np.sqrt(d_n + d_r)
        s = np.where(allowed, s, -1e30)
        a = np.exp(s - s.max(-1, keepdims=True))
        a /= a.sum(-1, keepdims=True)
        o[:, g] = a @ kv[:, g, d_n:]
    x = x + o.reshape(len(x), -1) @ blk["o_proj"]["kernel"]
    h = rms(x)
    score = 1 / (1 + np.exp(-(h @ blk["router"]["kernel"])))
    y = np.zeros_like(x)
    for t in np.flatnonzero(real):
        chosen = np.argsort(-(score[t] + blk["router_bias"]))[
            :SMALL["experts_per_span"]]
        weight = SMALL["route_scale"] * score[t, chosen] \
            / score[t, chosen].sum()
        for e, w_e in zip(chosen, weight):
            gate = silu(h[t] @ blk["experts_gate"]["kernel"][e])
            up = h[t] @ blk["experts_up"]["kernel"][e]
            y[t] += w_e * ((gate * up) @ blk["experts_down"]["kernel"][e])
    y += (silu(h @ blk["shared_gate"]["kernel"])
          * (h @ blk["shared_up"]["kernel"])) @ blk["shared_down"]["kernel"]
    x = rms(x + y)                                 # the final norm
    want = x @ p["span_head"]["kernel"][:, 0] + p["span_head"]["bias"][0]
    assert np.abs(logit(got[real]) - want[real]).max() < 1e-4


# --------------------------------- the bias, the scaling, the shared parts


def test_the_bias_moves_the_choice_and_never_the_weight():
    """``biased_sigmoid`` with and without a bias: the chosen experts
    differ for some spans; wherever two rules choose the same set the
    weights are bit for bit the same, and always they are the unbiased
    scores of the chosen, normalised; a bias the same for every expert
    changes nothing."""
    rng = np.random.default_rng(3)
    logits = jnp.asarray(rng.normal(size=(200, 8)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=8) * 0.1, jnp.float32)
    k, s = 2, 1.8
    which, weight = layers.biased_sigmoid(logits, bias, k, s)
    plain, plain_w = layers.biased_sigmoid(logits, jnp.zeros(8), k, s)
    lifted, lifted_w = layers.biased_sigmoid(logits, jnp.full(8, 0.7), k, s)
    moved = (np.sort(which, -1) != np.sort(plain, -1)).any(axis=-1)
    assert 0.1 < moved.mean() < 0.9                 # the bias chooses
    by_expert = lambda wh, wt: np.take_along_axis(  # noqa: E731
        np.asarray(wt), np.argsort(wh, -1), -1)
    assert np.array_equal(by_expert(which, weight)[~moved],
                          by_expert(plain, plain_w)[~moved])
    score = np.asarray(jax.nn.sigmoid(logits), np.float64)
    rows = np.arange(len(score))[:, None]
    chosen = score[rows, np.asarray(which)]
    np.testing.assert_allclose(
        weight, s * chosen / chosen.sum(-1, keepdims=True), rtol=1e-6)
    # by the biased score it chose: no expert left out beats one taken
    biased = score + np.asarray(bias, np.float64)
    taken = np.zeros_like(score, bool)
    taken[rows, np.asarray(which)] = True
    assert (np.where(taken, biased, np.inf).min(-1)
            >= np.where(taken, -np.inf, biased).max(-1) - 1e-6).all()
    assert np.array_equal(lifted, plain) and np.array_equal(lifted_w, plain_w)


@pytest.mark.parametrize("scale", [1.0, 1.8, 2.5])
def test_the_weights_of_the_chosen_sum_to_the_scaling(scale):
    rng = np.random.default_rng(4)
    logits = jnp.asarray(rng.normal(size=(64, 8)) * 3, jnp.float32)
    bias = jnp.asarray(rng.normal(size=8) * 0.05, jnp.float32)
    _, weight = layers.biased_sigmoid(logits, bias, 4, scale)
    assert weight.dtype == jnp.float32 and (np.asarray(weight) > 0).all()
    np.testing.assert_allclose(np.asarray(weight).sum(-1), scale, rtol=1e-6)


def unshared_rotary_key(monkeypatch):
    """The rotary key kept to the first head: every other head's rotary
    key columns zero."""
    attention, d_n = layers.attention, SMALL["qk_nope_dim"]
    monkeypatch.setattr(
        layers, "attention", lambda q, k, v, mask, dtype: attention(
            q, k.at[..., 1:, d_n:].set(0), v, mask, dtype))
    return {}


@pytest.mark.parametrize("what", [
    "no_shared_expert", "scaling_left_at_1", "rotary_key_not_shared",
    "an_expert_fewer", "bias_left_out"])
def test_a_part_left_out_is_another_model(built, what, monkeypatch):
    """Apart by more than the rehearsal's limits, so a build that leaves
    out the shared expert, the scaling, the rotary key of all heads but
    one, a routed expert a span or the selection bias cannot read
    ``correct``."""
    model, variables = built
    limits = load("benchmark", "tests", "rehearsal_latent.json")["correct"]
    args = packed_row([5, 7, 3], rows=4)
    real = args[2] > 0
    a = np.asarray(model.score_packed(variables, *args))
    over = {"no_shared_expert": lambda: {"shared_experts": 0},
            "scaling_left_at_1": lambda: {"route_scale": 1.0},
            "rotary_key_not_shared": lambda: unshared_rotary_key(monkeypatch),
            "an_expert_fewer": lambda: {
                "experts_per_span": SMALL["experts_per_span"] - 1},
            "bias_left_out": lambda: {}}[what]()
    other, _ = latent(**over)
    if what == "bias_left_out":
        variables = jax.tree_util.tree_map_with_path(
            lambda path, leaf: jnp.zeros_like(leaf)
            if "router_bias" in jax.tree_util.keystr(path) else leaf,
            variables)
    b = np.asarray(other.score_packed(variables, *args))
    gap = judge.logit_gap(b[real], a[real])
    assert np.sqrt(np.mean(gap * gap)) > limits["gap_rms"]


def test_every_head_reads_the_same_rotary_key(monkeypatch):
    """What reaches ``attention``: queries and keys d_n + d_r wide, values
    d_v wide, H heads of each (no repeat), and the rotary columns of the
    key equal across the heads while the unrotated ones differ."""
    seen = []
    attention = layers.attention

    def spy(q, k, v, mask, dtype):
        seen.append((q, k, v))
        return attention(q, k, v, mask, dtype)

    monkeypatch.setattr(layers, "attention", spy)
    model, variables = latent(n_layers=2)
    args = packed_row([7, 6])
    seen.clear()                        # ``init`` ran the forward once
    with jax.disable_jit():
        model._score_packed_impl(variables, *args)
    assert len(seen) == 2
    H, d_n, d_r, d_v = (SMALL["n_heads"], SMALL["qk_nope_dim"],
                        SMALL["qk_rope_dim"], SMALL["v_dim"])
    for q, k, v in seen:
        assert q.shape[-2:] == k.shape[-2:] == (H, d_n + d_r)
        assert v.shape[-2:] == (H, d_v)
        k = np.asarray(k)
        assert np.array_equal(k[..., :1, d_n:].repeat(H, -2), k[..., d_n:])
        assert not np.array_equal(k[..., 0, :d_n], k[..., 1, :d_n])
    # a trace's scores know its spans' positions: every layer is rotary
    base = np.asarray(model.score_packed(variables, *args))
    spread = (args[0], args[1], args[2], args[3] * 3)
    assert not np.array_equal(
        base, np.asarray(model.score_packed(variables, *spread)))


def test_the_dense_layer_has_no_router_and_the_load_one_row_a_routed_layer(
        built):
    model, variables = built
    enc = variables["params"]["encoder"]
    dense, routed = set(enc["block_0"]), set(enc["block_1"])
    attn = {"attn_norm", "q_a_proj", "q_a_norm", "q_b_proj", "kv_a_proj",
            "kv_a_norm", "kv_b_proj", "o_proj", "mlp_norm"}
    assert dense == attn | {"gate_proj", "up_proj", "down_proj"}
    assert routed == attn | {"router", "router_bias", "experts_gate",
                             "experts_up", "experts_down", "shared_gate",
                             "shared_up", "shared_down"}
    assert enc["block_0"]["gate_proj"]["kernel"].shape == (64, SMALL["d_ff"])
    cat, cont, seg, pos = packed_row([6, 5], rows=3)
    _, state = model.module.apply(
        variables, cat, cont, seg > 0, positions=pos, segments=seg,
        mutable=["moe"])
    (load_,) = state["moe"]["load"]
    assert load_.shape == (ROUTED, SMALL["n_experts"])
    assert (np.asarray(load_).sum(-1)
            == 33 * SMALL["experts_per_span"]).all()
    two, _ = latent(dense_layers=2)
    _, state = two.module.apply(
        two.init(jax.random.PRNGKey(1)), cat, cont, seg > 0, positions=pos,
        segments=seg, mutable=["moe"])
    assert state["moe"]["load"][0].shape == (ROUTED - 1, SMALL["n_experts"])


def test_the_grouped_products_equal_a_dense_pass_under_this_rule():
    """``routed_experts`` fed by ``biased_sigmoid`` with SiLU gates
    against every expert computed for every span and weighted (zero where
    not chosen); the slots that hold no span come back zero, take no
    assignment, and what they hold changes no real span's output."""
    rng = np.random.default_rng(6)
    T, d, E, f, k, s = 96, 32, 8, 16, 3, 1.8
    h = jnp.asarray(rng.normal(size=(T, d)), jnp.float32)
    r = jnp.asarray(rng.normal(size=(T, E)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=E) * 0.2, jnp.float32)
    real = jnp.asarray(rng.random(T) < 0.8)
    gate, up = (jnp.asarray(rng.normal(size=(E, d, f)) / 6, jnp.float32)
                for _ in range(2))
    down = jnp.asarray(rng.normal(size=(E, f, d)) / 4, jnp.float32)
    which, weight = layers.biased_sigmoid(r, bias, k, s)
    out, load_ = layers.routed_experts(h, which, weight, real, gate, up,
                                       down, jax.nn.silu)
    dense = jnp.einsum("tef,efd->ted", jax.nn.silu(
        jnp.einsum("td,edf->tef", h, gate))
        * jnp.einsum("td,edf->tef", h, up), down)
    mix = jnp.zeros((T, E)).at[jnp.arange(T)[:, None], which].set(weight)
    want = jnp.einsum("te,ted->td", mix, dense) * real[:, None]
    np.testing.assert_allclose(out, want, atol=4e-6)
    assert not np.asarray(out)[~np.asarray(real)].any()
    assert int(load_.sum()) == int(real.sum()) * k
    assert np.array_equal(load_, np.bincount(
        np.asarray(which)[np.asarray(real)].ravel(), minlength=E))
    noisy = jnp.where(real[:, None], h, jnp.nan)    # whatever padding holds
    again, _ = layers.routed_experts(noisy, which, weight, real, gate, up,
                                     down, jax.nn.silu)
    assert np.array_equal(out, again)
    relu, _ = layers.routed_experts(h, which, weight, real, gate, up, down)
    assert not np.allclose(relu, out)               # the caller's gate


def test_padding_slots_change_no_real_spans_score(built):
    """The whole model: a row's empty slots filled with other features
    (they stay out of ``segments``) score the real spans bit for bit the
    same, and so does the row among empty rows."""
    model, variables = built
    cat, cont, seg, pos = packed_row([6, 5], seed=8)
    base = np.asarray(model.score_packed(variables, cat, cont, seg, pos))
    junk_cat, junk_cont = cat.copy(), cont.copy()
    junk_cat[0, 11:], junk_cont[0, 11:] = 3, 7.5
    got = np.asarray(model.score_packed(variables, junk_cat, junk_cont,
                                        seg, pos))
    assert np.array_equal(got[0, :11], base[0, :11])
    pad = [np.concatenate([a, np.zeros_like(a), np.zeros_like(a)])
           for a in (cat, cont, seg, pos)]
    got = np.asarray(model.score_packed(variables, *pad))
    assert np.abs(logit(got[0, :11]) - logit(base[0, :11])).max() < 1e-5


# ------------------------------------------------------------- the sizes


def published():
    return load("benchmark", "configs", "glm-4.7-flash.json")


def test_the_published_cut_counts_its_parameters_all_bfloat16():
    stanza = published()["tpuanomaly"]
    model = TraceTransformer(make_model_config(stanza["model"],
                                               stanza["model_config"]))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    enc = {k: v for k, v in shapes["params"]["encoder"].items()
           if k != "embed"}

    def count(tree):
        return sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(tree))

    attention = 21_759_232
    assert count(enc["block_0"]) == 84_677_888 \
        == attention + 2 * 2048 + 3 * 2048 * 10240
    assert count(enc["block_1"]) == 635_311_424 \
        == attention + 2 * 2048 + 2048 * 64 + 64 + 65 * 3 * 2048 * 1536
    n = stanza["model_config"]["n_layers"]
    assert count(enc) == 84_677_888 + (n - 1) * 635_311_424 + 2048
    assert n == 9 and count(enc) - 2048 == 5_167_169_280   # + final norm
    assert {str(leaf.dtype) for leaf in jax.tree.leaves(enc)} == {"bfloat16"}
    blk = enc["block_1"]
    assert blk["q_a_proj"]["kernel"].shape == (2048, 768)
    assert blk["q_b_proj"]["kernel"].shape == (768, 20 * 256)
    assert blk["kv_a_proj"]["kernel"].shape == (2048, 512 + 64)
    assert blk["kv_b_proj"]["kernel"].shape == (512, 20 * (192 + 256))
    assert blk["o_proj"]["kernel"].shape == (20 * 256, 2048)
    assert blk["router"]["kernel"].shape == (2048, 64)
    assert blk["router_bias"].shape == (64,)
    assert blk["experts_gate"]["kernel"].shape == (64, 2048, 1536)
    assert blk["shared_down"]["kernel"].shape == (1536, 2048)
    assert model.cfg.span_attrs == {
        "model.block": "latent_moe", "model.passes": 1,
        "model.layer_applications": 9, "model.experts": 64,
        "model.experts_per_span": 4, "model.attention": "latent",
        "model.q_rank": 768, "model.kv_rank": 512,
        "model.shared_experts": 1, "model.layers_dense": 1}


def test_the_configuration_is_the_catalog_rows_config():
    cfg = published()
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["source_url"] == cfg["source"])
        assert row["name"] == "GLM-4.7-Flash"
        assert cfg["published"] == row["config"]
    pub, mc = cfg["published"], cfg["tpuanomaly"]["model_config"]
    for key, value in pub.items():                  # the top level too
        assert cfg[key] == (9 if key == "num_hidden_layers" else value)
    assert cfg["reduced"] == ["num_hidden_layers"]
    entry = next(c for c in load("BENCHMARK.json")["configs"]
                 if c["name"] == cfg["name"])
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"]
    assert (mc["d_model"], mc["n_heads"], mc["q_rank"], mc["kv_rank"],
            mc["qk_nope_dim"], mc["qk_rope_dim"], mc["v_dim"], mc["d_ff"],
            mc["n_experts"], mc["experts_per_span"], mc["d_expert"],
            mc["shared_experts"], mc["dense_layers"], mc["route_scale"],
            mc["rope_theta"], mc["norm_eps"]) == (
        pub["hidden_size"], pub["num_attention_heads"], pub["q_lora_rank"],
        pub["kv_lora_rank"], pub["qk_nope_head_dim"],
        pub["qk_rope_head_dim"], pub["v_head_dim"],
        pub["intermediate_size"], pub["n_routed_experts"],
        pub["num_experts_per_tok"], pub["moe_intermediate_size"],
        pub["n_shared_experts"], pub["first_k_dense_replace"],
        pub["routed_scaling_factor"], pub["rope_theta"],
        pub["rms_norm_eps"])
    # the floors: the leading dense layer and at least four routed ones
    assert mc["n_layers"] - mc["dense_layers"] >= 4
    assert (pub["n_group"], pub["topk_group"]) == (1, 1)   # no group step
    assert pub["hidden_act"] == "silu" and pub["norm_topk_prob"] is True
    assert set(cfg["correct"]) == {"delivery_faults", "gap_max", "gap_rms"}
    by = ARCH.flops_by_part(mc, [1])
    assert by["mlp"] / sum(by.values()) == pytest.approx(0.474, abs=2e-3)
    assert sum(by.values()) == pytest.approx(1.275e9, rel=2e-3)
    assert len(cfg["assumed"]) >= 9
    for word in ("rotate-half", "mscale", "selection bias",
                 "multi-token-prediction", "rows of 64", "final norm",
                 "bfloat16", "n_group"):
        assert any(word in line for line in cfg["assumed"]), word


def test_the_config_refuses_what_does_not_compose():
    with pytest.raises(ValueError, match="latent attention needs"):
        latent(kv_rank=0)
    with pytest.raises(ValueError, match="qk_rope_dim 7 is odd"):
        latent(qk_rope_dim=7)
    with pytest.raises(ValueError, match="experts_per_span 9"):
        latent(experts_per_span=9)
    with pytest.raises(ValueError, match="leaves no routed layer"):
        latent(dense_layers=4)
    with pytest.raises(ValueError, match="route_scale 0"):
        latent(route_scale=0)
    with pytest.raises(ValueError, match="passes"):
        latent(passes=2)
    # the routed block's own keys are not this block's, nor this block's
    # any other's
    with pytest.raises(ValueError, match=r"n_kv_heads.*routed block's"):
        latent(n_kv_heads=2)
    with pytest.raises(ValueError, match=r"window_layout.*routed block's"):
        latent(window_layout=[0] * 4)
    for block in ("encoder", "decoder"):
        with pytest.raises(ValueError, match=r"q_rank.*routed block's"):
            make_model_config("transformer", {"block": block, "q_rank": 8})
    moe = arch_case("moe_decoder")[1].SMALL
    for key, value in (("kv_rank", 8), ("shared_experts", 1),
                       ("dense_layers", 1), ("route_scale", 1.8)):
        with pytest.raises(ValueError, match=key + r".*\(latent_moe\)"):
            make_model_config("transformer", {**moe, key: value})
    cfg = make_model_config("transformer", dict(SMALL, dtype="bfloat16"))
    assert cfg.param_dtype == jnp.bfloat16 and hash(cfg) is not None
    assert cfg.routed and cfg.layer_applications == SMALL["n_layers"]


def test_the_block_states_its_scopes():
    assert set(BLOCK_PARTS) == {"encoder", "decoder", "moe", "latent_moe"}
    mine = BLOCK_PARTS["latent_moe"]
    assert set(mine) - set(BLOCK_PARTS["moe"]) == {"latent", "dense"}
    assert set(mine) == set(ARCH.PARTS)
    assert set(ARCH.PARTS.values()) == {"attn", "latent", "mlp", "dense",
                                        "route", "norm", "rest"}


def test_the_operations_carry_their_scopes(built):
    """Every product of the traced program sits under the scope that
    ``PARTS`` folds it by: into the two latents and out of them (the
    expansions are cut in their kernels and multiplied by parts, so they
    carry no module's name) under ``latent``; q k^T, a v and the output
    product under ``attn``, and nothing of ``latent`` inside it (the
    scopes are siblings); a dense layer's three and the shared expert's
    three under ``dense``; the router's under ``route``; the experts'
    grouped products under ``mlp``."""
    model, variables = built
    args = packed_row([6, 5])
    text = jax.jit(model._score_packed_impl).lower(
        variables, *args).as_text(debug_info=True)
    scopes = set(BLOCK_PARTS["latent_moe"])
    named, bare = {}, set()
    for loc in re.findall(r'loc\("([^"]*(?:dot_general|ragged_dot))"', text):
        parts = loc.split("/")
        first = next((p for p in parts if p in scopes), None)
        module = next((p for p in reversed(parts) if re.search(
            r"_proj$|^router$|^shared_|_head$", p)), None)
        if module is None:
            bare.add(first)
        else:
            named.setdefault(module, set()).add(first)
    assert named == {
        "q_a_proj": {"latent"}, "kv_a_proj": {"latent"},
        "o_proj": {"attn"}, "gate_proj": {"dense"}, "up_proj": {"dense"},
        "down_proj": {"dense"}, "shared_gate": {"dense"},
        "shared_up": {"dense"}, "shared_down": {"dense"},
        "router": {"route"}, "cont_proj": {"embed"},
        "span_head": {"head"}}
    assert bare == {"latent", "attn", "mlp"}
    kv = variables["params"]["encoder"]["block_0"]["kv_b_proj"]["kernel"]
    assert kv.shape == (SMALL["kv_rank"], SMALL["n_heads"] * (
        SMALL["qk_nope_dim"] + SMALL["v_dim"]))     # one kernel, as published


# ------------------------------------------------------------ the routes


def engine_config(**kw):
    mc = make_model_config("transformer", dict(SMALL, dtype="float32"))
    return EngineConfig(**{**dict(model="transformer", model_config=mc,
                                  max_len=16, trace_bucket=8,
                                  bucket_ladder=2), **kw})


def test_quantized_refuses_the_block_at_engine_construction():
    with pytest.raises(ValueError, match="block 'latent_moe'"):
        ScoringEngine(engine_config(quantized=True))


def test_the_partition_rules_place_every_new_kernel(built):
    from jax.sharding import PartitionSpec as P
    from odigos_tpu.parallel.sharding import match_partition_rules

    _, variables = built
    specs = match_partition_rules(variables["params"])
    enc = specs["encoder"]
    dense, routed = enc["block_0"], enc["block_1"]
    cols, rows = P(None, "model"), P("model", None)
    for blk in (dense, routed):
        assert blk["q_b_proj"]["kernel"] == cols    # heads side by side
        assert blk["kv_b_proj"]["kernel"] == cols
        assert blk["o_proj"]["kernel"] == rows
        assert blk["q_a_proj"]["kernel"] == P()     # read whole by each head
        assert blk["kv_a_proj"]["kernel"] == P()
        assert blk["q_a_norm"]["scale"] == blk["kv_a_norm"]["scale"] == P()
    assert dense["gate_proj"]["kernel"] == dense["up_proj"]["kernel"] == cols
    assert dense["down_proj"]["kernel"] == rows
    assert routed["shared_gate"]["kernel"] == cols
    assert routed["shared_up"]["kernel"] == cols
    assert routed["shared_down"]["kernel"] == rows
    for name in ("router", "experts_gate", "experts_up", "experts_down"):
        assert routed[name]["kernel"] == P()
    assert routed["router_bias"] == P()


def test_a_model_axis_is_refused_with_the_reason():
    with pytest.raises(ValueError, match=f"model axis 2 cannot place "
                       f"{5 * ROUTED} parameters.*block_1/.*"
                       f"split rows by expert"):
        ScoringEngine(engine_config(mesh={"data": 2, "model": 2}))


def test_the_block_scores_the_same_on_a_data_mesh_of_four(monkeypatch):
    """Four virtual CPU devices, ``mesh {data: 4}``, the parameters
    replicated, each device routing its own quarter of the rows: the
    engine's packed scores and the call's counts equal the
    single-device ones."""
    batch = synthesize_traces(60, seed=42)
    feats = featurize(batch)
    one = ScoringEngine(engine_config(seed=5)).backend
    four = ScoringEngine(engine_config(seed=5, mesh={"data": 4})).backend
    slots = []
    routed_experts = layers.routed_experts
    monkeypatch.setattr(layers, "routed_experts", lambda h, *a, **kw: (
        slots.append(h.shape[0]), routed_experts(h, *a, **kw))[1])
    placed = four._plan.place_variables(four.variables)
    blk = placed["params"]["encoder"]["block_1"]
    for leaf in (blk["experts_gate"]["kernel"], blk["router_bias"],
                 blk["shared_gate"]["kernel"], blk["kv_b_proj"]["kernel"]):
        assert leaf.sharding.spec == jax.sharding.PartitionSpec()
        assert len(leaf.sharding.device_set) == 4
    s1, s4 = one.score(batch, feats), four.score(batch, feats)
    assert s1.shape == s4.shape == (len(batch),)
    whole, quarter = max(slots), min(slots)     # as traced: 3 layers each
    assert whole == 4 * quarter and slots.count(quarter) == ROUTED
    np.testing.assert_allclose(s4, s1, atol=1e-5, rtol=1e-4)
    h1 = one.call_attrs(one.fetch(one.dispatch(batch, feats)))
    h4 = four.call_attrs(four.fetch(four.dispatch(batch, feats)))
    assert h1["moe.assignments"] == h4["moe.assignments"] \
        == len(batch) * SMALL["experts_per_span"] * ROUTED
    assert h1["moe.experts_busy_min"] == h4["moe.experts_busy_min"]


def test_the_fused_route_serves_the_block():
    from odigos_tpu.serving.fused import (PARITY_F32, extract_columns,
                                          routes_agree)

    eng = ScoringEngine(engine_config())
    backend = eng.backend
    assert backend.supports_fused
    b = synthesize_traces(40, seed=3)
    want = backend.score(b, featurize(b, eng.cfg.featurizer))
    cols, reason = extract_columns(b, eng.cfg.featurizer)
    assert reason is None
    handle = backend.dispatch_columns([cols])
    assert backend.call_attrs(backend.fetch(handle)) == {}
    got = backend.harvest(handle)
    np.testing.assert_allclose(got, want, rtol=PARITY_F32[0],
                               atol=PARITY_F32[1])
    assert routes_agree(got, want, "float32")


# --------------------------------------------------------------- tracing


def test_score_spans_say_what_the_model_is_and_what_each_call_routed():
    from odigos_tpu.selftelemetry.tracer import tracer
    from odigos_tpu.models.transformer import EXPERT_ASSIGNMENTS_METRIC
    from odigos_tpu.serving.engine import LAYER_APPLICATIONS_METRIC
    from odigos_tpu.utils.telemetry import meter

    eng = ScoringEngine(engine_config()).start()
    try:
        tracer.ring.drain()
        before = meter.snapshot()
        sizes = []
        for seed in (1, 2, 3):
            b = synthesize_traces(12, seed=seed)
            sizes.append(len(b))
            assert len(eng.score_sync(b, timeout_s=60.0)) == len(b)
        spans = [s for s in tracer.ring.snapshot() if s.name == "tpu/score"]
        assert len(spans) == 3
        per_span = SMALL["experts_per_span"] * ROUTED   # none in the dense
        for sp, n in zip(spans, sizes):
            a = sp.attrs
            assert (a["model.block"], a["model.passes"],
                    a["model.layer_applications"]) == ("latent_moe", 1, 4)
            assert (a["model.attention"], a["model.q_rank"],
                    a["model.kv_rank"], a["model.experts"],
                    a["model.experts_per_span"], a["model.shared_experts"],
                    a["model.layers_dense"]) == ("latent", 24, 20, 8, 2, 1, 1)
            assert "model.layers_rotary" not in a
            assert a["batch.spans"] == n
            assert a["moe.assignments"] == n * per_span
            assert 1.0 <= a["moe.load_max_over_mean"] <= 8 / 2
            # of 8 experts, at least the 2 a span takes hold one
            assert 2 <= a["moe.experts_busy_min"] <= 8
            assert isinstance(a["moe.experts_busy_min"], int)
        after = meter.snapshot()
        assert after[EXPERT_ASSIGNMENTS_METRIC] \
            - before.get(EXPERT_ASSIGNMENTS_METRIC, 0.0) \
            == sum(sizes) * per_span
        assert after[LAYER_APPLICATIONS_METRIC] \
            - before.get(LAYER_APPLICATIONS_METRIC, 0.0) == 3 * 4
    finally:
        eng.shutdown()


def test_a_collapsed_routing_reads_in_the_programs_own_count(built):
    """``moe.experts_busy_min``: with a selection bias that lifts two
    experts past every score, every span of every routed layer takes those
    two, and the call's count reads 2 where the seeded draw reads more."""
    model, variables = built
    args = packed_row([6, 5, 4], rows=4)
    _, counts = model.score_packed_counted(variables, *args)
    assert int(counts["moe.experts_busy_min"]) > 2
    lifted = jnp.zeros(SMALL["n_experts"]).at[jnp.array([1, 6])].set(2.0)
    collapsed = jax.tree_util.tree_map_with_path(
        lambda path, leaf: lifted.astype(leaf.dtype)
        if "router_bias" in jax.tree_util.keystr(path) else leaf, variables)
    _, counts = model.score_packed_counted(collapsed, *args)
    assert int(counts["moe.experts_busy_min"]) == 2
    assert float(counts["moe.load_max_over_mean"]) == pytest.approx(8 / 2)


def test_serving_names_no_model_or_configuration():
    names = ("glm", "smallthinker", "latent", "moe")
    serving = os.path.join(ROOT, "odigos_tpu", "serving")
    for fn in sorted(os.listdir(serving)):
        if fn.endswith(".py"):
            with open(os.path.join(serving, fn)) as f:
                words = set(re.findall(r"[a-z0-9]+", f.read().lower()))
            assert not [n for n in names if n in words], fn


# ------------------------------------------------------------- the cell


@pytest.fixture(scope="module")
def rehearsal_latent():
    r = load("benchmark", "tests", "rehearsal_latent.json")
    r["settle_s"] = 4.0
    return r


def test_the_cell_is_the_benchmarks_by_entries_alone():
    bench = load("BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("glm-4.7-flash", "backlog", 1)
    assert len(cell["why"]) <= 200
    assert [m["name"] for m in run.cell_metrics(bench, cell, "end_to_end")] \
        == ["spans_per_s", "setup_s"]
    mine = {m["name"] for m in run.cell_metrics(bench, cell, "per_layer")}
    sibling = next(w for w in bench["workloads"]
                   if w["name"] == "smallthinker-21b-a3b.backlog")
    beside = {m["name"] for m in run.cell_metrics(bench, sibling,
                                                  "per_layer")}
    assert mine == beside | {"step_latent_ms.backlog",
                             "step_dense_ms.backlog"}
    for name in ("step_latent_ms.backlog", "step_dense_ms.backlog"):
        m = next(m for m in bench["per_layer"] if m["name"] == name)
        assert m["workloads"] == [CELL] and m["moves"] == "spans_per_s"
        assert (m["layer"], m["source"], m["unit"], m["better"]) \
            == ("model step", "device_trace", "ms", "lower")
    # additions alone: the entries stand last in their lists, and the
    # cell's name last in every list it was appended to
    assert bench["workloads"][-1] is cell
    assert bench["configs"][-1]["name"] == "glm-4.7-flash"
    assert [m["name"] for m in bench["per_layer"][-2:]] \
        == ["step_latent_ms.backlog", "step_dense_ms.backlog"]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", []):
            assert m["workloads"][-1] == CELL


@pytest.mark.parametrize("seed", [21, 2**31 + 22])
def test_the_cell_rehearsed_is_correct_and_its_control_is_not(
        seed, rehearsal_latent, stood_in_trace):  # noqa: F811
    """The cell through ``run.run_cell`` at the rehearsal's size, traced:
    the program (bfloat16 over bfloat16 parameters) reads ``correct``
    against the plain reference, the reference in float8 put in its place
    does not, by ``gap_rms``; the line carries the cell's metrics, the two
    new parts among them, and the program's own count of assignments
    moved by spans x 4 x 4 routed layers."""
    from odigos_tpu.models.transformer import EXPERT_ASSIGNMENTS_METRIC
    from odigos_tpu.utils.telemetry import meter

    before = meter.snapshot().get(EXPERT_ASSIGNMENTS_METRIC, 0.0)
    line = run.run_cell(CELL, seed, 1.0, True, rehearse=rehearsal_latent,
                        control=True)
    assert line["correct"] is True and line["failed"] == 0
    assert line["rehearsal"] is True
    limits = rehearsal_latent["correct"]
    control = line["control"]
    assert control["correct"] is False and control["precision"] == "fp8"
    assert control["delivery_faults"] == 0
    # by gap_rms, one of the cell's limits: a sound run's widest gap (a
    # span whose fourth and fifth biased scores lie within bfloat16's
    # rounding takes another expert than the reference's) reaches the
    # control's, so gap_max guards a span that is wrong outright
    assert control["gap_rms"] > limits["gap_rms"]
    assert control["gap_rms"] > 2 * line["compared"]["gap_rms"]["value"]
    got = set(line["metrics"])
    assert {"step_latent_ms.backlog", "step_dense_ms.backlog",
            "step_route_ms.backlog", "experts_roofline.backlog",
            "step_norm_ms.backlog", "step_mlp_ms.backlog",
            "step_attn_ms.backlog", "step_mfu.backlog",
            "padded_share.backlog"} <= got
    assert not [m for m in got if m.endswith(".steady")]
    assert set(line["hosttrace"]["parts"]) <= {
        "attn", "latent", "mlp", "dense", "route", "norm", "rest"}
    moved = meter.snapshot()[EXPERT_ASSIGNMENTS_METRIC] - before
    assert moved >= line["attempted"] * 4 * 4      # the warm-up's beside
    assert moved % (4 * 4) == 0


def test_the_cell_judged_by_an_expert_fewer_is_not_correct(
        rehearsal_latent, stood_in_trace):  # noqa: F811
    """``benchmark/tests/latent_an_expert_fewer.json`` lays a reference
    with one routed expert a span fewer over the cell: the same served
    scores, through the same window and judge, read ``correct`` false by
    ``gap_rms``."""
    fewer = load("benchmark", "tests", "latent_an_expert_fewer.json")
    line = run.run_cell(CELL, 23, 1.0, False,
                        rehearse={**rehearsal_latent, **fewer})
    assert line["failed"] == 0 and line["correct"] is False
    read = line["compared"]
    assert read["delivery_faults"]["value"] == 0
    assert read["gap_rms"]["value"] \
        > 2 * rehearsal_latent["correct"]["gap_rms"]
