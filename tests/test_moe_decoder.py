"""The transformer scorer's third block kind (ISSUE 34): a routed decoder
block with pre-norm RMS residuals, grouped query heads over fewer
key/value heads, rotary positions and a window layer by layer, and a
router ahead of attention that sends each span to ``experts_per_span`` of
``n_experts`` ReLU-gated experts, the parameters held in bfloat16. Held
here: the program against the benchmark's plain reference
(``benchmark/architectures/moe_decoder.py``), one layer by hand, what the
window, the rotary flag, the top-k and the padding promise, the size of
the published cut, the configuration against the catalog, the routes that
refuse the block and those that serve it, what the engine says of the
model and of each call on its ``tpu/score`` spans, and the cell's own
rehearsal."""

from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import architectures, gen, judge, reference, run
from benchmark.tests.conftest import arch_case, stood_in_trace  # noqa: F401
from odigos_tpu.features import featurize
from odigos_tpu.features.featurizer import pack_sequences
from odigos_tpu.models import layers
from odigos_tpu.models.layers import BLOCK_PARTS
from odigos_tpu.models.transformer import TraceTransformer, TransformerConfig
from odigos_tpu.pdata import synthesize_traces
from odigos_tpu.serving import EngineConfig, ScoringEngine
from odigos_tpu.training import make_model_config

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 2**31 + 34
ARCH, CASE = arch_case("moe_decoder")
SMALL = CASE.SMALL
CELL = "smallthinker-21b-a3b.backlog"


def routed(**over):
    model = TraceTransformer(make_model_config(
        "transformer", {**SMALL, "dtype": "float32", **over}))
    return model, model.init(jax.random.PRNGKey(SEED))


@pytest.fixture(scope="module")
def built():
    return routed()


def load(*path):
    with open(os.path.join(ROOT, *path)) as f:
        return json.load(f)


def logit(p):
    p = np.asarray(p, np.float64)
    return np.log(p) - np.log1p(-p)


def packed_row(lengths, seed=0, L=16, rows=1):
    """One packed row (repeated ``rows`` times) holding traces of these
    lengths side by side, as ``pack_sequences`` lays them out."""
    rng = np.random.default_rng(seed)
    seg, pos = np.zeros(L, np.int32), np.zeros(L, np.int32)
    at = 0
    for s, n in enumerate(lengths, start=1):
        seg[at:at + n], pos[at:at + n] = s, np.arange(n)
        at += n
    cat = rng.integers(1, 4, (L, 5)).astype(np.int32)
    cont = rng.normal(size=(L, 3)).astype(np.float32)
    cat[seg == 0], cont[seg == 0] = 0, 0
    tile = lambda a: np.repeat(a[None], rows, axis=0)  # noqa: E731
    return tile(cat), tile(cont), tile(seg), tile(pos)


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


def test_the_routed_block_matches_the_plain_reference(built):
    """Seeded weights, float32 over the bfloat16 parameters, to 1e-4 in
    the logit, on frames whose traces of unequal length share rows and
    which cross ``block_rows``."""
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
        assert max(lengths) > SMALL["window"]       # the window cuts pairs
        assert np.abs(logit(got) - logit(ref)).max() < 1e-4


def test_the_weights_are_the_references_bit_for_bit(built):
    _, variables = built
    pairs = list(CASE.weight_pairs(ARCH, reference, variables["params"],
                                   SEED))
    assert len(pairs) == 6 + 8 * SMALL["n_layers"]
    for ours, theirs in pairs:
        np.testing.assert_array_equal(np.asarray(ours), np.asarray(theirs))


def test_one_layer_by_hand():
    """Float64 from the parameters: the router reads the layer's raw
    input, the top-k comes before the softmax, ReLU gates, query head g
    reads key/value head g // 2, the residuals are pre-norm and the final
    norm closes the stack."""
    model, variables = routed(n_layers=1, rope_layout=[1],
                              window_layout=[1])
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

    def rms(h):
        return h / np.sqrt((h * h).mean(-1, keepdims=True) + 1e-6)

    H, K, hd = SMALL["n_heads"], SMALL["n_kv_heads"], SMALL["head_dim"]
    w = SMALL["rope_theta"] ** (-np.arange(hd // 2) / (hd // 2))
    ang = pos[0][:, None] * w

    def rope(u):                                   # (L, heads, hd)
        a, b = u[..., :hd // 2], u[..., hd // 2:]
        cos, sin = np.cos(ang)[:, None], np.sin(ang)[:, None]
        return np.concatenate([a * cos - b * sin, b * cos + a * sin], -1)

    r = x @ blk["router"]["kernel"]                # ahead of the norm
    h = rms(x)
    q = rope((h @ blk["q_proj"]["kernel"]).reshape(-1, H, hd))
    k = rope((h @ blk["k_proj"]["kernel"]).reshape(-1, K, hd))
    v = (h @ blk["v_proj"]["kernel"]).reshape(-1, K, hd)
    apart = pos[0][:, None] - pos[0][None]
    allowed = (seg[0][:, None] == seg[0][None]) & real[:, None] \
        & real[None] & (apart >= 0) & (apart < SMALL["window"])
    o = np.zeros((len(x), H, hd))
    for g in range(H):
        s = q[:, g] @ k[:, g // (H // K)].T / np.sqrt(hd)
        s = np.where(allowed, s, -1e30)
        a = np.exp(s - s.max(-1, keepdims=True))
        a /= a.sum(-1, keepdims=True)
        o[:, g] = a @ v[:, g // (H // K)]
    x = x + o.reshape(len(x), -1) @ blk["o_proj"]["kernel"]
    h = rms(x)
    y = np.zeros_like(x)
    for t in np.flatnonzero(real):
        chosen = np.argsort(-r[t])[:SMALL["experts_per_span"]]
        weight = np.exp(r[t, chosen] - r[t, chosen].max())
        weight /= weight.sum()
        for e, w_e in zip(chosen, weight):
            gate = np.maximum(h[t] @ blk["experts_gate"]["kernel"][e], 0)
            up = h[t] @ blk["experts_up"]["kernel"][e]
            y[t] += w_e * ((gate * up) @ blk["experts_down"]["kernel"][e])
    x = rms(x + y)                                 # the final norm
    want = x @ p["span_head"]["kernel"][:, 0] + p["span_head"]["bias"][0]
    assert np.abs(logit(got[real]) - want[real]).max() < 1e-4


# ------------------------------------- window, rotary, top-k, padding


def test_the_window_cuts_a_trace_longer_than_it_and_no_shorter_one(built):
    """``window`` 5 against a window past the row, the same weights: a
    trace of 12 spans scores differently from its sixth span on, a row of
    traces of at most 5 spans bit for bit the same."""
    model, variables = built
    wide, _ = routed(window=64)
    long_row, short_rows = packed_row([12, 3]), packed_row([5, 4, 5])
    a = np.asarray(model.score_packed(variables, *long_row))[0]
    b = np.asarray(wide.score_packed(variables, *long_row))[0]
    assert np.array_equal(a[:5], b[:5])            # five spans back: seen
    assert not np.array_equal(a[5:12], b[5:12])
    assert np.array_equal(a[12:15], b[12:15])      # the short neighbour
    assert np.array_equal(
        np.asarray(model.score_packed(variables, *short_rows)),
        np.asarray(wide.score_packed(variables, *short_rows)))
    none, _ = routed(window_layout=[0] * 8)         # no layer has one
    assert np.array_equal(
        b, np.asarray(none.score_packed(variables, *long_row))[0])


def test_a_layer_without_rotary_leaves_queries_and_keys_unrotated(
        monkeypatch):
    """The stack of ``rope_layout`` 0, 1 rotates in its second block
    alone: twice (q and k), with the 2 key/value heads' keys among them;
    a stack that rotates nowhere never calls ``rotate`` and then a
    trace's scores do not know its spans' positions beyond their order."""
    seen = []
    rotate = layers.rotate

    def spy(x, cos, sin):
        seen.append(x.shape[-2])
        return rotate(x, cos, sin)

    monkeypatch.setattr(layers, "rotate", spy)
    two = dict(n_layers=2, window_layout=[0, 0])
    model, variables = routed(rope_layout=[0, 1], **two)
    bare, _ = routed(rope_layout=[0, 0], **two)
    args = packed_row([7, 6])
    seen.clear()                        # ``init`` ran each forward once
    with jax.disable_jit():
        model._score_packed_impl(variables, *args)
    assert seen == [SMALL["n_heads"], SMALL["n_kv_heads"]]
    seen.clear()
    with jax.disable_jit():
        base = np.asarray(bare._score_packed_impl(variables, *args))
        spread = (args[0], args[1], args[2], args[3] * 3)
        moved = np.asarray(bare._score_packed_impl(variables, *spread))
        rotated = np.asarray(model._score_packed_impl(variables, *spread))
    assert seen == [SMALL["n_heads"], SMALL["n_kv_heads"]]    # model's
    assert np.array_equal(base, moved)
    assert not np.array_equal(rotated, moved)


@pytest.mark.parametrize("what", ["an_expert_fewer", "no_softmax"])
def test_an_expert_left_out_or_the_softmax_left_out_is_another_model(
        built, what, monkeypatch):
    """Apart by more than the rehearsal's limits, so a build that takes
    one expert a span fewer, or weighs the chosen experts evenly, cannot
    read ``correct``."""
    model, variables = built
    limits = load("benchmark", "tests", "rehearsal_moe.json")["correct"]
    args = packed_row([5, 7, 3], rows=4)
    real = args[2] > 0
    a = np.asarray(model.score_packed(variables, *args))
    if what == "an_expert_fewer":
        other, _ = routed(experts_per_span=SMALL["experts_per_span"] - 1)
    else:
        softmax = jax.nn.softmax
        monkeypatch.setattr(jax.nn, "softmax", lambda t, axis=-1, **kw:
                            jnp.full_like(t, 1.0 / t.shape[axis])
                            if t.shape[-1] == SMALL["experts_per_span"]
                            else softmax(t, axis=axis, **kw))
        other, _ = routed()
    b = np.asarray(other.score_packed(variables, *args))
    gap = judge.logit_gap(b[real], a[real])
    assert np.abs(gap).max() > limits["gap_max"]
    assert np.sqrt(np.mean(gap * gap)) > limits["gap_rms"]


def test_the_grouped_products_equal_a_dense_pass_and_padding_is_inert():
    """``routed_experts`` against every expert computed for every span
    and weighted (zero where not chosen); the slots that hold no span
    come back zero, take no assignment, and what they hold changes no
    real span's output."""
    rng = np.random.default_rng(5)
    T, d, E, f, k = 96, 32, 8, 16, 3
    h = jnp.asarray(rng.normal(size=(T, d)), jnp.float32)
    r = jnp.asarray(rng.normal(size=(T, E)), jnp.float32)
    real = jnp.asarray(rng.random(T) < 0.8)
    gate, up = (jnp.asarray(rng.normal(size=(E, d, f)) / 6, jnp.float32)
                for _ in range(2))
    down = jnp.asarray(rng.normal(size=(E, f, d)) / 4, jnp.float32)
    chosen, weight = layers.top_softmax(r, k)
    out, load_ = layers.routed_experts(h, chosen, weight, real, gate, up,
                                       down)
    top, again = jax.lax.top_k(r, k)       # the rule: top-k, then softmax
    assert np.array_equal(chosen, again)
    assert np.array_equal(weight, jax.nn.softmax(top, axis=-1))
    dense = jnp.einsum("tef,efd->ted", jax.nn.relu(
        jnp.einsum("td,edf->tef", h, gate))
        * jnp.einsum("td,edf->tef", h, up), down)
    mix = jnp.zeros((T, E)).at[jnp.arange(T)[:, None], chosen].set(weight)
    want = jnp.einsum("te,ted->td", mix, dense) * real[:, None]
    np.testing.assert_allclose(out, want, atol=2e-6)
    assert not np.asarray(out)[~np.asarray(real)].any()
    assert int(load_.sum()) == int(real.sum()) * k
    assert np.array_equal(load_, np.bincount(
        np.asarray(chosen)[np.asarray(real)].ravel(), minlength=E))
    noisy = jnp.where(real[:, None], h, jnp.nan)    # whatever padding holds
    again, _ = layers.routed_experts(
        noisy, *layers.top_softmax(jnp.where(real[:, None], r, 9.0), k),
        real, gate, up, down)
    assert np.array_equal(out, again)


def test_the_pallas_grouped_products_equal_the_ragged_ones():
    """The TPU's branch of the experts (``megablox.gmm``) in interpret
    mode against the branch every other platform takes, over the rows
    that belong to an expert (one expert takes none; the last tile is
    part empty); what lies past them is nobody's."""
    rng = np.random.default_rng(9)
    m, d, f, E = 2 * layers.GROUP_ROWS, 256, 128, 4
    x = jnp.asarray(rng.normal(size=(m, d)), jnp.float32)
    gate, up = (jnp.asarray(rng.normal(size=(E, d, f)) / 16, jnp.float32)
                for _ in range(2))
    down = jnp.asarray(rng.normal(size=(E, f, d)) / 11, jnp.float32)
    load_ = jnp.asarray([300, 0, 411, 200], jnp.int32)
    want = layers._experts_ragged(x, gate, up, down, load_)
    got = layers._experts_gmm(x, gate, up, down, load_, interpret=True)
    n = int(load_.sum())
    np.testing.assert_allclose(got[:n], want[:n], atol=1e-5)
    # the gate's activation is the caller's, in both branches alike
    silu = layers._experts_ragged(x, gate, up, down, load_, jax.nn.silu)
    got = layers._experts_gmm(x, gate, up, down, load_, jax.nn.silu,
                              interpret=True)
    np.testing.assert_allclose(got[:n], silu[:n], atol=1e-5)
    assert not np.allclose(silu[:n], want[:n], atol=1e-3)


@pytest.fixture(scope="module")
def four_chips():
    """The devices of a described v5e 2 x 2 (no chip needed): the TPU's
    compiler is installed here. Described inside the fixture, never at
    import."""
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return topo.devices


@pytest.fixture(scope="module")
def one_chip(four_chips):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(four_chips[0])


@pytest.mark.parametrize("d, f, k, rule", [
    (2560, 768, 6, "top_softmax"), (2048, 1536, 4, "biased_sigmoid")])
def test_the_routed_experts_compile_for_the_chip_as_pallas_kernels(
        one_chip, d, f, k, rule):
    """``routed_experts`` at the published widths of each routed
    configuration and a 256-row call's spans, under its own rule of
    choice, compiled for the v5e: the three grouped products are Pallas
    kernels (``gmm``) under the ``mlp`` scope, none is XLA's own
    ragged-dot kernel (which keeps no scope in the trace), and the
    temporaries stay under 1.5 GB."""
    from jax.experimental.compilation_cache import compilation_cache

    T, E = 256 * 64, 64

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def route(h, r, real, g, u, dn):
        if rule == "top_softmax":
            return layers.routed_experts(h, *layers.top_softmax(r, k),
                                         real, g, u, dn)
        return layers.routed_experts(
            h, *layers.biased_sigmoid(r, r[0], k, 1.8), real, g, u, dn,
            jax.nn.silu)

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        compiled = jax.jit(route).lower(
            S((T, d), jnp.bfloat16), S((T, E), jnp.float32),
            S((T,), jnp.bool_), S((E, d, f), jnp.bfloat16),
            S((E, d, f), jnp.bfloat16), S((E, f, d), jnp.bfloat16)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
    text = compiled.as_text()
    kernels = [line for line in text.splitlines()
               if "tpu_custom_call" in line and "pallas_call" in line]
    assert len(kernels) == 3
    assert all("/mlp/" in line and "gmm" in line for line in kernels)
    assert "ragged-dot" not in text and "ragged_dot" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 1.5e9


@pytest.mark.parametrize("arch", ["moe_decoder", "latent_moe_decoder"])
def test_four_chips_each_route_their_own_rows(four_chips, arch):
    """The whole model at ``SMALL`` (either routed block's) compiled for a
    v5e 2 x 2 as the plan
    traces it (``mesh {data: 4}``, inside the mesh): the partitioner
    refuses a bare Pallas kernel ("Mosaic kernels cannot be automatically
    partitioned"), so each device runs the routed feed-forward on its own
    rows, three kernels a layer, and the only exchange of the stack is
    the sum of the loads."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from odigos_tpu.parallel.sharding import compile_plan

    small = arch_case(arch)[1].SMALL
    mesh = Mesh(np.array(four_chips).reshape(4), ("data",))
    model = TraceTransformer(make_model_config("transformer", small))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    plan = compile_plan(model, mesh, variables=shapes)
    rows, L = 8, small["max_len"]

    def S(shape, dtype, spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    args = (jax.tree.map(lambda a: S(a.shape, a.dtype, P()), shapes),
            S((rows, L, 5), jnp.int32, P("data")),
            S((rows, L, 3), jnp.float32, P("data")),
            S((rows, L), jnp.int32, P("data")),
            S((rows, L), jnp.int32, P("data")))
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with jax.set_mesh(mesh):
            text = plan._packed_jit.lower(*args).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
    kernels = [line for line in text.splitlines()
               if "tpu_custom_call" in line and "pallas_call" in line]
    assert len(kernels) == 3 * (small["n_layers"]
                                - small.get("dense_layers", 0))
    assert "all-gather" not in text and "all-to-all" not in text


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


def published_stanza():
    return load("benchmark", "configs",
                "smallthinker-21b-a3b.json")["tpuanomaly"]


def test_the_published_cut_counts_its_parameters_all_bfloat16():
    stanza = published_stanza()
    model = TraceTransformer(make_model_config(stanza["model"],
                                               stanza["model_config"]))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    enc = {k: v for k, v in shapes["params"]["encoder"].items()
           if k != "embed"}
    leaves = jax.tree.leaves(enc)
    assert sum(int(np.prod(leaf.shape)) for leaf in leaves) \
        == 12 * 398_627_840 + 2560 == 4_783_534_080 + 2560  # + final norm
    assert {str(leaf.dtype) for leaf in leaves} == {"bfloat16"}
    blk = enc["block_0"]
    assert blk["q_proj"]["kernel"].shape == (2560, 3584)
    assert blk["k_proj"]["kernel"].shape == (2560, 512)
    assert blk["o_proj"]["kernel"].shape == (3584, 2560)
    assert blk["router"]["kernel"].shape == (2560, 64)
    assert blk["experts_gate"]["kernel"].shape == (64, 2560, 768)
    assert blk["experts_down"]["kernel"].shape == (64, 768, 2560)
    assert model.cfg.span_attrs == {
        "model.block": "moe", "model.passes": 1,
        "model.layer_applications": 12, "model.experts": 64,
        "model.experts_per_span": 6, "model.layers_rotary": 9,
        "model.layers_window": 9}


def test_the_configuration_is_the_catalog_rows_config():
    cfg = load("benchmark", "configs", "smallthinker-21b-a3b.json")
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["source_url"] == cfg["source"])
        assert cfg["published"] == row["config"]
    pub, mc = cfg["published"], cfg["tpuanomaly"]["model_config"]
    for key, value in pub.items():                  # the top level too
        assert cfg[key] == (12 if key == "num_hidden_layers" else value)
    assert cfg["reduced"] == ["num_hidden_layers"]
    assert (mc["d_model"], mc["n_heads"], mc["n_kv_heads"], mc["head_dim"],
            mc["n_experts"], mc["experts_per_span"], mc["d_expert"],
            mc["window"], mc["rope_theta"], mc["norm_eps"]) == (
        pub["hidden_size"], pub["num_attention_heads"],
        pub["num_key_value_heads"], pub["head_dim"],
        pub["moe_num_primary_experts"],
        pub["moe_num_active_primary_experts"], pub["moe_ffn_hidden_size"],
        pub["sliding_window_size"], pub["rope_theta"], pub["rms_norm_eps"])
    n = mc["n_layers"]
    assert mc["rope_layout"] == pub["rope_layout"][:n]
    assert mc["window_layout"] == pub["sliding_window_layout"][:n]
    assert n % 4 == 0 and n >= 8                    # whole periods
    assert set(cfg["correct"]) == {"delivery_faults", "gap_max", "gap_rms"}
    assert ARCH.flops_by_part(mc, [1])["mlp"] / sum(
        ARCH.flops_by_part(mc, [1]).values()) == pytest.approx(0.626, abs=2e-3)


def test_the_config_refuses_what_does_not_compose():
    with pytest.raises(ValueError, match="multiple of n_kv_heads"):
        routed(n_kv_heads=3)
    with pytest.raises(ValueError, match="experts_per_span 9"):
        routed(experts_per_span=9)
    with pytest.raises(ValueError, match="each state all n_layers"):
        routed(rope_layout=[0, 1])
    with pytest.raises(ValueError, match="window 0"):
        routed(window=0)
    with pytest.raises(ValueError, match="passes"):
        routed(passes=2)
    with pytest.raises(ValueError, match="multiple of n_kv_heads"):
        routed(n_kv_heads=0)
    with pytest.raises(ValueError, match="routed block's"):
        make_model_config("transformer", {"n_experts": 8})
    with pytest.raises(ValueError, match="routed block's"):
        make_model_config("transformer", {"block": "decoder",
                                          "param_dtype": "bfloat16"})
    cfg = make_model_config("transformer", dict(SMALL, dtype="bfloat16"))
    assert cfg.param_dtype == jnp.bfloat16 and hash(cfg) is not None
    assert cfg.rope_layout == (0, 1, 1, 1, 0, 1, 1, 1)


def test_each_block_kind_states_its_scopes():
    assert set(BLOCK_PARTS) == {"encoder", "decoder", "moe", "latent_moe"}
    for arch in ("moe_decoder", "latent_moe_decoder"):  # both count
        cfg = make_model_config("transformer", arch_case(arch)[1].SMALL)
        assert cfg.routed and set(BLOCK_PARTS[cfg.block]) >= {"route", "mlp"}
        assert cfg.call_counters == {
            "moe.assignments": "odigos_anomaly_expert_assignments_total"}
    assert not TransformerConfig().routed
    assert TransformerConfig().call_counters == {}
    assert "route" in BLOCK_PARTS["moe"] and "norm" in BLOCK_PARTS["moe"]
    assert set(BLOCK_PARTS["moe"]) <= set(ARCH.PARTS)
    assert set(ARCH.PARTS.values()) == {"attn", "mlp", "route", "norm",
                                        "rest"}


# ------------------------------------------------------------ the routes


def engine_config(**kw):
    mc = make_model_config("transformer", dict(SMALL, dtype="float32"))
    return EngineConfig(**{**dict(model="transformer", model_config=mc,
                                  max_len=16, trace_bucket=8,
                                  bucket_ladder=2), **kw})


def test_quantized_refuses_the_block_at_engine_construction():
    with pytest.raises(ValueError, match="block 'moe'"):
        ScoringEngine(engine_config(quantized=True))


def test_a_model_axis_is_refused_with_the_reason():
    with pytest.raises(ValueError, match="model axis 2 cannot place 32 "
                       "parameters.*block_0/experts_down/kernel.*"
                       "split rows by expert"):
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
    gate = placed["params"]["encoder"]["block_0"]["experts_gate"]["kernel"]
    assert gate.sharding.spec == jax.sharding.PartitionSpec()
    assert len(gate.sharding.device_set) == 4
    s1, s4 = one.score(batch, feats), four.score(batch, feats)
    assert s1.shape == s4.shape == (len(batch),)
    whole, quarter = max(slots), min(slots)     # as traced: 8 layers each
    assert whole == 4 * quarter and slots.count(quarter) == 8
    np.testing.assert_allclose(s4, s1, atol=1e-5, rtol=1e-4)
    h1 = one.fetch(one.dispatch(batch, feats))
    h4 = four.fetch(four.dispatch(batch, feats))
    assert one.call_attrs(h1)["moe.assignments"] \
        == four.call_attrs(h4)["moe.assignments"] \
        == len(batch) * SMALL["experts_per_span"] * SMALL["n_layers"]


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
        per_span = SMALL["experts_per_span"] * SMALL["n_layers"]
        for sp, n in zip(spans, sizes):
            a = sp.attrs
            assert (a["model.block"], a["model.passes"],
                    a["model.layer_applications"]) == ("moe", 1, 8)
            assert (a["model.experts"], a["model.experts_per_span"],
                    a["model.layers_rotary"], a["model.layers_window"]) \
                == (8, 2, 6, 6)
            assert a["batch.spans"] == n
            assert a["moe.assignments"] == n * per_span
            # 8 experts, 2 a span: the busiest of 64 (layer, expert)
            # pairs lies between the mean and every span of a layer
            assert 1.0 <= a["moe.load_max_over_mean"] <= 8 / 2
            # the fewest experts busy in any of the 8 layers: at least
            # the 2 a span takes, at most all 8
            assert 2 <= a["moe.experts_busy_min"] <= 8
        after = meter.snapshot()
        assert after[EXPERT_ASSIGNMENTS_METRIC] \
            - before.get(EXPERT_ASSIGNMENTS_METRIC, 0.0) \
            == sum(sizes) * per_span
        assert after[LAYER_APPLICATIONS_METRIC] \
            - before.get(LAYER_APPLICATIONS_METRIC, 0.0) == 3 * 8
    finally:
        eng.shutdown()


def test_the_other_blocks_stamp_what_they_did():
    """No expert attribute, no count, no second entry."""
    mc = make_model_config("transformer", dict(
        d_model=64, n_heads=4, n_layers=2, d_ff=128, max_len=16,
        block="decoder", passes=2, dtype="float32"))
    backend = ScoringEngine(EngineConfig(
        model="transformer", model_config=mc, max_len=16, trace_bucket=8,
        bucket_ladder=2)).backend
    assert backend.model.score_packed_counted is None
    assert backend.score_attrs == {"model.block": "decoder",
                                   "model.passes": 2,
                                   "model.layer_applications": 4}
    b = synthesize_traces(12, seed=1)
    handle = backend.fetch(backend.dispatch(b, featurize(b)))
    assert backend.call_attrs(handle) == {}


def test_serving_names_no_model_or_configuration():
    names = ("smallthinker", "ouro", "vit-h", "vit_h")
    serving = os.path.join(ROOT, "odigos_tpu", "serving")
    for fn in sorted(os.listdir(serving)):
        if fn.endswith(".py"):
            with open(os.path.join(serving, fn)) as f:
                text = f.read().lower()
            assert not [n for n in names if n in text], fn


# ------------------------------------------------------------- the cell


@pytest.fixture(scope="module")
def rehearsal_moe():
    r = load("benchmark", "tests", "rehearsal_moe.json")
    r["settle_s"] = 4.0
    return r


def test_the_cell_is_the_benchmarks_by_entries_alone():
    bench = load("BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("smallthinker-21b-a3b", "backlog", 1)
    assert [m["name"] for m in run.cell_metrics(bench, cell, "end_to_end")] \
        == ["spans_per_s", "setup_s"]
    mine = {m["name"] for m in run.cell_metrics(bench, cell, "per_layer")}
    beside = {m["name"] for m in run.cell_metrics(
        bench, bench["workloads"][0], "per_layer")}
    assert mine == beside | {"step_norm_ms.backlog", "step_route_ms.backlog",
                             "experts_roofline.backlog"}
    for name in ("step_route_ms.backlog", "experts_roofline.backlog"):
        m = next(m for m in bench["per_layer"] if m["name"] == name)
        # a later routed cell appends its name; the accepted one stays
        assert m["workloads"][0] == CELL and m["moves"] == "spans_per_s"
        assert m["layer"] == "model step"


@pytest.mark.parametrize("seed", [21, 2**31 + 22])
def test_the_cell_rehearsed_is_correct_and_its_control_is_not(
        seed, rehearsal_moe, stood_in_trace):  # noqa: F811
    """The cell through ``run.run_cell`` at the rehearsal's size, traced:
    the program (bfloat16 over bfloat16 parameters) reads ``correct``
    against the plain reference, the reference in float8 put in its place
    does not, by ``gap_rms``; the line carries the cell's metrics and the
    program's own count of assignments moved by spans x 4 x 8."""
    from odigos_tpu.models.transformer import EXPERT_ASSIGNMENTS_METRIC
    from odigos_tpu.utils.telemetry import meter

    before = meter.snapshot().get(EXPERT_ASSIGNMENTS_METRIC, 0.0)
    line = run.run_cell(CELL, seed, 1.0, True, rehearse=rehearsal_moe,
                        control=True)
    assert line["correct"] is True and line["failed"] == 0
    assert line["rehearsal"] is True
    limits = rehearsal_moe["correct"]
    control = line["control"]
    assert control["correct"] is False and control["precision"] == "fp8"
    assert control["delivery_faults"] == 0
    # by gap_rms, one of the cell's limits: a sound run's widest gap (a
    # span that takes another expert than the reference's) reaches the
    # control's, so gap_max guards a span that is wrong outright
    assert control["gap_rms"] > limits["gap_rms"]
    assert control["gap_rms"] > 2 * line["compared"]["gap_rms"]["value"]
    got = set(line["metrics"])
    assert {"step_route_ms.backlog", "experts_roofline.backlog",
            "step_norm_ms.backlog", "step_mlp_ms.backlog",
            "step_mfu.backlog", "padded_share.backlog"} <= got
    assert not [m for m in got if m.endswith(".steady")]
    assert set(line["hosttrace"]["parts"]) <= {"attn", "mlp", "route",
                                               "norm", "rest"}
    moved = meter.snapshot()[EXPERT_ASSIGNMENTS_METRIC] - before
    assert moved >= line["attempted"] * 4 * 8      # the warm-up's beside
    assert moved % (4 * 8) == 0


def test_the_cell_judged_by_an_expert_fewer_is_not_correct(
        rehearsal_moe, stood_in_trace):  # noqa: F811
    """``benchmark/tests/an_expert_fewer.json`` lays a reference with one
    expert a span fewer over the cell: the same served scores, through the
    same window and judge, read ``correct`` false by ``gap_rms``."""
    fewer = load("benchmark", "tests", "an_expert_fewer.json")
    line = run.run_cell(CELL, 23, 1.0, False,
                        rehearse={**rehearsal_moe, **fewer})
    assert line["failed"] == 0 and line["correct"] is False
    read = line["compared"]
    assert read["delivery_faults"]["value"] == 0
    assert read["gap_rms"]["value"] > 2 * rehearsal_moe["correct"]["gap_rms"]
