"""The transformer scorer's second block kind (ISSUE 28): a decoder block
with sandwich RMS norms, rotary positions, causal attention within a trace
and SwiGLU, its stack run ``passes`` times over the same parameters as a
loop on the device. Held here: the program against the benchmark's plain
reference (``benchmark/architectures/looped_decoder.py``), what the
positions and the mask promise of a packed row, the loop, that the
defaults still build the parent's encoder, the partition rules on a
virtual mesh, the routes that refuse the block and those that serve it,
and what the engine says of the model on its ``tpu/score`` spans."""

from __future__ import annotations

import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import architectures, gen, judge
from odigos_tpu.features import featurize
from odigos_tpu.features.featurizer import pack_sequences
from odigos_tpu.models.layers import BLOCK_PARTS, PARTS
from odigos_tpu.models.transformer import TraceTransformer, TransformerConfig
from odigos_tpu.pdata import synthesize_traces
from odigos_tpu.serving import EngineConfig, ScoringEngine
from odigos_tpu.training import make_model_config

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 2**31 + 28
SMALL = {"d_model": 64, "n_heads": 4, "n_layers": 2, "d_ff": 128,
         "max_len": 16, "block": "decoder", "passes": 4,
         "rope_theta": 1e6, "norm_eps": 1e-6}


def looped(**over):
    model = TraceTransformer(TransformerConfig(
        dtype=jnp.float32, **{**SMALL, **over}))
    return model, model.init(jax.random.PRNGKey(SEED))


@pytest.fixture(scope="module")
def built():
    return looped()


def logit(p):
    p = np.asarray(p, np.float64)
    return np.log(p) - np.log1p(-p)


def packed_row(lengths, seed=0, L=16, rows=1):
    """One packed row (repeated ``rows`` times) holding traces of these
    lengths side by side, as ``pack_sequences`` lays them out: segments
    1, 2, ..., positions restarting with each trace."""
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


def test_the_looped_block_matches_the_plain_reference(built):
    """Seeded weights, float32, to 1e-4 in the logit, on frames whose
    traces of unequal length share rows and which cross ``block_rows``."""
    model, variables = built
    arch = architectures.load("looped_decoder")
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "backlog-shallow.json")) as f:
        traffic = json.load(f)
    pool = gen.make_pool({**traffic, "pool_frames": 2,
                          "traces_per_frame": 24}, 77)
    want = arch.scores(pool, SEED, SMALL, block_rows=8)
    for serial, (frame, ref) in enumerate(zip(pool, want), start=1):
        got, packed = program_scores(model, variables, frame, serial)
        assert packed.n_rows > 8                    # crosses a block
        shared = [len(set(row[row > 0])) for row in packed.segments]
        assert max(shared) > 1                      # traces share a row
        lengths = np.bincount(frame.trace)
        assert len(set(lengths[lengths > 0])) > 1   # of unequal length
        assert np.abs(logit(got) - logit(ref)).max() < 1e-4


def test_fewer_passes_is_another_model(built):
    """``passes`` 3 against ``passes`` 4, the same weights: apart by more
    than the rehearsal's ``gap_max``, so a build that leaves a pass out
    cannot read ``correct``."""
    model, variables = built
    with open(os.path.join(ROOT, "benchmark", "tests",
                           "rehearsal.json")) as f:
        limit = json.load(f)["correct"]["gap_max"]
    three = TraceTransformer(TransformerConfig(
        dtype=jnp.float32, **{**SMALL, "passes": 3}))
    args = packed_row([5, 7, 3], rows=4)
    a = np.asarray(model.score_packed(variables, *args))
    b = np.asarray(three.score_packed(variables, *args))
    real = args[2] > 0
    assert judge.logit_gap(a[real], b[real]).max() > limit


def test_one_pass_is_the_stack_and_the_final_norm_by_hand():
    model, variables = looped(passes=1, n_layers=1)
    cat, cont, seg, pos = packed_row([6, 4])
    got = np.asarray(model.score_packed(variables, cat, cont, seg, pos))[0]
    p = jax.tree.map(lambda a: np.asarray(a, np.float64), variables["params"])
    enc, blk = p["encoder"]["embed"], p["encoder"]["stack"]["block_0"]
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

    H, hd = SMALL["n_heads"], SMALL["d_model"] // SMALL["n_heads"]
    w = 1e6 ** (-np.arange(hd // 2) / (hd // 2))
    ang = pos[0][:, None] * w

    def rope(u):                                   # (L, H, hd)
        a, b = u[..., :hd // 2], u[..., hd // 2:]
        cos, sin = np.cos(ang)[:, None], np.sin(ang)[:, None]
        return np.concatenate([a * cos - b * sin, b * cos + a * sin], -1)

    h = rms(x)
    q = rope((h @ blk["q_proj"]["kernel"]).reshape(-1, H, hd))
    k = rope((h @ blk["k_proj"]["kernel"]).reshape(-1, H, hd))
    v = (h @ blk["v_proj"]["kernel"]).reshape(-1, H, hd)
    s = np.einsum("qhd,khd->hqk", q, k) / np.sqrt(hd)
    allowed = (seg[0][:, None] == seg[0][None]) & real[:, None] \
        & real[None] & (pos[0][:, None] >= pos[0][None])
    s = np.where(allowed[None], s, -1e30)
    a = np.exp(s - s.max(-1, keepdims=True))
    a /= a.sum(-1, keepdims=True)
    o = np.einsum("hqk,khd->qhd", a, v).reshape(len(x), -1)
    x = x + rms(o @ blk["o_proj"]["kernel"])
    h = rms(x)
    g = h @ blk["gate_proj"]["kernel"]
    h = g / (1 + np.exp(-g)) * (h @ blk["up_proj"]["kernel"])
    x = rms(x + rms(h @ blk["down_proj"]["kernel"]))     # the final norm
    want = x @ p["span_head"]["kernel"][:, 0] + p["span_head"]["bias"][0]
    assert np.abs(logit(got[real]) - want[real]).max() < 1e-4


# ------------------------------------------- positions, mask, the loop


def test_a_trace_scores_the_same_alone_and_packed_between_others(built):
    """Rotary angles come from ``positions`` and the mask from
    ``segments``: both restart with each trace of a row."""
    model, variables = built
    cat, cont, seg, pos = packed_row([5, 6, 4], seed=3)
    among = np.asarray(model.score_packed(variables, cat, cont, seg, pos))
    mid = slice(5, 11)
    alone = [np.zeros_like(a) for a in (cat, cont, seg, pos)]
    alone[0][0, :6], alone[1][0, :6] = cat[0, mid], cont[0, mid]
    alone[2][0, :6], alone[3][0, :6] = 1, np.arange(6)
    got = np.asarray(model.score_packed(variables, *alone))
    assert np.abs(logit(got[0, :6]) - logit(among[0, mid])).max() < 1e-5


def test_the_mask_is_causal_within_a_trace_and_closed_between_traces(built):
    model, variables = built
    cat, cont, seg, pos = packed_row([5, 6, 4], seed=4)
    base = np.asarray(model.score_packed(variables, cat, cont, seg, pos))
    later = cont.copy()
    later[0, 9] += 3.0                  # the fifth span of the second trace
    moved = np.asarray(model.score_packed(variables, cat, later, seg, pos))
    assert np.array_equal(moved[0, :9], base[0, :9])      # bit-equal
    assert not np.array_equal(moved[0, 9:11], base[0, 9:11])
    assert np.array_equal(moved[0, 11:15], base[0, 11:15])
    other = cat.copy()
    other[0, 11:15, 1] += 1             # every span of the third trace
    moved = np.asarray(model.score_packed(variables, other, cont, seg, pos))
    assert np.array_equal(moved[0, :11], base[0, :11])
    assert not np.array_equal(moved[0, 11:15], base[0, 11:15])


def test_unpacked_rows_take_their_place_in_the_row_as_position(built):
    """``score_spans`` (one trace a row, no ``positions``): the same as
    the packed call told that every row is one trace from 0."""
    model, variables = built
    cat, cont, seg, _ = packed_row([9], rows=2)
    mask = seg > 0
    span_p, trace_p = model.score_spans(variables, cat, cont, mask)
    pos = np.tile(np.arange(seg.shape[1], dtype=np.int32), (2, 1))
    packed = model.score_packed(variables, cat, cont, seg, pos)
    assert trace_p.shape == (2,)
    np.testing.assert_allclose(np.asarray(span_p)[mask],
                               np.asarray(packed)[mask], atol=1e-6)


def test_the_loop_is_on_the_device(built):
    """The lowered program holds the stack once: four passes lower to no
    more than 1.2 times one pass's text, and the parameters are the same
    tree whatever ``passes`` is."""
    model, variables = built
    once = TraceTransformer(TransformerConfig(
        dtype=jnp.float32, **{**SMALL, "passes": 1}))
    args = packed_row([5, 7, 3], rows=4)
    four = model.score_packed.lower(variables, *args).as_text()
    one = once.score_packed.lower(variables, *args).as_text()
    assert len(four) <= 1.2 * len(one)
    assert "stablehlo.while" in four
    assert jax.tree.structure(variables) == jax.tree.structure(
        once.init(jax.random.PRNGKey(SEED)))
    assert model.cfg.layer_applications == 8
    assert once.cfg.layer_applications == 2


# ------------------------------------------------- the parent's encoder

# taken on the parent commit (7b69af9) before models/ was edited: sha256 of
# the sorted "path:shape:dtype" lines of eval_shape(model.init), and scores
# of a fixed packed input (tests' ``golden_inputs``) at PRNGKey(3)
GOLDEN = {
    "vit_h14_tree": ("d99e7094b0bd266b0b76ca5ff169a49de5ee7baaaa492df8331dfb8"
                     "f9d23d27f", 525),
    "default_tree": ("d8a07ce56900d652a9649e6cac769376d6d0fbef2afdf488132d140"
                     "513ab3bd2", 77),
    "default_scores": [
        0.5285696983337402, 0.5285696983337402, 0.38736963272094727,
        0.3281213045120239, 0.8676005005836487, 0.407703697681427,
        0.7705786228179932, 0.27425214648246765, 0.37924715876579285,
        0.37924715876579285, 0.38475874066352844, 0.8639958500862122,
        0.4475603699684143, 0.2801823914051056, 0.2357923984527588,
        0.5866186618804932, 0.7424206733703613, 0.7424206733703613,
        0.8382241129875183, 0.6934788823127747, 0.4528551995754242,
        0.19498924911022186, 0.4363514482975006, 0.3581776022911072,
        0.44183170795440674, 0.44183170795440674, 0.14005038142204285,
        0.4578515589237213, 0.23633383214473724, 0.6020974516868591,
        0.7300989627838135, 0.6382959485054016],
    "tiny_f32_scores": [
        0.3194810450077057, 0.5951265692710876, 0.47610822319984436,
        0.28918033838272095, 0.4991625249385834, 0.6154670715332031,
        0.048008646816015244, 0.292432576417923, 0.39159247279167175,
        0.16925962269306183, 0.45995450019836426, 0.2401496022939682,
        0.11142515391111374, 0.11142515391111374, 0.23077234625816345,
        0.09197112172842026, 0.6571193337440491, 0.3312980532646179,
        0.15904203057289124, 0.5783637762069702, 0.39326465129852295,
        0.5041370987892151, 0.4103904962539673, 0.6946181654930115],
}


def tree_digest(model):
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    lines = sorted(f"{jax.tree_util.keystr(p)}:{tuple(leaf.shape)}:"
                   f"{leaf.dtype}" for p, leaf
                   in jax.tree_util.tree_leaves_with_path(shapes))
    return hashlib.sha256("\n".join(lines).encode()).hexdigest(), len(lines)


def golden_inputs(L, R=4):
    rng = np.random.default_rng(7)
    seg = np.sort(rng.integers(0, 4, (R, L)), axis=1).astype(np.int32)
    pos = np.zeros((R, L), np.int32)
    for r in range(R):
        for s in np.unique(seg[r]):
            idx = np.flatnonzero(seg[r] == s)
            pos[r, idx] = np.arange(len(idx))
    return (jnp.asarray(rng.integers(0, 4, (R, L, 5)), jnp.int32),
            jnp.asarray(rng.normal(size=(R, L, 3)), jnp.float32),
            jnp.asarray(seg), jnp.asarray(pos))


@pytest.mark.parametrize("name,fields", [
    ("default_tree", {}),
    ("vit_h14_tree", None)])
def test_the_defaults_build_the_parents_parameter_tree(name, fields):
    if fields is None:
        with open(os.path.join(ROOT, "benchmark", "configs",
                               "vit-h14.json")) as f:
            fields = json.load(f)["tpuanomaly"]["model_config"]
    model = TraceTransformer(make_model_config("transformer", fields))
    assert model.cfg.block == "encoder" and model.cfg.passes == 1
    assert tree_digest(model) == GOLDEN[name]


@pytest.mark.parametrize("name,fields,L,step,atol", [
    ("default_scores", {}, 64, 9, 4e-3),
    ("tiny_f32_scores", dict(d_model=64, n_heads=2, n_layers=2, d_ff=128,
                             max_len=16, dtype="float32"), 16, 3, 1e-6)])
def test_the_defaults_score_as_the_parent_did(name, fields, L, step, atol):
    """The encoder's program did not move: a fixed seed and input score
    what the parent scored (a bfloat16 rounding apart at most, where the
    model is bfloat16; a changed block would be tenths apart)."""
    model = TraceTransformer(make_model_config("transformer", fields))
    variables = model.init(jax.random.PRNGKey(3))
    got = np.asarray(model.score_packed(variables, *golden_inputs(L)))
    np.testing.assert_allclose(got[:, ::step].ravel(), GOLDEN[name],
                               rtol=0, atol=atol)


def test_make_model_config_takes_the_blocks_keys_and_rejects_a_typo():
    cfg = make_model_config("transformer", dict(
        SMALL, dtype="bfloat16"))
    assert (cfg.block, cfg.passes, cfg.rope_theta, cfg.norm_eps) \
        == ("decoder", 4, 1e6, 1e-6)
    assert cfg.dtype == jnp.bfloat16 and cfg.layer_applications == 8
    with pytest.raises(TypeError):
        make_model_config("transformer", {**SMALL, "pases": 4})
    with pytest.raises(ValueError, match="block kind"):
        make_model_config("transformer", {**SMALL, "block": "decodr"})
    with pytest.raises(ValueError, match="passes"):
        make_model_config("transformer", {"passes": 2})   # the encoder's
    with pytest.raises(ValueError, match="passes"):
        make_model_config("transformer", {**SMALL, "passes": 0})
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "ouro-2.6b.json")) as f:
        stanza = json.load(f)["tpuanomaly"]
    cfg = make_model_config(stanza["model"], stanza["model_config"])
    assert (cfg.block, cfg.layer_applications) == ("decoder", 192)


def test_each_block_kind_states_its_scopes():
    assert BLOCK_PARTS["encoder"] is PARTS
    assert PARTS == ("embed", "attn_mask", "attn", "mlp", "final_norm",
                     "head")
    assert "norm" in BLOCK_PARTS["decoder"]
    arch = architectures.load("looped_decoder")
    assert set(arch.PARTS) == set(BLOCK_PARTS["decoder"])


# ------------------------------------------------------------- the mesh


def test_partition_rules_cover_the_seven_kernels():
    from jax.sharding import PartitionSpec as P

    from odigos_tpu.parallel.sharding import match_partition_rules

    _, variables = looped()
    specs = match_partition_rules(variables)["params"]["encoder"]["stack"]
    blk = specs["block_1"]
    for name in ("q_proj", "k_proj", "v_proj", "gate_proj", "up_proj"):
        assert blk[name]["kernel"] == P(None, "model"), name
    for name in ("o_proj", "down_proj"):
        assert blk[name]["kernel"] == P("model", None), name
    for name in ("attn_norm", "attn_out_norm", "mlp_norm", "mlp_out_norm"):
        assert blk[name]["scale"] == P(), name
    assert specs["final_rms"]["scale"] == P()


def test_the_looped_block_scores_the_same_on_a_data_by_model_mesh():
    """Four virtual CPU devices, ``mesh {data: 2, model: 2}``: the engine's
    packed scores equal the single-device ones (tight, not bitwise: a
    ``model`` axis reassociates the contractions)."""
    mc = make_model_config("transformer", dict(SMALL, dtype="float32"))
    batch = synthesize_traces(60, seed=42)
    feats = featurize(batch)
    base = dict(model="transformer", trace_bucket=8, max_len=16,
                model_config=mc, seed=5)
    one = ScoringEngine(EngineConfig(**base)).backend
    four = ScoringEngine(EngineConfig(
        mesh={"data": 2, "model": 2}, **base)).backend
    placed = four._plan.place_variables(four.variables)
    q = placed["params"]["encoder"]["stack"]["block_0"]["q_proj"]["kernel"]
    assert q.sharding.spec == jax.sharding.PartitionSpec(None, "model")
    assert len(q.sharding.device_set) == 4
    s1, s4 = one.score(batch, feats), four.score(batch, feats)
    assert s1.shape == s4.shape == (len(batch),)
    np.testing.assert_allclose(s4, s1, atol=1e-5, rtol=1e-4)


# ------------------------------------------------------------ the routes


def engine_config(**kw):
    mc = make_model_config("transformer", dict(SMALL, dtype="float32"))
    return EngineConfig(**{**dict(model="transformer", model_config=mc,
                                  max_len=16, trace_bucket=8,
                                  bucket_ladder=2), **kw})


def test_quantized_refuses_the_block_at_engine_construction():
    with pytest.raises(ValueError, match="block 'decoder'"):
        ScoringEngine(engine_config(quantized=True))


def test_the_fused_route_serves_the_block():
    """The fused kernel inlines ``score_packed`` whatever the block is:
    same scores as the host route."""
    from odigos_tpu.serving.fused import (PARITY_F32, extract_columns,
                                          routes_agree)

    eng = ScoringEngine(engine_config())
    backend = eng.backend
    assert backend.supports_fused
    b = synthesize_traces(40, seed=3)
    want = backend.score(b, featurize(b, eng.cfg.featurizer))
    cols, reason = extract_columns(b, eng.cfg.featurizer)
    assert reason is None
    got = backend.harvest(backend.dispatch_columns([cols]))
    np.testing.assert_allclose(got, want, rtol=PARITY_F32[0],
                               atol=PARITY_F32[1])
    assert routes_agree(got, want, "float32")


def test_sampled_device_attribution_serves_the_block():
    from odigos_tpu.serving.fused import extract_columns

    eng = ScoringEngine(engine_config(device_attribution=True,
                                      device_attribution_stride=1))
    backend = eng.backend
    b = synthesize_traces(40, seed=3)
    want = backend.score(b, featurize(b, eng.cfg.featurizer))
    cols, _ = extract_columns(b, eng.cfg.featurizer)
    for _ in range(3):                  # the first sampled tick warms
        got = backend.harvest(backend.dispatch_columns([cols]))
    assert backend._attrib.sampled >= 1
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=1e-6)


# --------------------------------------------------------------- tracing


def test_score_spans_and_the_counter_say_how_many_layers_ran():
    from odigos_tpu.selftelemetry.tracer import tracer
    from odigos_tpu.serving.engine import LAYER_APPLICATIONS_METRIC
    from odigos_tpu.utils.telemetry import meter

    eng = ScoringEngine(engine_config()).start()
    try:
        tracer.ring.drain()
        before = meter.snapshot().get(LAYER_APPLICATIONS_METRIC, 0.0)
        for seed in (1, 2, 3):
            b = synthesize_traces(12, seed=seed)
            assert len(eng.score_sync(b, timeout_s=60.0)) == len(b)
        spans = [s for s in tracer.ring.snapshot() if s.name == "tpu/score"]
        assert len(spans) == 3
        for sp in spans:
            assert sp.attrs["model.block"] == "decoder"
            assert sp.attrs["model.passes"] == 4
            assert sp.attrs["model.layer_applications"] == 8
        moved = meter.snapshot()[LAYER_APPLICATIONS_METRIC] - before
        assert moved == 3 * 8
    finally:
        eng.shutdown()
