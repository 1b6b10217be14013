"""The transformer scorer's fifth block kind: a post-norm routed decoder
block (an RMS norm on each sublayer's output and none ahead of it, grouped
query heads whose queries and keys are normed head by head, rotary and a
window layer by layer, a sigmoid router with a selection bias, a shared
expert and a dense leading layer), on a device that holds a share of each
routed layer's experts (expert parallelism: the router chooses over all
of them, the device computes what its own experts give). Held here: the
program against the benchmark's plain reference
(``benchmark/architectures/postnorm_moe_decoder.py``), the shares of a
layer against the uncut layer, the window and the layers without rotary,
the held share in the route's two ways back, the two other routed blocks
scoring as they did before the share, the size of the published cut, the
configuration against the catalog, the routes that refuse the block and
those that serve it, what the engine says of each call on its
``tpu/score`` spans, and the cell's own rehearsal. The compiles for a
described chip are in ``tests/test_moe_decoder.py``, the one file that
describes one."""

from __future__ import annotations

import json
import os
import re
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import gen, reference, run
from benchmark.tests.conftest import arch_case, stood_in_trace  # noqa: F401
from odigos_tpu.features import featurize
from odigos_tpu.features.featurizer import pack_sequences
from odigos_tpu.models import layers, route_kernels
from odigos_tpu.models.layers import BLOCK_PARTS
from odigos_tpu.models.transformer import TraceTransformer
from odigos_tpu.pdata import synthesize_traces
from odigos_tpu.serving import EngineConfig, ScoringEngine
from odigos_tpu.training import make_model_config
from tests.test_moe_decoder import (D, F, load, logit, packed_row,
                                    sorted_rows)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 2**31 + 44
ARCH, CASE = arch_case("postnorm_moe_decoder")
SMALL = CASE.SMALL
CELL = "k-exaone-236b-a23b.backlog"
ROUTED = SMALL["n_layers"] - SMALL["dense_layers"]


def postnorm(**over):
    model = TraceTransformer(make_model_config(
        "transformer", {**SMALL, "dtype": "float32", **over}))
    return model, model.init(jax.random.PRNGKey(SEED))


@pytest.fixture(scope="module")
def built():
    return postnorm()


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


def test_the_block_matches_the_plain_reference(built):
    """Seeded weights, float32 over the bfloat16 parameters, to 1e-4 in
    the logit (float32 products in another order: the held assignments
    sorted and grouped against each held expert for every span), on
    frames whose traces of unequal length share rows, cross ``block_rows``
    and run past the window of 5, through window layers and a global one
    alike."""
    model, variables = built
    pool = gen.make_pool({**load("benchmark", "traffic", "backlog.json"),
                          "pool_frames": 2, "traces_per_frame": 24}, 77)
    want = ARCH.scores(pool, SEED, SMALL, block_rows=8)
    for serial, (frame, ref) in enumerate(zip(pool, want), start=1):
        got, packed = program_scores(model, variables, frame, serial)
        assert packed.n_rows > 8                    # crosses a block
        shared = [len(set(row[row > 0])) for row in packed.segments]
        assert max(shared) > 1                      # traces share a row
        assert packed.positions.max() >= SMALL["window"]   # the window cuts
        assert np.abs(logit(got) - logit(ref)).max() < 1e-4


def test_the_weights_are_the_references_bit_for_bit(built):
    _, variables = built
    pairs = list(CASE.weight_pairs(ARCH, reference, variables["params"],
                                   SEED))
    assert len(pairs) == 6 + 7 * SMALL["dense_layers"] + 12 * ROUTED
    for ours, theirs in pairs:
        np.testing.assert_array_equal(np.asarray(ours), np.asarray(theirs))
    blk = variables["params"]["encoder"]["block_1"]
    # the held experts alone, the router over all of them
    assert blk["experts_gate"]["kernel"].shape == (
        SMALL["experts_held"], SMALL["d_model"], SMALL["d_expert"])
    assert blk["router"]["kernel"].shape == (SMALL["d_model"],
                                             SMALL["n_experts"])
    assert blk["q_norm"]["scale"].shape == (SMALL["head_dim"],)
    assert np.asarray(blk["router_bias"], np.float32).any()
    assert ARCH.BIAS_SCALE == layers.SELECTION_BIAS_SCALE


@pytest.mark.parametrize("run_seed", [5, 2**31 + 45])
def test_a_weight_seed_fixes_every_weight_whatever_the_runs_seed(run_seed):
    """``weight_seed`` draws the program's every weight from that key
    whatever key init is handed (the engine hands it the run's seed), the
    same numbers a model without it draws from that key; the reference
    follows it too, so that program and reference still agree span for
    span when the run's seed is another."""
    fixed, drawn = postnorm(weight_seed=SEED)
    _, variables = built_from(run_seed, weight_seed=SEED)
    _, plain = built_from(SEED)
    for a, b, c in zip(jax.tree_util.tree_leaves(variables),
                       jax.tree_util.tree_leaves(drawn),
                       jax.tree_util.tree_leaves(plain)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))
    pool = gen.make_pool({**load("benchmark", "traffic", "backlog.json"),
                          "pool_frames": 1, "traces_per_frame": 12},
                         run_seed)
    model = {**SMALL, "weight_seed": SEED}
    want = ARCH.scores(pool, run_seed, model, block_rows=8)
    assert all(np.array_equal(a, b) for a, b in zip(
        want, ARCH.scores(pool, SEED, SMALL, block_rows=8)))
    got, _ = program_scores(fixed, variables, pool[0], 1)
    assert np.abs(logit(got) - logit(want[0])).max() < 1e-4


def built_from(seed, **over):
    """The program's model at ``SMALL`` (float32 activations) and the
    variables its init makes from ``PRNGKey(seed)``."""
    model = TraceTransformer(make_model_config(
        "transformer", {**SMALL, "dtype": "float32", **over}))
    return model, model.init(jax.random.PRNGKey(seed))


def shares_of(w, n_held):
    """The reference's weights of a routed layer cut into its shares:
    (first expert, weights holding that share's experts alone)."""
    for first in range(0, w["experts_gate"].shape[0], n_held):
        yield first, {**w, **{nm: w[nm][first:first + n_held] for nm in (
            "experts_gate", "experts_up", "experts_down")}}


def test_the_shares_of_a_layer_add_up_to_the_uncut_layer():
    """Sixteen experts over four shares of four: each share's routed part,
    as the program's dispatch computes it (the router's choice and
    weights over all sixteen, the held range from ``first``), summed over
    the shares with the shared expert counted once, equals the uncut
    reference's whole feed-forward before its norm, within float32
    rounding; and so do the reference's own shares. Every span's eight
    rows of choices fall in some share, none in two."""
    uncut = {**SMALL, "experts_held": 16, "first_expert_held": 0}
    w = ARCH.block_weights(ARCH.layer_keys(SEED, 1, True), uncut, True)
    rng = np.random.default_rng(5)
    h = jnp.asarray(rng.normal(size=(40, SMALL["d_model"])), jnp.float32)
    real = jnp.asarray([i % 9 != 4 for i in range(40)])
    whole = np.asarray(ARCH.feed_forward(h, w, uncut, routed=True))
    whole = whole * np.asarray(real)[:, None]
    mm = jnp.matmul
    hi = jax.lax.Precision.HIGHEST
    shared = mm(jax.nn.silu(mm(h, w["shared_gate"], precision=hi))
                * mm(h, w["shared_up"], precision=hi), w["shared_down"],
                precision=hi)
    which, weight = layers.biased_sigmoid(
        mm(h, w["router"], precision=hi), w["router_bias"],
        SMALL["experts_per_span"], SMALL["route_scale"])
    program, ref, held = 0, 0, 0
    for first, part in shares_of(w, 4):
        out, load_, _ = layers.routed_experts(
            h, which, weight, real, part["experts_gate"], part["experts_up"],
            part["experts_down"], jax.nn.silu, first)
        program, held = program + np.asarray(out), held + np.asarray(load_)
        ref = ref + np.asarray(ARCH.feed_forward(
            h, part, {**uncut, "experts_held": 4, "first_expert_held": first},
            routed=True, shared=False))
    program = program + np.asarray(shared) * np.asarray(real)[:, None]
    ref = (ref + np.asarray(shared)) * np.asarray(real)[:, None]
    assert held.sum() == int(real.sum()) * SMALL["experts_per_span"]
    np.testing.assert_allclose(program, whole, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(ref, whole, rtol=1e-5, atol=1e-5)
    # and one share alone is a part of it, not the whole
    first, part = next(shares_of(w, 4))
    alone = np.asarray(layers.routed_experts(
        h, which, weight, real, part["experts_gate"], part["experts_up"],
        part["experts_down"], jax.nn.silu, first)[0])
    assert np.abs(alone + np.asarray(shared) - whole).max() > 1e-2


def test_a_share_of_every_expert_is_the_whole_dispatch():
    """A held range of all the experts from 0 routes as the whole
    dispatch does: the same loads and rows moved, the output to float32
    rounding (the choice-by-choice mask fuses otherwise than the span's)."""
    rng = np.random.default_rng(3)
    h = jnp.asarray(rng.normal(size=(24, 32)), jnp.float32)
    logits = jnp.asarray(rng.normal(size=(24, 8)), jnp.float32)
    gate, up = (jnp.asarray(rng.normal(size=(8, 32, 16)) / 6, jnp.float32)
                for _ in range(2))
    down = jnp.asarray(rng.normal(size=(8, 16, 32)) / 4, jnp.float32)
    which, weight = layers.biased_sigmoid(logits, logits[0] * 0, 3, 2.5)
    real = jnp.asarray([i % 5 != 1 for i in range(24)])
    a = layers.routed_experts(h, which, weight, real, gate, up, down,
                              jax.nn.silu)
    b = layers.routed_experts(h, which, weight, real, gate, up, down,
                              jax.nn.silu, 0)
    np.testing.assert_allclose(np.asarray(a[0]), np.asarray(b[0]),
                               rtol=1e-6, atol=1e-6)
    for x, y in zip(a[1:], b[1:]):
        assert np.array_equal(np.asarray(x), np.asarray(y))


# the counted scores of each other routed block at its ``SMALL`` on one
# packed row, float32 and bfloat16, digested: what the two blocks scored
# before a device could hold a share of a layer's experts
BEFORE = {("moe_decoder", "float32"): "34c4ec1d20010147",
          ("moe_decoder", "bfloat16"): "436648e525e676ef",
          ("latent_moe_decoder", "float32"): "e5b35ff5518112e8",
          ("latent_moe_decoder", "bfloat16"): "925fff9aa5e3078d"}


@pytest.mark.parametrize("arch, dtype", sorted(BEFORE))
def test_the_other_routed_blocks_score_as_before_the_share(arch, dtype):
    import hashlib

    model = TraceTransformer(make_model_config(
        "transformer", {**arch_case(arch)[1].SMALL, "dtype": dtype}))
    variables = model.init(jax.random.PRNGKey(SEED))
    scores, counts = model.score_packed_counted(
        variables, *packed_row([7, 5, 3], seed=4, rows=3))
    digest = hashlib.sha256(np.asarray(scores).tobytes()).hexdigest()[:16]
    assert digest == BEFORE[arch, dtype]
    assert "moe.choices" not in counts and "moe.held_share" not in counts


def test_the_window_cuts_a_trace_longer_than_it_and_no_shorter_one(built):
    """``window`` 5 against a window past the row, the same weights: a
    trace of 12 spans scores differently from its sixth span on, a row of
    traces of at most 5 spans bit for bit the same."""
    model, variables = built
    wide, _ = postnorm(window=64)
    long_row, short_rows = packed_row([12, 3]), packed_row([5, 4, 5])
    a = np.asarray(model.score_packed(variables, *long_row))[0]
    b = np.asarray(wide.score_packed(variables, *long_row))[0]
    assert np.array_equal(a[:5], b[:5])            # five spans back: seen
    assert not np.array_equal(a[5:12], b[5:12])
    assert np.array_equal(a[12:15], b[12:15])      # the short neighbour
    assert np.array_equal(
        np.asarray(model.score_packed(variables, *short_rows)),
        np.asarray(wide.score_packed(variables, *short_rows)))


def test_a_global_layer_has_no_positions(monkeypatch):
    """``rope_layout`` 1, 0: the window layer rotates q and k (64 query
    and key heads' worth: 4 and 2 here), the global layer neither; every
    head's query and key is normed first, in both. A stack that rotates
    nowhere knows its spans' positions only through their order, so
    spreading them apart changes no score; the window layer's rotary
    does."""
    seen, normed = [], []
    rotate, rms = layers.rotate, layers.nn.RMSNorm.__call__

    def spy(x, cos, sin):
        seen.append(x.shape[-2])
        return rotate(x, cos, sin)

    def spy_rms(self, x, *a, **kw):
        if self.name in ("q_norm", "k_norm"):
            normed.append((self.name, x.shape[-1]))
        return rms(self, x, *a, **kw)

    monkeypatch.setattr(layers, "rotate", spy)
    monkeypatch.setattr(layers.nn.RMSNorm, "__call__", spy_rms)
    two = dict(n_layers=2, dense_layers=1, window_layout=[0, 0])
    model, variables = postnorm(rope_layout=[1, 0], **two)
    bare, _ = postnorm(rope_layout=[0, 0], **two)
    args = packed_row([7, 6])
    seen.clear()
    normed.clear()                      # ``init`` ran each forward once
    with jax.disable_jit():
        model._score_packed_impl(variables, *args)
    assert seen == [SMALL["n_heads"], SMALL["n_kv_heads"]]
    assert normed == [("q_norm", SMALL["head_dim"]),
                      ("k_norm", SMALL["head_dim"])] * 2
    with jax.disable_jit():
        base = np.asarray(bare._score_packed_impl(variables, *args))
        spread = (args[0], args[1], args[2], args[3] * 3)
        moved = np.asarray(bare._score_packed_impl(variables, *spread))
        rotated = np.asarray(model._score_packed_impl(variables, *spread))
        kept = np.asarray(model._score_packed_impl(variables, *args))
    assert np.array_equal(base, moved)
    assert not np.allclose(rotated, kept, atol=1e-6)


def test_the_norms_sit_after_each_sublayer(built, monkeypatch):
    """No norm reads the block's input: the first RMS norm of a block
    after the q and k norms is the attention sublayer's output's (8192
    columns at published widths, d_model here), and the block adds its
    normed sublayers to a stream nobody normed: a block whose sublayers
    return zero hands its input on unchanged."""
    model, variables = built
    names = []
    rms = layers.nn.RMSNorm.__call__

    def spy(self, x, *a, **kw):
        names.append(self.name)
        return rms(self, x, *a, **kw)

    monkeypatch.setattr(layers.nn.RMSNorm, "__call__", spy)
    with jax.disable_jit():
        model._score_packed_impl(variables, *packed_row([6, 5]))
    per_block = ["q_norm", "k_norm", "attn_out_norm", "mlp_out_norm"]
    assert names == per_block * SMALL["n_layers"] + ["final_rms"]


# ------------------------------------------------- the way back, held


def held_routing(spans, k, n_experts, first, n_held, real, seed=0):
    """(which, weight, real, order, load) as ``routed_experts`` makes them
    under a held share: ``real`` (spans, k), a choice of an expert in
    ``[first, first + n_held)`` of a real span."""
    rng = np.random.default_rng(seed)
    which = np.stack([rng.choice(n_experts, k, replace=False)
                      for _ in range(spans)]) - first
    mine = jnp.asarray(real)[:, None] & jnp.asarray(
        (which >= 0) & (which < n_held))
    weight = jax.nn.softmax(jnp.asarray(rng.normal(size=(spans, k)),
                                        jnp.float32)) * mine
    expert = jnp.where(mine, jnp.asarray(which), n_held).reshape(-1)
    order = jnp.argsort(expert, stable=True)
    load_ = jnp.sum(expert[:, None] == jnp.arange(n_held), axis=0,
                    dtype=jnp.int32)
    return weight, mine, order, load_


@pytest.mark.parametrize("spans, first", [(200, 0), (72, 12), (256, 28)])
def test_the_way_back_by_dma_takes_a_held_share(spans, first):
    """The kernels' way back under a held share (4 of 32 experts, k 8):
    only the held choices' rows are moved, every other choice comes back
    zero whatever its word rows hold (they are never written), and the
    result equals XLA's gather with the choice-by-choice mask: to float32
    rounding, bit for bit in bfloat16 on rows and weights whose sums are
    exact, with NaN planted past the last held assignment."""
    k = 8
    real = [i % 7 != 3 for i in range(spans)]
    weight, mine, order, load_ = held_routing(spans, k, 32, first, 4, real)
    n_real = jnp.sum(mine, dtype=jnp.int32)
    assert int(n_real) == int(load_.sum()) < int(np.sum(real)) * k
    y = sorted_rows(spans, k)
    np.testing.assert_allclose(
        route_kernels.combine(y, order, n_real, weight, mine, jnp.float32,
                              interpret=True),
        held_xla_back(y, order, weight, mine, jnp.float32),
        rtol=2e-6, atol=1e-6)
    weight = jnp.round(weight * 64) / 64
    combine = partial(route_kernels.combine, order=order, n_real=n_real,
                      weight=weight, real=mine, dtype=jnp.bfloat16,
                      interpret=True)
    exact = sorted_rows(spans, k, exact=True)
    got = combine(exact)
    assert np.array_equal(got, held_xla_back(exact, order, weight, mine,
                                             jnp.bfloat16))
    assert np.asarray(got, np.float32).any()
    planted = sorted_rows(spans, k, nan_from=int(n_real), exact=True)
    assert np.array_equal(combine(planted), got)
    assert not np.isnan(np.asarray(got, np.float32)).any()


def held_order(spans, k, case, seed=0):
    """(order, n_held): a sorted order whose first ``n_held`` rows are the
    held choices. ``case``: "share", ``held_routing``'s share of 4 of 32
    experts; "one", one held expert that every span chose, as its third
    choice (a span's row each, in span order); "none", "every" or a
    count, that many rows of a shuffled order."""
    if case == "share":
        _, mine, order, _ = held_routing(spans, k, 32, 8, 4,
                                         [True] * spans, seed)
        return order, int(jnp.sum(mine))
    if case == "one":
        expert = jnp.tile(jnp.asarray([1, 1, 0] + [1] * (k - 3)), spans)
        return jnp.argsort(expert, stable=True), spans
    n_held = {"none": 0, "every": spans * k}.get(case, case)
    return jnp.asarray(np.random.default_rng(seed).permutation(spans * k)), \
        n_held


@pytest.mark.parametrize("spans, d, case", [
    (200, 512, "none"), (64, 512, "every"),
    (200, 512, 603),                # neither 16 nor a tile divides it
    (256, 512, "share"), (72, 512, "one"),
    (64, 6144, "share"), (40, 6144, "one")])
def test_the_way_in_by_dma_moves_the_held_rows_alone(spans, d, case):
    """The kernel's way in (``route_kernels.into_expert_order``, interpret
    mode): the rows up to the last held choice are XLA's gather's,
    ``h[order // k]``, bit for bit; the tiles past it (of 512 rows, the
    grouped products' tile) are not written at all: the interpreter
    starts the output as NaN, and NaN is what they hold."""
    k = 8
    order, n_held = held_order(spans, k, case)
    rows = -(-spans * k // layers.GROUP_ROWS) * layers.GROUP_ROWS
    rng = np.random.default_rng(spans + d)
    h = jnp.asarray(rng.normal(size=(spans, d)), jnp.bfloat16)
    got = route_kernels.into_expert_order(h, order, k, jnp.int32(n_held),
                                          rows, interpret=True)
    assert got.shape == (rows, d) and got.dtype == jnp.bfloat16
    want = layers._into_expert_order(h, order, k)

    def bits(a):
        return np.asarray(jax.lax.bitcast_convert_type(a, jnp.uint16))

    assert np.array_equal(bits(got[:n_held]), bits(want[:n_held]))
    unwritten = -(-n_held // layers.GROUP_ROWS) * layers.GROUP_ROWS
    assert np.isnan(np.asarray(got[unwritten:], np.float32)).all()


def held_xla_back(y, order, weight, mine, dtype):
    """XLA's gather back and weighted sum under a held share, as
    ``_back_by_gather`` has them: a choice not held is taken out whole."""
    spans, k = weight.shape
    g = y[jnp.argsort(order).reshape(spans, k).T]
    g = jnp.where(mine.T[:, :, None], g, 0).astype(jnp.float32)
    return jnp.sum(g * weight.T[:, :, None], axis=0).astype(dtype)


def test_the_tpus_branch_takes_a_held_share():
    """The TPU's branch of ``routed_experts`` under a held share (the DMA
    way in, the ``gmm`` products over the held assignments alone, the DMA
    way back), in interpret mode, against the same products between XLA's
    two gathers; and the rows each moved: a held assignment's in and
    back, where XLA's gathers move every slot's k rows each way. The way
    in leaves NaN (the interpreter's unwritten memory) in the tiles past
    the last held row, and none of it reaches the output."""
    spans, k = 200, 8
    real = [i % 7 != 2 for i in range(spans)]
    weight, mine, order, load_ = held_routing(spans, k, 32, 8, 4, real, 1)
    rng = np.random.default_rng(2)
    h = jnp.asarray(rng.normal(size=(spans, D)), jnp.bfloat16)
    gate, up = (jnp.asarray(rng.normal(size=(4, D, F)) / 16, jnp.bfloat16)
                for _ in range(2))
    down = jnp.asarray(rng.normal(size=(4, F, D)) / 11, jnp.bfloat16)
    into = route_kernels.into_expert_order(h, order, k, load_.sum(),
                                           2048, interpret=True)
    assert int(load_.sum()) < 512       # the first tile of four written
    assert np.isnan(np.asarray(into[512:], np.float32)).all()
    args = (h, order, weight, mine, load_, gate, up, down, jax.nn.silu)
    got, moved = layers._back_by_dma(*args, interpret=True)
    want, all_moved = layers._back_by_gather(
        *args, partial(layers._experts_gmm, interpret=True))
    assert not np.isnan(np.asarray(got, np.float32)).any()
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=2**-7,
                               atol=2**-12)
    assert int(moved) == 2 * int(load_.sum())
    assert int(all_moved) == 2 * spans * k


def stripped_program(arch: str, counted: bool) -> str:
    """The model at ``arch``'s ``SMALL`` (d 256, bfloat16: the width the
    route's kernels take) lowered for the TPU, on eight packed rows, each
    Pallas kernel's body printed without locations and digested: a
    kernel's serialized body carries its caller's file and line, which an
    edit above it moves."""
    import base64
    import hashlib

    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir

    small = {**arch_case(arch)[1].SMALL, "d_model": D, "dtype": "bfloat16"}
    model = TraceTransformer(make_model_config("transformer", small))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    S, L = jax.ShapeDtypeStruct, small["max_len"]
    score = model.score_packed_counted if counted else model.score_packed
    text = jax.jit(score).trace(
        shapes, S((8, L, 5), jnp.int32), S((8, L, 3), jnp.float32),
        S((8, L), jnp.int32), S((8, L), jnp.int32)).lower(
            lowering_platforms=("tpu",)).as_text()

    def body(m):
        ctx = mlir.make_ir_context()
        ctx.allow_unregistered_dialects = True
        with ctx:
            asm = ir.Module.parse(base64.b64decode(m.group(1))).operation \
                .get_asm(enable_debug_info=False)
        return hashlib.sha256(asm.encode()).hexdigest()

    return re.sub(r"(?<=\\22body\\22: \\22)([A-Za-z0-9+/=]+)", body, text)


# the whole-range routed blocks' programs for the TPU (``stripped_program``,
# sha256, the first 16 digits), plain and counted: what they lowered to
# before the way in by DMA, which the held share alone takes
WHOLE_RANGE = {("moe_decoder", False): "9bdf14729b4a81ce",
               ("moe_decoder", True): "3a58f33877fa3587",
               ("latent_moe_decoder", False): "3f9a8b3499210efd",
               ("latent_moe_decoder", True): "1f5143331642c77a"}


@pytest.mark.parametrize("arch, counted", sorted(WHOLE_RANGE))
def test_the_whole_range_lowers_as_before_the_way_in_by_dma(arch, counted):
    import hashlib

    text = stripped_program(arch, counted)
    assert "into_expert_order" not in text
    assert "tpu_custom_call" in text
    digest = hashlib.sha256(text.encode()).hexdigest()[:16]
    assert digest == WHOLE_RANGE[arch, counted]


def test_a_held_share_lowers_the_way_in_by_dma():
    text = stripped_program("postnorm_moe_decoder", True)
    assert text.count('kernel_name = "into_expert_order"') == 1


# ------------------------------------------------------------- the sizes


def published():
    return load("benchmark", "configs", "k-exaone-236b-a23b.json")


def test_the_published_cut_counts_its_parameters_all_bfloat16():
    stanza = published()["tpuanomaly"]
    model = TraceTransformer(make_model_config(stanza["model"],
                                               stanza["model_config"]))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    enc = {k: v for k, v in shapes["params"]["encoder"].items()
           if k != "embed"}

    def count(tree):
        return sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(tree))

    attention = 113_246_208 + 2 * 128 + 2 * 6144
    assert count(enc["block_0"]) == 452_997_376 \
        == attention + 3 * 6144 * 18432
    assert count(enc["block_1"]) == 453_783_936 \
        == attention + 6144 * 128 + 128 + 9 * 3 * 6144 * 2048
    n = stanza["model_config"]["n_layers"]
    assert count(enc) == 452_997_376 + (n - 1) * 453_783_936 + 6144
    assert n == 8 and count(enc) - 6144 == 3_629_484_928   # + final norm
    assert {str(leaf.dtype) for leaf in jax.tree.leaves(enc)} == {"bfloat16"}
    blk = enc["block_1"]
    assert blk["q_proj"]["kernel"].shape == (6144, 64 * 128)
    assert blk["k_proj"]["kernel"].shape == (6144, 8 * 128)
    assert blk["o_proj"]["kernel"].shape == (64 * 128, 6144)
    assert blk["q_norm"]["scale"].shape == (128,)
    assert blk["router"]["kernel"].shape == (6144, 128)
    assert blk["router_bias"].shape == (128,)
    assert blk["experts_gate"]["kernel"].shape == (8, 6144, 2048)
    assert blk["shared_down"]["kernel"].shape == (2048, 6144)
    assert model.cfg.span_attrs == {
        "model.block": "postnorm_moe", "model.passes": 1,
        "model.layer_applications": 8, "model.experts": 128,
        "model.experts_per_span": 8, "model.layers_rotary": 6,
        "model.layers_window": 6, "model.shared_experts": 1,
        "model.layers_dense": 1, "model.experts_held": 8,
        "model.first_expert_held": 0}


def test_the_configuration_is_the_catalog_rows_config():
    cfg = published()
    # MODEL_CATALOG names a JSON-lines catalog of published configurations,
    # one row per model with its source_url and config; checked where given.
    catalog = os.environ.get("MODEL_CATALOG", "")
    if catalog and os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["source_url"] == cfg["source"])
        assert row["name"] == "K-EXAONE-236B-A23B"
        assert cfg["published"] == row["config"]
    pub, mc = cfg["published"], cfg["tpuanomaly"]["model_config"]
    cut = {"num_hidden_layers": 8, "num_experts": 8}
    for key, value in pub.items():                  # the top level too
        assert cfg[key] == cut.get(key, value)
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts"]
    entry = next(c for c in load("BENCHMARK.json")["configs"]
                 if c["name"] == cfg["name"])
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"]
    slide = [int(t == "sliding_attention")
             for t in pub["layer_types"][:mc["n_layers"]]]
    assert mc["rope_layout"] == mc["window_layout"] == slide
    assert [w for w in pub["sliding_windows"][:mc["n_layers"]] if w] \
        == [mc["window"]] * sum(slide)
    assert pub["mlp_layer_types"][:mc["dense_layers"] + 1] \
        == ["dense", "sparse"]
    assert (mc["d_model"], mc["n_heads"], mc["n_kv_heads"], mc["head_dim"],
            mc["d_ff"], mc["n_experts"], mc["experts_per_span"],
            mc["d_expert"], mc["shared_experts"], mc["dense_layers"],
            mc["route_scale"], mc["window"], mc["rope_theta"],
            mc["norm_eps"]) == (
        pub["hidden_size"], pub["num_attention_heads"],
        pub["num_key_value_heads"], pub["head_dim"],
        pub["intermediate_size"], pub["num_experts"],
        pub["num_experts_per_tok"], pub["moe_intermediate_size"],
        pub["num_shared_experts"], pub["first_k_dense_replace"],
        pub["routed_scaling_factor"], pub["sliding_window"],
        pub["rope_parameters"]["rope_theta"], pub["rms_norm_eps"])
    # the chip's share: 8 of the 128 experts, a sixteenth
    assert (mc["experts_held"], mc["first_expert_held"]) == (8, 0)
    assert cfg["num_experts"] == mc["experts_held"]
    # the floors: a whole period past the dense layer, four routed layers,
    # eight routed experts held
    assert mc["n_layers"] - mc["dense_layers"] >= 4
    assert mc["experts_held"] >= 8 and sum(slide) == 3 * (8 - sum(slide))
    assert (pub["n_group"], pub["topk_group"]) == (1, 1)   # no group step
    assert pub["scoring_func"] == "sigmoid" and pub["norm_topk_prob"]
    assert set(cfg["correct"]) == {"delivery_faults", "gap_max", "gap_rms"}
    by = ARCH.flops_by_part(mc, [1])
    assert (by["attn"] + by["qk"]) / sum(by.values()) == pytest.approx(
        0.55, abs=5e-3)
    assert sum(by.values()) == pytest.approx(3.30e9, rel=2e-3)
    for word in ("norm placement", "q_norm", "selection bias", "n_group",
                 "rotate-half", "multi-token-prediction", "rows of 64",
                 "final norm", "vocabulary", "random", "no bias"):
        assert any(word in line for line in cfg["assumed"]), word
    for word in ("16 chips", "experts 0-7", "8 layers"):
        assert word in cfg["reduced_how"]["deployment"], word


def test_the_config_refuses_what_does_not_compose():
    with pytest.raises(ValueError, match="do not lie within n_experts 16"):
        postnorm(experts_held=4, first_expert_held=13)
    with pytest.raises(ValueError, match="do not lie within"):
        postnorm(first_expert_held=-1)
    with pytest.raises(ValueError, match="not a multiple of n_kv_heads"):
        postnorm(n_kv_heads=3)
    with pytest.raises(ValueError, match="each state all n_layers"):
        postnorm(rope_layout=[1, 1, 0])
    with pytest.raises(ValueError, match="leaves no routed layer"):
        postnorm(dense_layers=4)
    with pytest.raises(ValueError, match="experts_per_span 17"):
        postnorm(experts_per_span=17)
    with pytest.raises(ValueError, match="route_scale 0"):
        postnorm(route_scale=0)
    # the latent block's attention keys are not this block's
    with pytest.raises(ValueError, match=r"q_rank.*\(latent_moe\)"):
        postnorm(q_rank=8)
    # the held share and the fixed draw are this block's alone: the four
    # others refuse them
    for block in ("encoder", "decoder"):
        for key in ("experts_held", "first_expert_held", "weight_seed"):
            with pytest.raises(ValueError,
                               match=key + r".*\(postnorm_moe\)"):
                make_model_config("transformer", {"block": block, key: 1})
    for arch in ("moe_decoder", "latent_moe_decoder"):
        other = arch_case(arch)[1].SMALL
        for key in ("experts_held", "first_expert_held", "weight_seed"):
            with pytest.raises(ValueError,
                               match=key + r".*\(postnorm_moe\)"):
                make_model_config("transformer", {**other, key: 2})
    cfg = make_model_config("transformer", dict(SMALL, dtype="bfloat16"))
    assert cfg.param_dtype == jnp.bfloat16 and hash(cfg) is not None
    assert cfg.routed and cfg.shares_experts
    assert cfg.held_experts == (4, 4)
    whole = make_model_config("transformer", dict(SMALL, experts_held=0,
                                                  first_expert_held=0))
    assert whole.held_experts == (0, 16) and not whole.shares_experts


def test_the_block_states_its_scopes():
    assert set(BLOCK_PARTS) == {"encoder", "decoder", "moe", "latent_moe",
                                "postnorm_moe"}
    mine = BLOCK_PARTS["postnorm_moe"]
    assert set(mine) - set(BLOCK_PARTS["latent_moe"]) == {"qk"}
    assert "latent" not in mine
    assert set(mine) == set(ARCH.PARTS)
    assert set(ARCH.PARTS.values()) == {"attn", "qk", "mlp", "dense",
                                        "route", "norm", "rest"}


def test_the_operations_carry_their_scopes(built):
    """Every product of the traced program sits under the scope that
    ``PARTS`` folds it by: q, k, v, the core and the output product under
    ``attn``; the per-head norms and the rotary under ``qk`` and in no
    other; a dense layer's three and the shared expert's three under
    ``dense``; the router's under ``route``; the held experts' grouped
    products under ``mlp``."""
    model, variables = built
    args = packed_row([6, 5])
    text = jax.jit(model._score_packed_impl).lower(
        variables, *args).as_text(debug_info=True)
    scopes = set(BLOCK_PARTS["postnorm_moe"])
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
        "q_proj": {"attn"}, "k_proj": {"attn"}, "v_proj": {"attn"},
        "o_proj": {"attn"}, "gate_proj": {"dense"}, "up_proj": {"dense"},
        "down_proj": {"dense"}, "shared_gate": {"dense"},
        "shared_up": {"dense"}, "shared_down": {"dense"},
        "router": {"route"}, "cont_proj": {"embed"},
        "span_head": {"head"}}
    assert bare == {"attn", "mlp"}
    norms = set(re.findall(r'loc\("[^"]*/((?:qk|norm|attn)/[^"/]*_norm)/',
                           text))
    assert {n.split("/")[1] for n in norms if n.startswith("qk/")} \
        == {"q_norm", "k_norm"}
    assert not [n for n in norms if n.startswith("attn/")]


# ------------------------------------------------------------ the routes


def engine_config(**kw):
    mc = make_model_config("transformer", dict(SMALL, dtype="float32"))
    return EngineConfig(**{**dict(model="transformer", model_config=mc,
                                  max_len=16, trace_bucket=8,
                                  bucket_ladder=2), **kw})


def test_quantized_refuses_the_block_at_engine_construction():
    with pytest.raises(ValueError, match="block 'postnorm_moe'"):
        ScoringEngine(engine_config(quantized=True))


def test_the_partition_rules_place_every_kernel(built):
    from jax.sharding import PartitionSpec as P
    from odigos_tpu.parallel.sharding import match_partition_rules

    _, variables = built
    specs = match_partition_rules(variables["params"])
    enc = specs["encoder"]
    dense, routed = enc["block_0"], enc["block_1"]
    cols, rows = P(None, "model"), P("model", None)
    for blk in (dense, routed):
        for name in ("q_proj", "k_proj", "v_proj"):
            assert blk[name]["kernel"] == cols      # heads side by side
        assert blk["o_proj"]["kernel"] == rows
        assert blk["q_norm"]["scale"] == blk["k_norm"]["scale"] == P()
    assert dense["gate_proj"]["kernel"] == dense["up_proj"]["kernel"] == cols
    assert dense["down_proj"]["kernel"] == rows
    assert routed["shared_gate"]["kernel"] == cols
    assert routed["shared_down"]["kernel"] == rows
    for name in ("router", "experts_gate", "experts_up", "experts_down"):
        assert routed[name]["kernel"] == P()
    assert routed["router_bias"] == P()


def test_a_model_axis_is_refused_with_the_reason():
    with pytest.raises(ValueError, match=f"model axis 2 cannot place "
                       f"{5 * ROUTED} parameters.*block_1/.*"
                       f"split rows by expert"):
        ScoringEngine(engine_config(mesh={"data": 2, "model": 2}))


def test_the_block_scores_the_same_on_a_data_mesh_of_four():
    """Four virtual CPU devices, ``mesh {data: 4}``, each routing its own
    quarter of the rows to the held experts: the engine's packed scores
    and the call's counts equal the single-device ones."""
    batch = synthesize_traces(60, seed=42)
    feats = featurize(batch)
    one = ScoringEngine(engine_config(seed=5)).backend
    four = ScoringEngine(engine_config(seed=5, mesh={"data": 4})).backend
    s1, s4 = one.score(batch, feats), four.score(batch, feats)
    np.testing.assert_allclose(s4, s1, atol=1e-5, rtol=1e-4)
    h1 = one.call_attrs(one.fetch(one.dispatch(batch, feats)))
    h4 = four.call_attrs(four.fetch(four.dispatch(batch, feats)))
    for key in ("moe.assignments", "moe.choices", "moe.experts_busy_min"):
        assert h1[key] == h4[key], key
    assert h1["moe.choices"] == len(batch) * SMALL["experts_per_span"] \
        * ROUTED


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


def test_score_spans_say_what_the_chip_holds_and_what_each_call_routed():
    """``moe.assignments`` counts what the held experts took and
    ``moe.choices`` every choice of a real span, held here or not (spans
    x 4 x 3 routed layers); ``moe.held_share`` is their ratio, near the
    held fraction 4 / 16; both counts feed their counters."""
    from odigos_tpu.models.transformer import (EXPERT_ASSIGNMENTS_METRIC,
                                               EXPERT_CHOICES_METRIC)
    from odigos_tpu.selftelemetry.tracer import tracer
    from odigos_tpu.utils.telemetry import meter

    eng = ScoringEngine(engine_config()).start()
    try:
        tracer.ring.drain()
        before = meter.snapshot()
        sizes, held = [], []
        for seed in (1, 2, 3):
            b = synthesize_traces(12, seed=seed)
            sizes.append(len(b))
            assert len(eng.score_sync(b, timeout_s=60.0)) == len(b)
        spans = [s for s in tracer.ring.snapshot() if s.name == "tpu/score"]
        assert len(spans) == 3
        per_span = SMALL["experts_per_span"] * ROUTED
        for sp, n in zip(spans, sizes):
            a = sp.attrs
            assert (a["model.block"], a["model.experts"],
                    a["model.experts_held"], a["model.first_expert_held"],
                    a["model.layers_rotary"], a["model.layers_window"],
                    a["model.layers_dense"]) == ("postnorm_moe", 16, 4, 4,
                                                 3, 3, 1)
            assert a["moe.choices"] == n * per_span
            assert 0 < a["moe.assignments"] < a["moe.choices"]
            assert a["moe.held_share"] == pytest.approx(
                a["moe.assignments"] / a["moe.choices"])
            assert 1 <= a["moe.experts_busy_min"] <= SMALL["experts_held"]
            held.append(a["moe.assignments"])
            # XLA's gathers here: every dispatched slot's choices, twice
            slots = np.prod([int(x) for x in a["device.shape"].split("x")])
            assert a["moe.rows_moved"] == 2 * slots * per_span
        assert 0.1 < sum(held) / (sum(sizes) * per_span) < 0.5
        after = meter.snapshot()
        assert after[EXPERT_CHOICES_METRIC] \
            - before.get(EXPERT_CHOICES_METRIC, 0.0) == sum(sizes) * per_span
        assert after[EXPERT_ASSIGNMENTS_METRIC] \
            - before.get(EXPERT_ASSIGNMENTS_METRIC, 0.0) == sum(held)
    finally:
        eng.shutdown()


def test_a_routing_that_leaves_the_share_idle_reads_in_the_count(built):
    """A selection bias that lifts two experts held elsewhere past every
    score sends every span's first choices there: the held share of the
    choices falls, and with every expert but those two lifted it reaches
    the held experts' whole share, none idle."""
    model, variables = built
    args = packed_row([6, 5, 4], rows=4)
    _, counts = model.score_packed_counted(variables, *args)
    base = float(counts["moe.held_share"])

    def lifted(experts):
        bias = jnp.zeros(SMALL["n_experts"]).at[jnp.array(experts)].set(2.0)
        return jax.tree_util.tree_map_with_path(
            lambda path, leaf: bias.astype(leaf.dtype)
            if "router_bias" in jax.tree_util.keystr(path) else leaf,
            variables)

    _, away = model.score_packed_counted(lifted([0, 15]), *args)
    assert float(away["moe.held_share"]) <= base
    _, home = model.score_packed_counted(lifted([4, 5, 6, 7]), *args)
    assert float(home["moe.held_share"]) == pytest.approx(
        4 / SMALL["experts_per_span"])
    assert int(home["moe.experts_busy_min"]) == SMALL["experts_held"]


def test_serving_names_no_model_or_configuration():
    names = ("exaone", "postnorm", "glm", "smallthinker", "moe")
    serving = os.path.join(ROOT, "odigos_tpu", "serving")
    for fn in sorted(os.listdir(serving)):
        if fn.endswith(".py"):
            with open(os.path.join(serving, fn)) as f:
                words = set(re.findall(r"[a-z0-9]+", f.read().lower()))
            assert not [n for n in names if n in words], fn


# ------------------------------------------------------------- the cell


@pytest.fixture(scope="module")
def rehearsal_postnorm():
    r = load("benchmark", "tests", "rehearsal_postnorm.json")
    r["settle_s"] = 4.0
    return r


def test_the_cell_is_the_benchmarks_by_entries_alone():
    bench = load("BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("k-exaone-236b-a23b", "backlog", 1)
    assert len(cell["why"]) <= 200
    assert [m["name"] for m in run.cell_metrics(bench, cell, "end_to_end")] \
        == ["spans_per_s", "setup_s"]
    mine = {m["name"] for m in run.cell_metrics(bench, cell, "per_layer")}
    sibling = next(w for w in bench["workloads"]
                   if w["name"] == "glm-4.7-flash.backlog")
    beside = {m["name"] for m in run.cell_metrics(bench, sibling,
                                                  "per_layer")}
    assert mine == beside - {"step_latent_ms.backlog",
                             "experts_roofline.backlog"} \
        | {"step_qk_ms.backlog"}
    m = next(m for m in bench["per_layer"]
             if m["name"] == "step_qk_ms.backlog")
    assert m["workloads"] == [CELL] and m["moves"] == "spans_per_s"
    assert (m["layer"], m["source"], m["unit"], m["better"]) \
        == ("model step", "device_trace", "ms", "lower")
    # additions alone: the entries stand last in their lists, and the
    # cell's name last in every list it was appended to
    assert bench["workloads"][-1] is cell
    assert bench["configs"][-1]["name"] == "k-exaone-236b-a23b"
    assert bench["per_layer"][-1] is m
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", []):
            assert m["workloads"][-1] == CELL


@pytest.mark.parametrize("seed", [21, 2**31 + 22])
def test_the_cell_rehearsed_is_correct_and_its_control_is_not(
        seed, rehearsal_postnorm, stood_in_trace):  # noqa: F811
    """The cell through ``run.run_cell`` at the rehearsal's size, traced:
    the program (bfloat16 over bfloat16 parameters, 8 of 32 experts held)
    reads ``correct`` against the plain reference given the same share,
    the reference in float8 put in its place does not; the line carries
    the cell's metrics, the new part among them, and the program's own
    count of choices moved by spans x 8 x 4 routed layers."""
    from odigos_tpu.models.transformer import EXPERT_CHOICES_METRIC
    from odigos_tpu.utils.telemetry import meter

    before = meter.snapshot().get(EXPERT_CHOICES_METRIC, 0.0)
    line = run.run_cell(CELL, seed, 1.0, True, rehearse=rehearsal_postnorm,
                        control=True)
    assert line["correct"] is True and line["failed"] == 0
    assert line["rehearsal"] is True
    limits = rehearsal_postnorm["correct"]
    control = line["control"]
    assert control["correct"] is False and control["precision"] == "fp8"
    assert control["delivery_faults"] == 0
    assert control["gap_rms"] > limits["gap_rms"]
    assert control["gap_rms"] > 2 * line["compared"]["gap_rms"]["value"]
    got = set(line["metrics"])
    assert {"step_qk_ms.backlog", "step_dense_ms.backlog",
            "step_route_ms.backlog", "step_norm_ms.backlog",
            "step_mlp_ms.backlog", "step_attn_ms.backlog",
            "step_mfu.backlog", "padded_share.backlog"} <= got
    assert "experts_roofline.backlog" not in got
    assert not [m for m in got if m.endswith(".steady")]
    assert set(line["hosttrace"]["parts"]) <= {
        "attn", "qk", "mlp", "dense", "route", "norm", "rest"}
    moved = meter.snapshot()[EXPERT_CHOICES_METRIC] - before
    assert moved >= line["attempted"] * 8 * 4      # the warm-up's beside
    assert moved % (8 * 4) == 0


def test_the_cell_judged_by_seven_experts_is_not_correct(
        rehearsal_postnorm, stood_in_trace):  # noqa: F811
    """``benchmark/tests/postnorm_seven_experts.json`` lays a reference
    with one routed expert a span fewer over the cell: the same served
    scores, through the same window and judge, read ``correct`` false by
    ``gap_rms`` (the rehearsal's limits by the rule the cell's follow:
    sound runs read 0.021-0.033 and the float8 control 0.072-0.116 over
    five seeds, seven experts 0.071-0.076 over two, against 0.048)."""
    fewer = load("benchmark", "tests", "postnorm_seven_experts.json")
    line = run.run_cell(CELL, 23, 1.0, False,
                        rehearse={**rehearsal_postnorm, **fewer})
    assert line["failed"] == 0 and line["correct"] is False
    read = line["compared"]
    assert read["delivery_faults"]["value"] == 0
    assert read["gap_rms"]["value"] \
        > 1.3 * rehearsal_postnorm["correct"]["gap_rms"]
