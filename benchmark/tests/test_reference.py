"""The plain reference of every architecture a configuration names
against the program's own model at a tiny size in float32: the same
weights from the same seed, the same scores for the same spans. What each
architecture's program model is and which parameter each weight equals is
in its case file (``arch_cases/<name>.py``)."""

import json
import os

import numpy as np
import pytest

from benchmark import gen, reference
from benchmark.tests.conftest import ARCHITECTURES, arch_case

HERE = os.path.dirname(os.path.abspath(__file__))
SEED = 2**31 + 11


def make_pool(seed, **over):
    with open(os.path.join(HERE, "..", "traffic", "backlog.json")) as f:
        traffic = json.load(f)
    traffic.update(over)
    return gen.make_pool(traffic, seed)


@pytest.fixture(scope="module", params=ARCHITECTURES)
def built(request):
    """(architecture, its case, the program's model, its variables)."""
    arch, case = arch_case(request.param)
    return (arch, case) + tuple(case.program(SEED))


def program_scores(model, variables, frame, max_len, serial):
    from odigos_tpu.features import featurize
    from odigos_tpu.features.featurizer import pack_sequences

    batch = gen.rekey(gen.to_request(frame), serial)
    packed = pack_sequences(batch, featurize(batch), max_len=max_len)
    dev = np.asarray(model.score_packed(
        variables, packed.categorical, packed.continuous, packed.segments,
        packed.positions))
    got = np.zeros(len(batch), np.float32)
    got[packed.span_index[packed.mask]] = dev[packed.mask]
    return got


def test_weights_are_what_flax_makes(built):
    import jax

    arch, case, _, variables = built
    p = variables["params"]
    pairs = list(case.weight_pairs(arch, reference, p, SEED))
    assert pairs
    for ours, theirs in pairs:
        np.testing.assert_array_equal(np.asarray(ours), np.asarray(theirs))
    # every bias the program makes is zero and every norm's scale one,
    # which is what the reference hands itself
    for path, leaf in jax.tree_util.tree_leaves_with_path(p):
        name = jax.tree_util.keystr(path)
        if name.endswith("['bias']"):
            assert not np.asarray(leaf).any(), name
        if name.endswith("['scale']"):
            assert (np.asarray(leaf) == 1).all(), name


def test_scores_match_the_program_in_float32(built):
    arch, case, model, variables = built
    pool = make_pool(12345, pool_frames=3, traces_per_frame=24)
    ref = arch.scores(pool, SEED, case.SMALL, block_rows=32)
    for frame, want in zip(pool, ref):
        got = program_scores(model, variables, frame,
                             case.SMALL["max_len"], 7)
        assert np.abs(got - want).max() < 2e-6


def test_a_trace_longer_than_a_row_is_cut_like_the_program_cuts_it(built):
    """A row of 16 and a pool of frontend traces (17 spans each at depth
    6): both sides cut the trace into pieces that attend within
    themselves."""
    arch, case, model, variables = built
    L = case.SMALL["max_len"]
    pool = make_pool(3, pool_frames=1, traces_per_frame=12)
    assert max(np.bincount(pool[0].trace)) > L
    want = arch.scores(pool, SEED, case.SMALL, block_rows=32)[0]
    got = program_scores(model, variables, pool[0], L, 1)
    assert np.abs(got - want).max() < 2e-6


def test_the_whole_model_config_reaches_the_reference(built):
    """``run.py`` hands the configuration's whole ``model_config`` over,
    ``dtype`` and all: the reference reads what it needs and is float32
    whatever the served type."""
    arch, case, _, _ = built
    pool = make_pool(5, pool_frames=1, traces_per_frame=8)
    a = arch.scores(pool, SEED, case.SMALL, block_rows=32)
    b = arch.scores(pool, SEED, {**case.SMALL, "dtype": "bfloat16"},
                    block_rows=32)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    low = arch.scores(pool, SEED, case.SMALL, precision=arch.CONTROL,
                      block_rows=32)
    assert not all(np.array_equal(x, y) for x, y in zip(a, low))


def test_the_shared_product_computes_what_it_lists():
    """``reference.PRECISIONS`` is what ``_matmul`` computes, no more: a
    name outside it raises rather than falling back to float32."""
    a = np.linspace(-1, 1, 12, dtype=np.float32).reshape(3, 4)
    w = np.linspace(1, -2, 8, dtype=np.float32).reshape(4, 2)
    exact = a @ w
    for precision in reference.PRECISIONS:
        got = np.asarray(reference._matmul(precision)(a, w))
        assert np.abs(got - exact).max() < (1e-6 if precision == "float32"
                                            else 0.2)
    for other in ("int8", "int4", "bfloat16", "high", ""):
        with pytest.raises(ValueError):
            reference._matmul(other)


def test_the_seed_changes_values_not_sizes():
    a = make_pool(1, pool_frames=4, traces_per_frame=32)
    b = make_pool(2**31 + 5, pool_frames=4, traces_per_frame=32)

    def sizes(pool):
        # frame by frame and trace by trace, in place: which traces share
        # a frame, and in which order, decides how a call packs
        return [[int(c) for c in np.bincount(f.trace)] for f in pool]

    assert sizes(a) == sizes(b)
    # and under the same ids: the program packs traces in their order
    assert all(np.array_equal(x.trace_lo, y.trace_lo) for x, y in zip(a, b))
    assert not np.array_equal(a[0].start, b[0].start)
    c = make_pool(1, pool_frames=4, traces_per_frame=32)
    assert all(np.array_equal(x.end, y.end) and x.strings == y.strings
               for x, y in zip(a, c))
