"""What every architecture's plain reference shares: raw span columns and
a seed in, and the steps that are this system's and not the backbone's.

Straightforward ``jax.numpy`` in float32 with every matrix product at
``highest`` precision, no kernels, no ladder, no shared rows beyond a
plain greedy fill. It imports nothing of the program and takes nothing
the program made: it featurizes the generator's plain columns itself
(``featurize``), lays them out in rows (``lay_out``), derives the weights
from ``--seed`` itself (``_param_key``, ``_inits``: the same numbers
flax's ``Module.init(PRNGKey(seed))`` gives the program, same key
derivation, same initializers) and holds this system's span embedder and
span head (``outer_weights``, ``span_embedding``, ``span_head``), which
every backbone sits between. ``score_rows`` drives an architecture's
forward pass over the laid-out rows a block at a time.

The backbone's own equations, its weights and its operation count are in
``benchmark/architectures/<name>.py``, which imports from here.

``_matmul("fp8")`` is the control's product: inputs cast to float8
(e4m3; activations scaled per row, weights per output column), the
precision next below bfloat16.
"""

from __future__ import annotations

import hashlib
from functools import partial
from typing import Any

import numpy as np

CAT_WIDTH = 5     # service, name, kind, status, parent service
CONT_WIDTH = 3    # log1p(duration us), is root, depth hint
VOCAB = {"service": 512, "name": 2048, "kind": 8, "status": 4}


# ------------------------------------------------------------- featurize


def _hash_id(s: str, vocab: int) -> int:
    h = int.from_bytes(hashlib.blake2b(s.encode(), digest_size=8).digest(),
                       "little")
    return 1 + h % (vocab - 1)


def featurize(frame) -> tuple[np.ndarray, np.ndarray]:
    """(n, 5) int32 categorical ids and (n, 3) float32 continuous
    columns of one frame, from its plain columns."""
    n = len(frame)
    svc_ids = np.array([_hash_id(s, VOCAB["service"])
                        for s in frame.strings], np.int32)
    name_ids = np.array([_hash_id(s, VOCAB["name"])
                         for s in frame.strings], np.int32)
    service = svc_ids[frame.service]
    row_of = {int(s): i for i, s in enumerate(frame.span_id)}
    parent_row = np.array([row_of.get(int(p), -1) for p in frame.parent])
    found = parent_row >= 0
    parent_service = np.where(found, service[np.maximum(parent_row, 0)], 0)
    cat = np.stack([service, name_ids[frame.name],
                    frame.kind.astype(np.int32),
                    frame.status.astype(np.int32), parent_service],
                   axis=1).astype(np.int32)
    dur_us = np.maximum(frame.end.astype(np.int64)
                        - frame.start.astype(np.int64), 0) / 1_000.0
    is_root = frame.parent == 0
    cont = np.stack([np.log1p(dur_us), is_root.astype(np.float64),
                     np.where(is_root, 0.0, np.where(found, 1.0, 0.5))],
                    axis=1).astype(np.float32)
    assert cat.shape == (n, CAT_WIDTH) and cont.shape == (n, CONT_WIDTH)
    return cat, cont


def lay_out(frames, max_len: int, row_multiple: int = 64):
    """Place every span of ``frames`` in a (rows, max_len) grid: spans of
    a trace side by side in start-time order (ties in sent order), whole
    traces filled greedily into rows, a trace longer than ``max_len`` cut
    into pieces that attend within themselves. Returns the grid's
    categorical, continuous, segment and position arrays and, per frame,
    the (row, column) of each of its spans."""
    cats, conts, where = [], [], []
    seg_rows: list[tuple[int, int, int]] = []   # (frame, first span, n)
    for f, frame in enumerate(frames):
        cat, cont = featurize(frame)
        order = np.lexsort((frame.start, frame.trace))   # stable
        cats.append(cat[order])
        conts.append(cont[order])
        where.append(order)
        tr = frame.trace[order]
        cuts = np.flatnonzero(np.diff(tr)) + 1
        for a, b in zip(np.r_[0, cuts], np.r_[cuts, len(tr)]):
            for c in range(a, b, max_len):
                seg_rows.append((f, c, min(max_len, b - c)))
    # greedy fill
    placed = []
    row, col, seg = 0, 0, 0
    for f, first, n in seg_rows:
        if col + n > max_len:
            row, col, seg = row + 1, 0, 0
        seg += 1
        placed.append((f, first, n, row, col, seg))
        col += n
    rows = -(-(row + 1) // row_multiple) * row_multiple
    cat = np.zeros((rows, max_len, CAT_WIDTH), np.int32)
    cont = np.zeros((rows, max_len, CONT_WIDTH), np.float32)
    segments = np.zeros((rows, max_len), np.int32)
    positions = np.zeros((rows, max_len), np.int32)
    slot = [np.zeros((len(fr), 2), np.int64) for fr in frames]
    for f, first, n, r, c, s in placed:
        cat[r, c:c + n] = cats[f][first:first + n]
        cont[r, c:c + n] = conts[f][first:first + n]
        segments[r, c:c + n] = s
        positions[r, c:c + n] = np.arange(n)
        src = where[f][first:first + n]
        slot[f][src, 0] = r
        slot[f][src, 1] = np.arange(c, c + n)
    return cat, cont, segments, positions, slot


# ---------------------------------------------------------------- weights


def _param_key(root, path: tuple[str, ...], count: int):
    """The key flax hands the ``count``-th parameter made in the module
    at ``path``: the root key folded with the first four bytes of the
    SHA-1 of the path names and the count (flax.core.scope)."""
    import jax
    import jax.numpy as jnp

    m = hashlib.sha1()
    for x in path:
        m.update(x.encode("utf-8"))
    m.update(count.to_bytes((count.bit_length() + 7) // 8, "big"))
    return jax.random.fold_in(
        root, jnp.uint32(int.from_bytes(m.digest()[:4], "big")))


def _inits():
    import jax

    init = jax.nn.initializers
    return (init.variance_scaling(1.0, "fan_in", "truncated_normal"),
            init.variance_scaling(1.0, "fan_in", "normal", out_axis=0))


def outer_weights(seed: int, d_model: int, max_len: int) -> dict[str, Any]:
    """Embedding tables, continuous projection, position table and span
    head (float32), keyed by flax's own parameter paths."""
    import jax
    import jax.numpy as jnp

    root = jax.random.PRNGKey(seed)
    lecun, embed = _inits()
    emb = ("encoder", "embed")
    f32 = jnp.float32
    return {
        "service": embed(_param_key(root, emb + ("service_embed",), 1),
                         (VOCAB["service"], d_model), f32),
        "name": embed(_param_key(root, emb + ("name_embed",), 1),
                      (VOCAB["name"], d_model), f32),
        "kind": embed(_param_key(root, emb + ("kind_embed",), 1),
                      (VOCAB["kind"], d_model), f32),
        "status": embed(_param_key(root, emb + ("status_embed",), 1),
                        (VOCAB["status"], d_model), f32),
        "cont_w": lecun(_param_key(root, emb + ("cont_proj",), 1),
                        (CONT_WIDTH, d_model), f32),
        "cont_b": jnp.zeros((d_model,), f32),
        "pos": embed(_param_key(root, ("encoder", "pos_embed"), 1),
                     (max_len, d_model), f32),
        "head_w": lecun(_param_key(root, ("span_head",), 1),
                        (d_model, 1), f32),
        "head_b": jnp.zeros((1,), f32),
    }


# ---------------------------------------------------------------- forward


# the precisions ``_matmul`` computes: the reference's own, and those an
# architecture's ``CONTROL`` may name. An architecture whose control is
# another brings the product itself, in its own file.
PRECISIONS = ("float32", "fp8")


def _matmul(precision: str):
    import jax
    import jax.numpy as jnp

    if precision == "float32":
        return partial(jnp.matmul, precision=jax.lax.Precision.HIGHEST)
    if precision == "fp8":
        def f8(a, w):
            sa = jnp.max(jnp.abs(a), axis=-1, keepdims=True) / 448.0
            sw = jnp.max(jnp.abs(w), axis=0, keepdims=True) / 448.0
            qa = (a / jnp.maximum(sa, 1e-30)).astype(jnp.float8_e4m3fn)
            qw = (w / jnp.maximum(sw, 1e-30)).astype(jnp.float8_e4m3fn)
            return jnp.matmul(qa.astype(jnp.float32), qw.astype(jnp.float32),
                              precision=jax.lax.Precision.HIGHEST) * sa * sw

        return f8
    raise ValueError(f"unknown precision {precision!r}")


def span_embedding(outer: dict[str, Any], cat, cont):
    """The span embedder's sum, before any position term: the five
    categorical lookups and the continuous projection."""
    import jax
    import jax.numpy as jnp

    return (outer["service"][cat[..., 0]] + outer["name"][cat[..., 1]]
            + outer["kind"][cat[..., 2]] + outer["status"][cat[..., 3]]
            + outer["service"][cat[..., 4]]
            + jnp.matmul(cont, outer["cont_w"],
                         precision=jax.lax.Precision.HIGHEST)
            + outer["cont_b"])


def span_head(outer: dict[str, Any], h):
    """The span head over the backbone's normed output: one logit a
    span, through the sigmoid."""
    import jax
    import jax.numpy as jnp

    logit = jnp.matmul(h, outer["head_w"],
                       precision=jax.lax.Precision.HIGHEST)[..., 0]
    return jax.nn.sigmoid(logit + outer["head_b"][0])


def score_rows(frames, max_len: int, block_rows: int, forward,
               ) -> list[np.ndarray]:
    """``forward(cat, cont, segments, positions) -> (rows, max_len)``
    scores, run over the laid-out rows of ``frames`` a block of
    ``block_rows`` at a time; returns one float32 array per frame in the
    frame's own span order."""
    import jax.numpy as jnp

    cat, cont, seg, pos, slot = lay_out(frames, max_len,
                                        row_multiple=block_rows)
    out = np.zeros(seg.shape, np.float32)
    for r0 in range(0, seg.shape[0], block_rows):
        sl = slice(r0, r0 + block_rows)
        out[sl] = np.asarray(forward(
            jnp.asarray(cat[sl]), jnp.asarray(cont[sl]),
            jnp.asarray(seg[sl]), jnp.asarray(pos[sl])))
    return [out[sl_[:, 0], sl_[:, 1]] for sl_ in slot]
