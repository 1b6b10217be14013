"""The plain reference: raw span columns and a seed in, span scores out.

Straightforward ``jax.numpy`` in float32 with every matrix product at
``highest`` precision, no kernels, no ladder, no shared rows beyond a
plain greedy fill. It imports nothing of the program and takes nothing
the program made: it featurizes the generator's plain columns itself,
derives the weights from ``--seed`` itself (the same numbers flax's
``Module.init(PRNGKey(seed))`` gives the program: same key derivation,
same initializers) and runs the encoder layer by layer, generating each
layer's weights inside the jitted layer step, so that ViT-H's 2.5 GB of
float32 parameters never sit on the device at once.

The equations, after arXiv:2010.11929 section 3.1 (pre-LN encoder), with
this system's embedder and head in place of patches and class token::

    x0 = E_service[svc] + E_name[name] + E_kind[kind] + E_status[status]
         + E_service[parent_svc] + cont @ W_c + b_c + E_pos[position]
    h  = LN(x);  q, k, v = h W_q + b_q, h W_k + b_k, h W_v + b_v
    a  = softmax(q k^T / sqrt(d_head), over the spans of the same trace)
    x  = x + (a v) W_o + b_o
    x  = x + gelu_tanh(LN(x) W_1 + b_1) W_2 + b_2          (each layer)
    score = sigmoid(LN(x) w_s + b_s)

``precision="fp8"`` is the control: the same reference with the six
matrix products of every layer computed from inputs cast to float8
(e4m3; activations scaled per row, weights per output column), the
precision next below the configuration's bfloat16.
"""

from __future__ import annotations

import hashlib
from functools import partial
from typing import Any

import numpy as np

CAT_WIDTH = 5     # service, name, kind, status, parent service
CONT_WIDTH = 3    # log1p(duration us), is root, depth hint
VOCAB = {"service": 512, "name": 2048, "kind": 8, "status": 4}
LN_EPS = 1e-6


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


def layer_keys(seed: int, n_layers: int):
    """(n_layers, 6, 2) uint32: the keys of each block's six kernels
    (query, key, value, out, feed-forward in, feed-forward out)."""
    import jax
    import jax.numpy as jnp

    root = jax.random.PRNGKey(seed)
    out = []
    for i in range(n_layers):
        blk = ("encoder", f"block_{i}")
        mha = blk + ("MultiHeadDotProductAttention_0",)
        out.append(jnp.stack(
            [_param_key(root, mha + (nm,), 1)
             for nm in ("query", "key", "value", "out")]
            + [_param_key(root, blk + (nm,), 1)
               for nm in ("Dense_0", "Dense_1")]))
    return jnp.stack(out)


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


def block_weights(keys, d_model: int, d_ff: int) -> dict[str, Any]:
    """One block's parameters from its six kernel keys: lecun-normal
    kernels, zero biases, unit LayerNorm scales (what flax makes)."""
    import jax.numpy as jnp

    lecun, _ = _inits()
    f32 = jnp.float32
    d = d_model
    return {
        "wq": lecun(keys[0], (d, d), f32), "wk": lecun(keys[1], (d, d), f32),
        "wv": lecun(keys[2], (d, d), f32), "wo": lecun(keys[3], (d, d), f32),
        "w1": lecun(keys[4], (d, d_ff), f32),
        "w2": lecun(keys[5], (d_ff, d), f32),
        "bq": jnp.zeros((d,), f32), "bk": jnp.zeros((d,), f32),
        "bv": jnp.zeros((d,), f32), "bo": jnp.zeros((d,), f32),
        "b1": jnp.zeros((d_ff,), f32), "b2": jnp.zeros((d,), f32),
        "ln1_s": jnp.ones((d,), f32), "ln1_b": jnp.zeros((d,), f32),
        "ln2_s": jnp.ones((d,), f32), "ln2_b": jnp.zeros((d,), f32),
    }


# ---------------------------------------------------------------- forward


def _layer_norm(x, scale, bias):
    import jax.numpy as jnp

    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + LN_EPS) * scale + bias


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


def block_step(x, allowed, keys, *, n_heads: int, d_ff: int,
               precision: str):
    """One pre-LN encoder block over (rows, L, d) with its weights made
    here from ``keys``; ``allowed`` is (rows, L, L) bool."""
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST
    mm = _matmul(precision)
    rows, L, d = x.shape
    hd = d // n_heads
    w = block_weights(keys, d, d_ff)
    h = _layer_norm(x, w["ln1_s"], w["ln1_b"])
    q = (mm(h, w["wq"]) + w["bq"]).reshape(rows, L, n_heads, hd)
    k = (mm(h, w["wk"]) + w["bk"]).reshape(rows, L, n_heads, hd)
    v = (mm(h, w["wv"]) + w["bv"]).reshape(rows, L, n_heads, hd)
    s = jnp.einsum("rqhd,rkhd->rhqk", q, k, precision=hi) / np.sqrt(hd)
    s = jnp.where(allowed[:, None], s, jnp.finfo(jnp.float32).min)
    a = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("rhqk,rkhd->rqhd", a, v, precision=hi).reshape(rows, L, d)
    x = x + mm(o, w["wo"]) + w["bo"]
    h = _layer_norm(x, w["ln2_s"], w["ln2_b"])
    h = jax.nn.gelu(mm(h, w["w1"]) + w["b1"], approximate=True)
    return x + mm(h, w["w2"]) + w["b2"]


def scores(frames, seed: int, model: dict[str, Any],
           precision: str = "float32", block_rows: int = 256
           ) -> list[np.ndarray]:
    """The reference's score of every span of every frame, as one float32
    array per frame in the frame's own span order. ``model`` holds
    d_model, n_heads, n_layers, d_ff and max_len."""
    import jax
    import jax.numpy as jnp

    d, L = int(model["d_model"]), int(model["max_len"])
    n_layers, d_ff = int(model["n_layers"]), int(model["d_ff"])
    cat, cont, seg, pos, slot = lay_out(frames, L, row_multiple=block_rows)
    hi = jax.lax.Precision.HIGHEST
    outer = outer_weights(seed, d, L)

    @jax.jit
    def embed(cat, cont, seg, pos):
        x = (outer["service"][cat[..., 0]] + outer["name"][cat[..., 1]]
             + outer["kind"][cat[..., 2]] + outer["status"][cat[..., 3]]
             + outer["service"][cat[..., 4]]
             + jnp.matmul(cont, outer["cont_w"], precision=hi)
             + outer["cont_b"] + outer["pos"][pos])
        return x * (seg > 0)[..., None]

    step = jax.jit(partial(block_step, n_heads=int(model["n_heads"]),
                           d_ff=d_ff, precision=precision))

    @jax.jit
    def head(x):
        h = _layer_norm(x, jnp.ones((d,)), jnp.zeros((d,)))
        logit = jnp.matmul(h, outer["head_w"], precision=hi)[..., 0]
        return jax.nn.sigmoid(logit + outer["head_b"][0])

    keys = layer_keys(seed, n_layers)
    out = np.zeros(seg.shape, np.float32)
    for r0 in range(0, seg.shape[0], block_rows):
        sl = slice(r0, r0 + block_rows)
        s = jnp.asarray(seg[sl])
        allowed = (s[:, :, None] == s[:, None, :]) & (s > 0)[:, :, None] \
            & (s > 0)[:, None, :]
        x = embed(jnp.asarray(cat[sl]), jnp.asarray(cont[sl]), s,
                  jnp.asarray(pos[sl]))
        for i in range(n_layers):
            x = step(x, allowed, keys[i])
        out[sl] = np.asarray(head(x))
    return [out[sl_[:, 0], sl_[:, 1]] for sl_ in slot]
