"""The routed block's rows on the TPU as Pallas DMA kernels. The way
back, ``combine``, is two: each real assignment's row of the grouped
products' output, in expert order, is moved by one DMA to its place in
assignment order (``_scatter``), and a span's k rows are then read in
bulk, weighed in float32 and summed in the order j = 0..k-1 (``_weigh``).
Rows of slots that hold no span are never moved. The way in under a held
share of the experts, ``into_expert_order``, is one (``_gather``): each
held choice's span row is moved by one DMA to its place in expert order,
and no other row is. ``routed_experts`` (``layers.py``) runs
``combine`` on the TPU after the grouped products, and
``into_expert_order`` before them where the device holds a share of the
experts; every other platform keeps XLA's gathers.

Why not XLA's gather: it reads rows at memory speed from a table the chip
can hold close, as the span rows into expert order are where every expert
is held (spans x d, 84 MB in the 256-row call: 0.80 ms a layer, where a
DMA way in ran 5.81), and five times slower from one it cannot, as the
expert rows back are (spans x k x d, 503 MB; PERF.md section 6). And it
writes every slot's k rows, where under a held share most of them are
choices of experts held elsewhere, which nothing reads (15 of 16 at
K-EXAONE's share of 8 of 128 experts).

A bfloat16 row is not a unit a DMA can move in XLA's tiled layout: a tile
is 16 rows x 128 columns and two rows share each 32-bit word. What a DMA
can move is one 32-bit row of an (8, 128) tile, seen through a view of the
tiles as (8, 1, 128) pieces that XLA lays out with no copy (``_rows``).
So a tile of expert rows is split in VMEM into word rows, one row each:
word c of a row holds its column c in the low half and column c + d/2 in
the high half (a bfloat16 is the high half of the float32 it widens to,
so either half widens with one shift or one mask).

The way in splits the span rows into word rows the same way, once, and
joins the two rows of a word back in VMEM before a tile leaves.

All take ``interpret`` for the CPU (tests); their tiles follow from d, k
and the row counts they are given.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
# VMEM a kernel's staging buffers may take (both slots), of the chip's
# 128 MiB, and the scoped limit that leaves room for Mosaic's own
STAGING_BYTES = 16 * 2**20
VMEM_LIMIT = 48 * 2**20
# the halves of a 32-bit word
LOW, HIGH = np.uint32(0xFFFF), np.uint32(0xFFFF0000)


def supported(h: jax.Array) -> bool:
    """Whether the kernels take rows of this dtype and width: bfloat16,
    whole 32-bit words in whole lane tiles (d a multiple of 256)."""
    return h.dtype == jnp.bfloat16 and h.shape[-1] % (2 * LANES) == 0


def _tile(n: int, most: int) -> int:
    """The largest power of two from 16 up to ``most`` that divides n."""
    t = 16
    while t * 2 <= most and n % (t * 2) == 0:
        t *= 2
    return t


def _rows(pieces: jax.Array) -> jax.Array:
    """(m/8, n/128, 8, 1, 128) 32-bit pieces as the (m, n) array whose
    tiled layout holds the same bytes: a DMA moves one row of the first
    shape, a block reads the second."""
    a, b, _, _, _ = pieces.shape
    return pieces.reshape(a, b, 8, LANES).transpose(0, 2, 1, 3).reshape(
        a * 8, b * LANES)


def _scatter(y: jax.Array, dest: jax.Array, n_real: jax.Array, rows: int,
             interpret: bool) -> jax.Array:
    """Rows of ``y`` (sorted, bfloat16, (m, d)) to word rows in the order
    ``dest`` names: row r of ``y`` for r < ``n_real`` becomes word row
    ``dest[r]`` of the (rows, d/2) uint32 result (word rows, as the module
    says), one DMA a row; a tile of ``y`` past ``n_real`` is neither read nor
    moved. Returns the result as (rows/8, d/256, 8, 1, 128) pieces
    (``_rows``).

    Each tile of ``y`` is fetched by the kernel itself, the next one
    before this one's rows leave: behind them in the DMA queues it would
    wait for each to land."""
    m, d = y.shape
    W = d // 2
    pieces = W // LANES
    tile = _tile(m, 512)
    n_tiles = m // tile
    by_tile = dest.reshape(n_tiles, 1, tile)

    def kernel(n_ref, dest_ref, y_hbm, out_hbm, y_buf, words, fetch_sem,
               row_sem, issued):
        i = pl.program_id(0)
        slot = i % 2
        n = n_ref[0]

        def fetch(tile_no, s):
            return pltpu.make_async_copy(
                y_hbm.at[pl.ds(tile_no * tile, tile)], y_buf.at[s],
                fetch_sem.at[s])

        def drain(s):
            def one(_, c):
                pltpu.make_async_copy(
                    words.at[s, 0, pl.ds(0, 1), :, pl.ds(0, 1)],
                    out_hbm.at[pl.ds(0, 1), :, pl.ds(0, 1)],
                    row_sem.at[s]).wait()
                return c
            jax.lax.fori_loop(0, issued[s], one, 0)
            issued[s] = 0

        @pl.when(i == 0)
        def _():
            issued[0] = 0
            issued[1] = 0

            @pl.when(n > 0)
            def _():
                fetch(0, 0).start()

        @pl.when(i * tile < n)
        def _():
            @pl.when((i + 1) * tile < n)
            def _():
                fetch(i + 1, 1 - slot).start()

            fetch(i, slot).wait()
            drain(slot)           # what left this slot two tiles ago

            def split(a, c):
                # the tile's rows 16a..16a+15, two a word, to word rows
                # 8a..8a+7 of the even and of the odd rows
                at = pl.ds(pl.multiple_of(a * 16, 16), 16)
                for p in range(pieces):
                    lo = pltpu.bitcast(y_buf[slot, at, pl.ds(p * LANES, LANES)],
                                       jnp.uint32)
                    hi = pltpu.bitcast(
                        y_buf[slot, at, pl.ds(W + p * LANES, LANES)],
                        jnp.uint32)
                    words[slot, 0, a, p, :, 0, :] = (lo & LOW) | (hi << 16)
                    words[slot, 1, a, p, :, 0, :] = (lo >> 16) | (hi & HIGH)
                return c
            jax.lax.fori_loop(0, tile // 16, split, 0)

            def row(r, c):
                to, half = dest_ref[0, 0, r], r >> 1
                pltpu.make_async_copy(
                    words.at[slot, r & 1, pl.ds(half >> 3, 1), :,
                             pl.ds(half & 7, 1)],
                    out_hbm.at[pl.ds(to >> 3, 1), :, pl.ds(to & 7, 1)],
                    row_sem.at[slot]).start()
                return c
            count = jnp.minimum(tile, n - i * tile)   # real rows: a prefix
            jax.lax.fori_loop(0, count, row, 0)
            issued[slot] = count

        @pl.when(i == n_tiles - 1)
        def _():
            drain(0)
            drain(1)

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((rows // 8, pieces, 8, 1, LANES),
                                       jnp.uint32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(n_tiles,),
            in_specs=[pl.BlockSpec((1, 1, tile), lambda i, n: (i, 0, 0),
                                   memory_space=pltpu.SMEM),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.VMEM((2, tile, d), y.dtype),
                            pltpu.VMEM((2, 2, tile // 16, pieces, 8, 1,
                                        LANES), jnp.uint32),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.SMEM((2,), jnp.int32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(jnp.reshape(n_real, (1,)).astype(jnp.int32), by_tile, y)


def _gather(words: jax.Array, src: jax.Array, n_held: jax.Array, dtype,
            interpret: bool) -> jax.Array:
    """Word rows (as the module says; (a, d/256, 8, 1, 128) pieces,
    ``_rows``) to rows of ``dtype`` in the order ``src`` (m,) names: row r
    of the (m, d) result for r < ``n_held`` is word row ``src[r]`` joined,
    one DMA a row. A tile of the result past ``n_held`` is neither written
    nor zeroed; the rows of the last tile written past ``n_held`` hold
    whatever the tile's staging held.

    A tile's row DMAs are issued in the grid step of that tile and waited
    for in the next one, which joins the tile's two rows a word into
    ``dtype``, as the previous tile's rows land, and sends it out whole."""
    _, pieces, _, _, _ = words.shape
    W = pieces * LANES
    m = src.shape[0]
    tile = _tile(m, 512)
    n_tiles = m // tile
    by_tile = src.reshape(n_tiles, 1, tile)

    def kernel(n_ref, src_ref, words_hbm, out_hbm, got, rows, row_sem,
               out_sem, state):
        i = pl.program_id(0)
        slot = i % 2
        n = n_ref[0]

        def row(t, r, s):
            # word row t to row r of the tile, with the rows of its parity
            return pltpu.make_async_copy(
                words_hbm.at[pl.ds(t >> 3, 1), :, pl.ds(t & 7, 1)],
                got.at[s, r & 1, pl.ds(r >> 4, 1), :, pl.ds((r >> 1) & 7, 1)],
                row_sem.at[s])

        def out(tile_no, s):
            return pltpu.make_async_copy(
                rows.at[s], out_hbm.at[pl.ds(tile_no * tile, tile)],
                out_sem.at[s])

        def finish(tile_no, s):
            def landed(_, c):
                row(0, 0, s).wait()
                return c
            jax.lax.fori_loop(0, state[s], landed, 0)

            @pl.when(state[2 + s] > 0)
            def _():
                out(0, s).wait()   # the tile this slot sent two tiles ago

            def join(a, c):
                # word rows 8a..8a+7 of the even and of the odd rows to the
                # tile's rows 16a..16a+15, two a word
                at = pl.ds(pl.multiple_of(a * 16, 16), 16)
                for p in range(pieces):
                    even = got[s, 0, a, p, :, 0, :]
                    odd = got[s, 1, a, p, :, 0, :]
                    rows[s, at, pl.ds(p * LANES, LANES)] = pltpu.bitcast(
                        (even & LOW) | (odd << 16), dtype)
                    rows[s, at, pl.ds(W + p * LANES, LANES)] = pltpu.bitcast(
                        (even >> 16) | (odd & HIGH), dtype)
                return c
            jax.lax.fori_loop(0, tile // 16, join, 0)
            out(tile_no, s).start()
            state[2 + s] = 1

        @pl.when(i == 0)
        def _():
            for at in range(4):   # rows issued, then a tile sent, a slot
                state[at] = 0

        @pl.when(i * tile < n)
        def _():
            def issue(r, c):
                row(src_ref[0, 0, r], r, slot).start()
                return c
            count = jnp.minimum(tile, n - i * tile)   # held rows: a prefix
            jax.lax.fori_loop(0, count, issue, 0)
            state[slot] = count

        @pl.when((i > 0) & ((i - 1) * tile < n))
        def _():
            finish(i - 1, 1 - slot)

        @pl.when(i == n_tiles - 1)
        def _():
            @pl.when(i * tile < n)
            def _():
                finish(i, slot)

            for s in range(2):
                @pl.when(state[2 + s] > 0)
                def _():
                    out(0, s).wait()

    def current(i, n_ref):
        # the last tile that holds a row: past it the indices stay put
        return (jnp.minimum(i, jnp.maximum(n_ref[0] - 1, 0) // tile), 0, 0)

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((m, 2 * W), dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(n_tiles,),
            in_specs=[pl.BlockSpec((1, 1, tile), current,
                                   memory_space=pltpu.SMEM),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.VMEM((2, 2, tile // 16, pieces, 8, 1,
                                        LANES), jnp.uint32),
                            pltpu.VMEM((2, tile, 2 * W), dtype),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.SMEM((4,), jnp.int32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret, name="into_expert_order",
    )(jnp.reshape(n_held, (1,)).astype(jnp.int32), by_tile, words)


def into_expert_order(h: jax.Array, order: jax.Array, k: int,
                      n_held: jax.Array, rows: int,
                      interpret: bool = False) -> jax.Array:
    """The way in under a held share: ``out[r] = h[order[r] // k]`` for
    r < ``n_held``, the held choices, which the sort put first; ``h`` (T,
    d) bfloat16, ``order`` (T k,) the sorted order (as ``combine`` has
    it). Returns (``rows``, d), a whole number of the grouped products'
    tiles; its rows from ``n_held`` on are nobody's, and the tiles past
    the last held row are not written at all."""
    T, d = h.shape
    W = d // 2
    spans = -(-T // 8) * 8
    # each span row as a word row: column c low, column c + d/2 high
    lo, hi = (jax.lax.bitcast_convert_type(part, jnp.uint16).astype(
        jnp.uint32) for part in (h[:, :W], h[:, W:]))
    words = jnp.pad(lo | (hi << 16), ((0, spans - T), (0, 0)))
    words = words.reshape(spans // 8, 8, W // LANES, LANES).transpose(
        0, 2, 1, 3).reshape(spans // 8, W // LANES, 8, 1, LANES)
    src = jnp.pad(order // k, (0, rows - order.shape[0]))
    return _gather(words, src.astype(jnp.int32), n_held, h.dtype, interpret)


def _weigh(words: jax.Array, weight: jax.Array, real: jax.Array, dtype,
           interpret: bool) -> jax.Array:
    """``sum_j weight[t, j] * row j of span t`` in float32, j in order:
    ``words`` (k, T, d/2) uint32 word rows (as the module says),
    ``weight`` (T, k) float32, ``real`` (T, 1) int32, or (T, k) choice by
    choice; a span that is not real, or a choice that is not, comes back
    zero whatever its rows hold. Returns (T, d) in ``dtype``."""
    k, T, W = words.shape
    tile = _tile(T, max(16, STAGING_BYTES // (2 * k * W * 4)))
    by_choice = real.shape[1] > 1

    def kernel(words_ref, weight_ref, real_ref, out_ref):
        def rows(a, c):
            at = pl.ds(pl.multiple_of(a * 16, 16), 16)
            w, ok = weight_ref[at, :], real_ref[at, :] > 0
            lo = hi = jnp.zeros((16, W), jnp.float32)
            for j in range(k):
                word = words_ref[j, at, :]
                mine = ok[:, j:j + 1] if by_choice else ok
                # a bfloat16 is the high half of the float32 it widens to
                for half, bits in ((0, word << 16), (1, word & HIGH)):
                    term = jnp.where(mine, jax.lax.bitcast_convert_type(
                        bits, jnp.float32) * w[:, j:j + 1], 0.0)
                    if half:
                        hi = hi + term
                    else:
                        lo = lo + term
            out_ref[at, pl.ds(0, W)] = lo.astype(out_ref.dtype)
            out_ref[at, pl.ds(W, W)] = hi.astype(out_ref.dtype)
            return c
        jax.lax.fori_loop(0, tile // 16, rows, 0)

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((T, 2 * W), dtype),
        grid=(T // tile,),
        in_specs=[pl.BlockSpec((k, tile, W), lambda i: (0, i, 0)),
                  pl.BlockSpec((tile, k), lambda i: (i, 0)),
                  pl.BlockSpec((tile, real.shape[1]), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((tile, 2 * W), lambda i: (i, 0)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(words, weight, real)


def combine(y: jax.Array, order: jax.Array, n_real: jax.Array,
            weight: jax.Array, real: jax.Array, dtype,
            interpret: bool = False) -> jax.Array:
    """The way back: ``out[t] = sum_j weight[t, j] * y[rank of (t, j)]``
    in float32, j in order, in ``dtype``; ``y`` (m, d) bfloat16 in the
    sorted order ``order`` (T k,) makes (assignment t k + j at row r
    where order[r] = t k + j), its first ``n_real`` rows real; ``weight``
    (T, k) float32 and ``real`` (T,) bool, or (T, k) where a choice and
    not a span holds an assignment (a held share of the experts). A span
    or a choice that is not real comes back zero. Returns (T, d)."""
    T, k = weight.shape
    m, d = y.shape
    spans = -(-T // 16) * 16
    at = jnp.arange(m)
    o = jnp.pad(order, (0, m - order.shape[0]))
    dest = jnp.where(at < n_real, o % k * spans + o // k, -1)
    words = _rows(_scatter(y, dest.astype(jnp.int32), n_real, k * spans,
                           interpret)).reshape(k, spans, d // 2)
    pad = ((0, spans - T), (0, 0))
    out = _weigh(words, jnp.pad(weight.astype(jnp.float32), pad),
                 jnp.pad((real[:, None] if real.ndim == 1 else real)
                         .astype(jnp.int32), pad), dtype,
                 interpret)
    return out[:T]
