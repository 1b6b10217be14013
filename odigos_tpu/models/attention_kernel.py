"""Attention inside packed rows on the TPU as one Pallas kernel
(``packed_attention``): the encoder block's (``layers.EncoderBlock``) core
between the query, key and value projections and the output projection,
which ``self_attention`` runs around it. Scores, mask and softmax stay in
VMEM; heads are read where the projections wrote them; a block of rows
that holds no span is neither read nor computed.

Layout. q, k and v come as the projections write them, (T, L, heads x
d_head) bfloat16, seen as (T L, D) with no copy: positions by rows, each
head's columns side by side. A grid step takes ``block_rows`` rows; inside
it the positions are worked in groups of 128 (one lane tile, ``GROUP / L``
rows), so every operand of every product is a whole (128, 128) tile:

- a head's columns are read through the 128-column tiles they fall in
  (a head of 80 spans one or two of them), the columns of other heads
  set to zero: the scores of head h are the sum over its tiles of
  ``q_tile k_tile^T``, and its output is ``p_h v_tile`` over its tiles,
  each landing in the tile's own columns of the output;
- heads are worked a run at a time, the fewest whose columns end on a
  tile's edge (8 of 80 columns, 2 of 64), in a loop over the runs: the
  heads of one run are unrolled, each run lies over its tiles alike;
- the mask is built from ``segments`` (row-local trace ids, 0 =
  padding): query i may read key j where both are of one row and one
  trace and j is real. The group's rows attend to each other's keys in
  the product and the mask takes them out; the MXU is 128 wide, so a
  product of 64 keys costs what one of 128 does.

Scores and softmax in float32, the products in bfloat16 with float32
accumulation, scores scaled by 1/sqrt(d_head). A masked score is the
finite ``NEG``: a query with no key to read (a padding position) takes
the mean of its group's values, finite, as flax's attention does with a
row of ``finfo.min``. A block of rows whose segments are all 0 writes
zeros; the grid fetches no q, k or v for it (its index map points at the
last block that had a span, already in VMEM).

``interpret`` for the CPU (tests); on the TPU ``layers.EncoderBlock``
reaches ``self_attention`` through ``jax.lax.platform_dependent``.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import layout
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec

GROUP = 128
# VMEM the q, k, v and output blocks may take (two slots each), and the
# scoped limit that leaves room for the group's temporaries
STAGING_BYTES = 16 * 2**20
VMEM_LIMIT = 48 * 2**20
NEG = float(jnp.finfo(jnp.float32).min)
# the mesh axis a plan splits the packed rows over (parallel/sharding.py)
ROWS_AXIS = "data"


def supported(dtype, rows: int, L: int, d_model: int, heads: int) -> bool:
    """Whether the kernel takes a call of ``rows`` packed rows of ``L``
    positions at this width: bfloat16, ``d_model`` in whole lane tiles and
    whole heads, rows of a multiple of 8 positions that divide a group of
    128 (a trace never crosses a group), and rows that fill whole groups."""
    return (jnp.dtype(dtype) == jnp.bfloat16 and d_model % GROUP == 0
            and d_model % heads == 0 and L % 8 == 0 and GROUP % L == 0
            and rows * L % GROUP == 0 and rows > 0)


def block_rows(rows: int, L: int, d_model: int) -> int:
    """Rows a grid step takes: whole groups, the most that divides
    ``rows`` and whose four blocks (q, k, v, out; two slots each) fit
    ``STAGING_BYTES``."""
    group = GROUP // L
    most = max(group, STAGING_BYTES // (8 * L * d_model * 2))
    t = group
    while t * 2 <= most and rows % (t * 2) == 0:
        t *= 2
    return t


def rows_skipped(rows: int, real_rows: int, block: int,
                 shards: int = 1) -> int:
    """Rows of a call of ``rows`` (its first ``real_rows`` holding spans,
    as the packer fills them) in blocks of ``block`` rows that hold no
    span, summed over ``shards`` devices that each attend their own
    ``rows / shards``."""
    local = rows // shards
    skipped = 0
    for d in range(shards):
        real = min(max(real_rows - d * local, 0), local)
        skipped += local - -(-real // block) * block
    return skipped


def _head_tiles(heads: int, d_head: int) -> list[list[tuple[int, int, int]]]:
    """For each head, the 128-column tiles its columns fall in: (tile,
    first column, end) with the columns counted inside the tile."""
    out = []
    for h in range(heads):
        lo, hi = h * d_head, (h + 1) * d_head
        out.append([(c, max(lo, c * GROUP) - c * GROUP,
                     min(hi, (c + 1) * GROUP) - c * GROUP)
                    for c in range(lo // GROUP, -(-hi // GROUP))])
    return out


def _period(heads: int, d_head: int) -> int:
    """Heads in a run (the module's docstring): the fewest whose columns
    end on a lane tile's edge, or all of them where those do not divide
    the heads."""
    run = GROUP // math.gcd(d_head, GROUP)
    return run if heads % run == 0 else heads


def _kernel(live_ref, fetch_ref, seg_ref, q_ref, k_ref, v_ref, out_ref,
            acc_ref, *, heads: int, d_head: int, L: int):
    del fetch_ref
    run = _period(heads, d_head)
    tiles = _head_tiles(run, d_head)
    width = run * d_head                 # whole lane tiles
    scale = 1.0 / math.sqrt(d_head)
    groups = q_ref.shape[0] // GROUP

    @pl.when(live_ref[pl.program_id(0)] == 0)
    def _():
        out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)

    @pl.when(live_ref[pl.program_id(0)] != 0)
    def _():
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, GROUP), 1)

        def group(g, carry):
            at = pl.ds(pl.multiple_of(g * GROUP, GROUP), GROUP)
            key = jnp.broadcast_to(seg_ref[g], (GROUP, GROUP))
            allowed = (key.T == key) & (key > 0)
            if L < GROUP:    # and of one row
                allowed &= (
                    jax.lax.broadcasted_iota(jnp.int32, key.shape, 0) // L
                    == jax.lax.broadcasted_iota(jnp.int32, key.shape, 1) // L)

            def heads_of(r, carry):
                base = r * width

                def span(c):
                    return pl.ds(pl.multiple_of(base + c * GROUP, GROUP),
                                 GROUP)

                def cols(ref, c, a, b):
                    tile = ref[at, span(c)]
                    if (a, b) == (0, GROUP):
                        return tile
                    return jnp.where((lane >= a) & (lane < b), tile,
                                     jnp.zeros_like(tile))

                written = set()
                for h in range(run):
                    s = None
                    for c, a, b in tiles[h]:
                        part = jax.lax.dot_general(
                            cols(q_ref, c, a, b), k_ref[at, span(c)],
                            (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
                        s = part if s is None else s + part
                    s = jnp.where(allowed, s * scale, NEG)
                    p = jnp.exp(s - jnp.max(s, axis=1, keepdims=True))
                    p = (p / jnp.sum(p, axis=1, keepdims=True)).astype(
                        v_ref.dtype)
                    for c, a, b in tiles[h]:
                        o = jnp.dot(p, cols(v_ref, c, a, b),
                                    preferred_element_type=jnp.float32)
                        if c in written:
                            acc_ref[:, span(c)] += o
                        else:
                            acc_ref[:, span(c)] = o
                            written.add(c)
                return carry

            jax.lax.fori_loop(0, heads // run, heads_of, 0)
            out_ref[at, :] = acc_ref[...].astype(out_ref.dtype)
            return carry

        jax.lax.fori_loop(0, groups, group, 0)


def packed_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     segments: jax.Array, heads: int,
                     interpret: bool = False) -> jax.Array:
    """Attention of each packed row's traces: ``q``, ``k``, ``v`` (T, L,
    heads x d_head) bfloat16 as the projections wrote them, ``segments``
    (T, L) int32 row-local trace ids, 0 = padding. Returns (T, L, heads
    x d_head) in ``q``'s dtype; rows in blocks that hold no span read
    zero. ``supported`` says which shapes it takes."""
    T, L, D = q.shape
    tb = block_rows(T, L, D)
    n_blocks = T // tb
    live = jnp.any(segments.reshape(n_blocks, tb * L) > 0,
                   axis=1).astype(jnp.int32)
    # the block whose q, k and v a step reads: its own where it holds a
    # span, else the last one before it that did (no fetch)
    fetch = jax.lax.cummax(jnp.where(live > 0, jnp.arange(n_blocks), 0))
    seg = segments.astype(jnp.int32).reshape(T * L // GROUP, 1, GROUP)
    rows_of = (tb * L // GROUP, 1, GROUP)
    block = (tb * L, D)

    def by_fetch(i, live, fetch):
        return fetch[i], 0

    out = pl.pallas_call(
        partial(_kernel, heads=heads, d_head=D // heads, L=L),
        out_shape=jax.ShapeDtypeStruct((T * L, D), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(n_blocks,),
            in_specs=[pl.BlockSpec(rows_of,
                                   lambda i, live, fetch: (fetch[i], 0, 0)),
                      pl.BlockSpec(block, by_fetch),
                      pl.BlockSpec(block, by_fetch),
                      pl.BlockSpec(block, by_fetch)],
            out_specs=pl.BlockSpec(block, lambda i, live, fetch: (i, 0)),
            scratch_shapes=[pltpu.VMEM((GROUP, D), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="packed_attention",
    )(live, fetch, seg, q.reshape(T * L, D), k.reshape(T * L, D),
      v.reshape(T * L, D))
    return out.reshape(T, L, D)


# Shape-bucketing strategy of the jitted function here (the package hygiene
# test asserts every jit path in models/ declares one)
SHAPE_BUCKETING = {
    "self_attention": "traced inside the scoring entry that calls it, on "
                      "that call's rows: the engine's BucketLadder rungs",
}


# jitted, as ``layers.routed_experts`` is, so that a stack's layers share
# one trace and one lowering of it: a call's set-up pays every lowering
@partial(jax.jit, static_argnames=("heads", "dtype", "interpret"))
def self_attention(h: jax.Array, segments: jax.Array, params, *, heads: int,
                   dtype, interpret: bool = False) -> jax.Array:
    """The encoder block's attention over packed rows ``h`` (T, L, d) from
    flax's ``MultiHeadDotProductAttention`` parameters (``params``): the
    query, key and value projections write (T L, heads x d_head), each
    kernel seen as one (d_model, heads x d_head) product over the
    positions in row order, ``packed_attention`` reads them there over
    ``segments`` (T, L), each device its own rows under a mesh that splits
    them along ``ROWS_AXIS``, and the output projection reads the kernel's
    output as it is. The products and their biases in ``dtype``, as
    flax's. ``interpret`` runs the kernel in the Pallas interpreter."""
    T, L, d = h.shape

    def product(p, a):
        kernel = p["kernel"].astype(dtype)
        return jnp.dot(a, kernel.reshape(a.shape[-1], -1)) \
            + p["bias"].astype(dtype).reshape(-1)

    def core(q, k, v, seg):
        rows = seg.shape[0]
        return packed_attention(
            *(a.reshape(rows, L, -1) for a in (q, k, v)), seg, heads,
            interpret).reshape(rows * L, -1)

    mesh = jax.sharding.get_abstract_mesh()
    if not mesh.empty and mesh.shape.get(ROWS_AXIS, 1) > 1:
        rows = PartitionSpec(ROWS_AXIS)
        core = jax.shard_map(core, in_specs=(rows,) * 4, out_specs=rows,
                             check_vma=False)
    # the projections read and write positions in row order, the layout
    # the kernel takes (a product over (T, L, d) writes them positions
    # first, the order XLA keeps the residual stream in)
    x = layout.with_layout_constraint(
        h.astype(dtype), layout.Layout((0, 1, 2))).reshape(T * L, d)
    o = core(*(product(params[n], x) for n in ("query", "key", "value")),
             segments)
    return product(params["out"], o).reshape(T, L, d)
