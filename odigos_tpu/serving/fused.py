"""Fused device-side featurize→pack→score (ISSUE 19).

The submit lane hands the engine a decoded frame's raw column views
(:class:`~odigos_tpu.features.featurizer.SpanColumns`) and ONE jitted
XLA computation does everything the host used to: string-table hashing
(via device-resident gather tables), the parent self-join, categorical/
continuous assembly (``featurize_columns_jax``, the numpy featurizer's
device twin), packing into the BucketLadder-bucketed shape, and the
model forward — one device call per coalesced group, no per-span host
work beyond 17 pooled column copies. The computation is pure ``jnp``
ops structured so the matmul core (the model forward it inlines) can
later drop into a Pallas kernel without touching the assembly stages.

Route discipline:

* **Opt-in and kill-switchable.** The non-fused route stays bit-
  identical and default-on; ``fast_path: {fused: true}`` arms this one,
  and ``ODIGOS_FUSED=0`` (read per frame) disarms it live.
* **Fallback ladder.** Any frame the kernel doesn't cover silently
  takes the host route with the reason counted (FALLBACK_REASONS):
  legacy JSON-attr frames, zero-span frames, attr-slot configs,
  misaligned/foreign-dtype columns, a backend with no fused kernel.
* **Parity.** Per-span scores match the host route within the
  documented ULP bound (docs/architecture.md): the single arithmetic
  divergence is duration recomposed from split uint32 clocks in f32
  instead of f64 — ~1e-7 relative on log1p(duration_us), amplified
  only by the model's own Lipschitz factor.

x32 note: serving runs without jax_enable_x64, so every uint64 column
is split host-side into uint32 (lo, hi) halves — a zero-copy
``view(uint32)`` on the little-endian contiguous column — and all
device comparisons/sorts treat (hi, lo) pairs as one 64-bit key.
"""

from __future__ import annotations

import os
import threading
from collections import OrderedDict
from typing import Any, Optional

import numpy as np

from ..features.bufferpool import alloc as _alloc
from ..features.featurizer import (FeaturizerConfig, SpanColumns,
                                   batch_columns, featurize_columns_jax,
                                   _hash_table)
from ..pdata.attrstore import AttrDictView
from .engine import SequenceBackend

# jit-site shape discipline (tests/test_package_hygiene.py): the fused
# call's span axis is padded to a geometric bucket (_span_bucket), its
# packed-row axis derived statically from that bucket (next-fit bound:
# two adjacent rows always hold > max_len spans, so 2N/L + 2 rows cover
# any input) and rounded onto the engine's BucketLadder, and the hash
# tables to power-of-two lengths (_table_bucket) — steady-state traffic
# reuses a handful of precompiled XLA shapes.
SHAPE_BUCKETING = {
    "fused_score": "span axis padded to a geometric power-of-two bucket "
                   "(_span_bucket); packed-row axis static per span "
                   "bucket via the 2N/L + 2 next-fit bound rounded by "
                   "BucketLadder.round_rows; hash tables padded to "
                   "power-of-two lengths (_table_bucket); rows is a "
                   "static argname",
}

# the closed set of reasons a frame takes the host route instead; the
# fast path counts each fallback under exactly one of these (metric
# odigos_fastpath_fused_fallback_total{reason=...})
FALLBACK_REASONS = (
    "disabled",            # ODIGOS_FUSED=0 kill switch
    "backend",             # backend has no fused kernel (mock/zscore/mesh)
    "legacy_attrs",        # JSON attr frames (no AttrDictView store)
    "attr_slots",          # attr-slot features need the host attr matrix
    "zero_span",           # empty frame: nothing to score
    "misaligned_columns",  # non-contiguous / foreign-dtype u64 columns
)

# host-route vs fused-route score parity, per serving precision (all
# pinned by tests/test_fused.py). float32: the device twin's f32
# split-clock duration is a few ULP off the host's f64, which the forward
# cannot amplify past ~1e-5 relative — (rtol, atol). Narrower operands:
# the same few ULP can flip a rounding boundary, so single spans may move
# by a quantum while the population agrees tightly — (max |d|, mean |d|).
# bfloat16 is set from the v5e's reading on the flagship (max 2.9e-3,
# mean 1.1e-5 over 2.6k-10k-span groups) with ~3x / ~9x headroom; int8's
# quantum is coarser and keeps the bound its own test was sized for.
PARITY_F32 = (2e-5, 1e-6)
PARITY_BF16 = (1e-2, 1e-4)
PARITY_INT8 = (0.05, 5e-3)


def served_precision(backend: Any) -> str:
    """The narrowest operands the backend's matmuls see: ``int8`` for a
    quantized model, ``bfloat16`` for a bfloat16 model anywhere and for
    ANY model on a TPU (whose default matmul precision rounds float32
    operands to bfloat16), else ``float32``."""
    import jax

    if backend._quantized is not None:
        return "int8"
    if (np.dtype(backend.model.cfg.dtype).itemsize < 4
            or jax.default_backend() == "tpu"):
        return "bfloat16"
    return "float32"


def routes_agree(got: np.ndarray, want: np.ndarray, precision: str) -> bool:
    """The parity verdict for one group scored on both routes."""
    if precision == "float32":
        return bool(np.allclose(got, want, rtol=PARITY_F32[0],
                                atol=PARITY_F32[1]))
    max_d, mean_d = {"bfloat16": PARITY_BF16, "int8": PARITY_INT8}[precision]
    diff = np.abs(got - want)
    return bool(diff.max() < max_d and diff.mean() < mean_d)


# the uint64 columns the device kernel splits host-side; each must be a
# C-contiguous little-endian uint64 array or the split view is invalid
_U64_COLUMNS = ("span_id", "parent_span_id", "trace_id_hi", "trace_id_lo",
                "start_unix_nano", "end_unix_nano")


def fused_enabled() -> bool:
    """Live kill switch: ``ODIGOS_FUSED=0`` disarms the fused route per
    frame (no restart, no reconfigure) — the operator's big red button
    when a device kernel misbehaves mid-incident."""
    return os.environ.get("ODIGOS_FUSED", "1") != "0"


def extract_columns(batch: Any, config: Optional[FeaturizerConfig] = None
                    ) -> tuple[Optional[SpanColumns], Optional[str]]:
    """The fallback ladder's gate: the frame's :class:`SpanColumns` view
    if the fused kernel covers it, else ``(None, reason)`` with reason
    drawn from :data:`FALLBACK_REASONS`. Zero-copy on success."""
    config = config or FeaturizerConfig()
    if len(batch) == 0:
        return None, "zero_span"
    if config.attr_slots:
        # attr-slot features gather through the batch's attr store on
        # the host; the device kernel has no columnar view of it
        return None, "attr_slots"
    if not isinstance(batch.span_attrs, AttrDictView):
        # legacy JSON-attr decode (attr_format="json" or hand-built
        # batches): per-span dicts, not a columnar store — the host
        # route's featurize handles them unchanged
        return None, "legacy_attrs"
    for name in _U64_COLUMNS:
        col = batch.col(name)
        if col.dtype != np.uint64 or not col.flags.c_contiguous:
            # the u64→2×u32 split is a zero-copy view that only exists
            # for contiguous native-layout columns (in-place-protected
            # or sliced-with-stride frames fail here)
            return None, "misaligned_columns"
    return batch_columns(batch), None


def _span_bucket(n: int) -> int:
    """Geometric span-axis bucket: power of two, floor 512 — bounds the
    set of compiled span counts the same way the BucketLadder bounds
    packed row counts."""
    b = 512
    while b < n:
        b <<= 1
    return b


def _table_bucket(n: int) -> int:
    """Hash-table axis bucket (power of two, floor 1024): table length
    would otherwise leak every sender's string-pool size into the jit
    shape key."""
    b = 1024
    while b < n:
        b <<= 1
    return b


def _split_u64(col: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """(lo, hi) uint32 halves of a contiguous little-endian uint64
    column — zero-copy views, validated by :func:`extract_columns`."""
    v = col.view(np.uint32).reshape(-1, 2)
    return v[:, 0], v[:, 1]


# value-keyed LRU of device-resident table pairs; a hand-rolled
# OrderedDict (vs functools.lru_cache) so the cache can also answer
# "how many device bytes do these tables pin?" for the footprint gauge
_TABLE_LRU = 32
_table_lock = threading.Lock()
_table_cache: OrderedDict = OrderedDict()


def _device_tables(strings: tuple[str, ...], service_vocab: int,
                   name_vocab: int):
    """Device-resident hash gather tables for one interned string pool,
    padded to the power-of-two table bucket. Memoized by value like the
    host ``_hash_table`` (wire senders re-ship the same pools), so a
    steady sender set hashes + uploads each pool exactly once and the
    fused call's tables are warm device constants thereafter."""
    key = (strings, service_vocab, name_vocab)
    with _table_lock:
        hit = _table_cache.get(key)
        if hit is not None:
            _table_cache.move_to_end(key)
            return hit[0], hit[1]

    import jax.numpy as jnp

    svc = _hash_table(strings, service_vocab)
    nam = _hash_table(strings, name_vocab)
    tb = _table_bucket(len(svc))
    # setup path, not a per-frame allocation: the padded tables live in
    # the value-keyed LRU and outlive any frame (the same allowlisted
    # stance as featurizer._hash_table)
    svc_p = np.zeros(tb, np.int32)
    nam_p = np.zeros(tb, np.int32)
    svc_p[:len(svc)] = svc
    nam_p[:len(nam)] = nam
    dsvc, dnam = jnp.asarray(svc_p), jnp.asarray(nam_p)
    with _table_lock:
        _table_cache[key] = (dsvc, dnam,
                             int(dsvc.nbytes) + int(dnam.nbytes))
        while len(_table_cache) > _TABLE_LRU:
            _table_cache.popitem(last=False)
    return dsvc, dnam


def device_table_bytes() -> int:
    """Device bytes currently pinned by the resident gather tables —
    the fused route's invisible-since-PR-17 footprint, published as
    ``odigos_device_table_bytes{site=fused.tables}`` by the device
    runtime collector."""
    with _table_lock:
        return sum(entry[2] for entry in _table_cache.values())


class FusedSequenceBackend(SequenceBackend):
    """SequenceBackend plus the fused columns→scores dispatch.

    ``dispatch_columns`` replaces the host featurize+pack with 17 pooled
    column copies and one jitted device call; everything else — the
    coalesce/harvest split, the ladder, failover, warm() — rides the
    parent unchanged, and ``dispatch``/``score`` remain the bit-exact
    host route every fallback frame takes.
    """

    def __init__(self, cfg, mesh: Any = None):
        super().__init__(cfg, mesh=mesh)
        self._fused_score_jit = None
        self.fused_site: Optional[str] = None
        # (span bucket, rows) shapes this backend has already compiled —
        # the fused analogue of BucketLadder's warm set, for bucket_hit
        self._fused_shapes: OrderedDict = OrderedDict()
        # sampled intra-fused attribution (ISSUE 20): armed by config,
        # built lazily so the import stays jax-free on the off path
        self._attrib = None
        self.last_attrib: Optional[dict] = None
        self.last_span_bucket: Optional[int] = None
        if getattr(cfg, "device_attribution", False):
            from .deviceattrib import DeviceAttribution
            self._attrib = DeviceAttribution(
                self, getattr(cfg, "device_attribution_stride", 32))

    @property
    def supports_fused(self) -> bool:
        """Whether ``dispatch_columns`` covers this configuration: the
        mesh partition plan keeps its own sharded call graph, and
        attr-slot features need the host attr matrix."""
        return self._plan is None and self.cfg.featurizer.attr_slots == 0

    # --------------------------------------------------- fused dispatch

    def dispatch_columns(self, cols_list: list[SpanColumns]) -> Any:
        """Fused pack stage: pooled column staging + ONE non-blocking
        device call that featurizes, packs, and scores. Returns an
        opaque handle for ``harvest``. ``cols_list`` is the coalesced
        group in request order; scores come back in the concatenated
        original span order."""
        n_real = sum(len(c) for c in cols_list)
        N = _span_bucket(n_real)
        L = self.max_len
        if self.cfg.model == "transformer":
            # static row bound: next-fit never closes two adjacent rows
            # holding <= L spans total, so 2N/L + 2 rows always fit the
            # padded span bucket — rounded onto the warm ladder rungs
            R = self.ladder.round_rows(2 * N // L + 2)
        else:
            # sequence route: one row per trace; a trace has >= 1 span,
            # so the span bucket itself bounds the trace count
            R = N
        tables, arrays = self._prep_columns(cols_list, N)
        self.last_shape = [R, L]
        # density is a device-side fact now; the host never scatters the
        # mask, so padding waste is unknowable here (reported as absent)
        self.last_padding_waste = None
        key = (N, R)
        self.last_bucket_hit = key in self._fused_shapes
        self._fused_shapes[key] = True
        if len(self._fused_shapes) > 16:
            self._fused_shapes.popitem(last=False)
        self.last_span_bucket = N
        variables = self._fused_variables()
        fn = self._fused_score()
        sample = self._attrib is not None and self._attrib.tick()
        if not sample:
            # the PR 17 hot path, untouched: one non-blocking call
            self.last_attrib = None
            dev = fn(variables, *tables, *arrays, rows=R)
        else:
            dev, self.last_attrib = self._attrib.run(
                fn, variables, tables, arrays, R, n_real)
        if not self.last_bucket_hit:
            # this bucket's warm moment: capture XLA's cost model for
            # the shape (no second compile: jax reuses the lowering and
            # the executable the call above just built)
            from ..models.costmodel import cost_ledger
            cost_ledger.capture(
                self.fused_site or "fused", f"r{R}x{L}", fn,
                (variables, *tables, *arrays), {"rows": R},
                n_real=n_real, n_padded=N,
                memory=self._attrib is not None)
        return ("fused", dev, n_real)

    def harvest(self, handle: Any) -> np.ndarray:
        if handle[0] == "fused":
            _, dev, n = handle
            # the one blocking host<->device fetch; scores are already
            # in concatenated original span order (the kernel's inverse
            # scatter), so the engine's per-request split applies as-is
            return np.asarray(dev, dtype=np.float32)[:n]
        return super().harvest(handle)

    # ---------------------------------------------------- host staging

    def _prep_columns(self, cols_list: list[SpanColumns], N: int):
        """Stage the group's columns into pooled (N,) arrays: int32
        ids/ordinals + the uint64 columns split into uint32 halves.
        Runs inside the engine's pack lease, so a warmed frame stages
        allocation-free. Returns ``(device tables, 17-tuple of arrays
        in _impl argument order)``."""
        fcfg = self.cfg.featurizer
        if len(cols_list) == 1:
            svc_tab, nam_tab = _device_tables(
                cols_list[0].strings, fcfg.service_vocab, fcfg.name_vocab)
            tab_lens = [0]  # single pool: indices need no base offset
        else:
            # per-frame tables concatenated with per-frame base offsets
            # (each frame's service/name indices address its own pool)
            host_tabs = [(_hash_table(c.strings, fcfg.service_vocab),
                          _hash_table(c.strings, fcfg.name_vocab))
                         for c in cols_list]
            tab_lens = [len(t[0]) for t in host_tabs]
            tb = _table_bucket(sum(tab_lens))
            svc_tab = _alloc((tb,), np.int32)
            nam_tab = _alloc((tb,), np.int32)
            off = 0
            for (st, nt), k in zip(host_tabs, tab_lens):
                svc_tab[off:off + k] = st
                nam_tab[off:off + k] = nt
                off += k
            svc_tab[off:] = 0
            nam_tab[off:] = 0

        svc = _alloc((N,), np.int32)
        nam = _alloc((N,), np.int32)
        kind = _alloc((N,), np.int32)
        status = _alloc((N,), np.int32)
        frame = _alloc((N,), np.int32)
        u32 = [_alloc((N,), np.uint32) for _ in range(12)]
        (span_lo, span_hi, par_lo, par_hi, start_lo, start_hi,
         end_lo, end_hi, thi_lo, thi_hi, tlo_lo, tlo_hi) = u32

        off = 0
        tab_off = 0
        for fi, c in enumerate(cols_list):
            k = len(c)
            sl = slice(off, off + k)
            np.add(c.service, np.int32(tab_off), out=svc[sl])
            np.add(c.name, np.int32(tab_off), out=nam[sl])
            kind[sl] = c.kind
            status[sl] = c.status_code
            frame[sl] = fi
            for (lo_a, hi_a), col in (
                    ((span_lo, span_hi), c.span_id),
                    ((par_lo, par_hi), c.parent_span_id),
                    ((start_lo, start_hi), c.start_unix_nano),
                    ((end_lo, end_hi), c.end_unix_nano),
                    ((thi_lo, thi_hi), c.trace_id_hi),
                    ((tlo_lo, tlo_hi), c.trace_id_lo)):
                lo, hi = _split_u64(col)
                lo_a[sl] = lo
                hi_a[sl] = hi
            off += k
            if fi < len(tab_lens):
                tab_off += tab_lens[fi]
        for arr in (svc, nam, kind, status, *u32):
            arr[off:] = 0
        frame[off:] = -1  # padding marker (drives is_pad device-side)

        return (svc_tab, nam_tab), (svc, nam, kind, status, span_lo,
                                    span_hi, par_lo, par_hi, start_lo,
                                    start_hi, end_lo, end_hi, thi_lo,
                                    thi_hi, tlo_lo, tlo_hi, frame)

    # ------------------------------------------------------ device side

    def _fused_variables(self):
        # the int8 scorer closes over its own quantized weights; handing
        # it the bf16 variables too would transfer them every call
        return None if self._quantized is not None else self.variables

    def _fused_score(self):
        if self._fused_score_jit is None:
            import jax

            from ..models import jitstats

            site = ("fused.score_packed"
                    if self.cfg.model == "transformer"
                    else "fused.score_spans")
            self.fused_site = site
            self._fused_score_jit = jitstats.track_jit(
                site, jax.jit(self._build_fused_impl(),
                              static_argnames=("rows",)))
        return self._fused_score_jit

    def _build_fused_impl(self):
        """The single fused computation: featurize (device twin) →
        trace-sort → pack (next-fit via searchsorted + pointer-doubling
        row marking) → model forward → inverse scatter to original span
        order. Pure jnp, static shapes; the model forward it inlines is
        the seam a Pallas kernel can later replace.

        Composed from the module-level phase builders below — the same
        functions the device attribution sampler jits one-by-one — so
        the fused jaxpr is by construction identical to the sum of its
        attributable sub-stages."""
        transformer = self.cfg.model == "transformer"
        pack = _build_pack_packed(self.max_len) if transformer \
            else _build_pack_spans(self.max_len)
        fwd = _build_forward_packed(self.model, self._quantized) \
            if transformer else _build_forward_spans(self.model)

        def _impl(variables, service_table, name_table, svc, nam, kind,
                  status, span_lo, span_hi, par_lo, par_hi, start_lo,
                  start_hi, end_lo, end_hi, thi_lo, thi_hi, tlo_lo,
                  tlo_hi, frame, *, rows):
            cat, cont = featurize_columns_jax(
                service_table, name_table, svc, nam, kind, status,
                span_hi, span_lo, par_hi, par_lo, end_hi, end_lo,
                start_hi, start_lo, frame)
            packed = pack(cat, cont, start_lo, start_hi, thi_lo, thi_hi,
                          tlo_lo, tlo_hi, frame, rows=rows)
            return fwd(variables, *packed, rows=rows)

        return _impl


# ------------------------------------------------- fused phase builders
#
# PACK and FORWARD as standalone jnp functions, closed over the static
# geometry/model exactly like the old inline body. ``_build_fused_impl``
# composes them under one jit (identical trace to the pre-split code);
# serving/deviceattrib.py jits each one separately to stamp the
# sampled intra-fused waterfall.


def _sorted_trace_layout(start_lo, start_hi, thi_lo, thi_hi, tlo_lo,
                         tlo_hi, frame):
    """Shared head of both pack routes: the trace-major/time-minor sort
    and per-trace position arithmetic."""
    import jax
    import jax.numpy as jnp

    n = frame.shape[0]
    is_pad = frame < 0
    # trace-major, time-minor sort — the host pack's
    # np.lexsort((start, lo, hi)) over split keys, with is_pad
    # primary so padding sorts last and (crucially) never merges
    # into a real trace that happens to carry trace id 0
    perm = jnp.lexsort((start_lo, start_hi, tlo_lo, tlo_hi,
                        thi_lo, thi_hi, is_pad))
    pad_s = is_pad[perm]
    thh = thi_hi[perm]
    thl = thi_lo[perm]
    tlh = tlo_hi[perm]
    tll = tlo_lo[perm]
    new_trace = jnp.concatenate([
        jnp.ones(1, bool),
        (thh[1:] != thh[:-1]) | (thl[1:] != thl[:-1])
        | (tlh[1:] != tlh[:-1]) | (tll[1:] != tll[:-1])
        | (pad_s[1:] != pad_s[:-1])])
    idx = jnp.arange(n)
    # first sorted index of each trace, forward-filled — the
    # vectorized cumcount the host gets from run_starts/repeat
    first_idx = jax.lax.cummax(jnp.where(new_trace, idx, 0))
    pos_in_trace = idx - first_idx
    return perm, pad_s, new_trace, pos_in_trace


def _build_pack_spans(L: int):
    """Sequence-route (autoencoder) pack: one row per trace, truncation
    at L via the scatter's mode="drop" (same spans the host's keep-mask
    drops)."""

    def _pack(cat, cont, start_lo, start_hi, thi_lo, thi_hi, tlo_lo,
              tlo_hi, frame, *, rows):
        import jax.numpy as jnp

        perm, pad_s, new_trace, pos_in_trace = _sorted_trace_layout(
            start_lo, start_hi, thi_lo, thi_hi, tlo_lo, tlo_hi, frame)
        cat_s = cat[perm]
        cont_s = cont[perm]
        C = cat.shape[1]
        D = cont.shape[1]
        trace_ord = jnp.cumsum(new_trace) - 1
        row_eff = jnp.where(pad_s, rows, trace_ord)
        col = pos_in_trace
        catp = jnp.zeros((rows, L, C), jnp.int32) \
            .at[row_eff, col].set(cat_s, mode="drop")
        contp = jnp.zeros((rows, L, D), jnp.float32) \
            .at[row_eff, col].set(cont_s, mode="drop")
        mask = jnp.zeros((rows, L), bool) \
            .at[row_eff, col].set(~pad_s, mode="drop")
        return catp, contp, mask, perm, row_eff, col, pad_s

    return _pack


def _build_forward_spans(model):
    """Sequence-route forward: score, squash to (0, 1) in-kernel (the
    host does it at harvest), inverse-scatter to original span order."""

    def _forward(variables, catp, contp, mask, perm, row_eff, col,
                 pad_s, *, rows):
        import jax.numpy as jnp

        L = catp.shape[1]
        n = perm.shape[0]
        errs, _ = model.score_spans(variables, catp, contp, mask)
        sq = 1.0 - jnp.exp(-errs)
        safe_row = jnp.minimum(row_eff, rows - 1)
        safe_col = jnp.minimum(col, L - 1)
        val = jnp.where(pad_s | (col >= L), 0.0,
                        sq[safe_row, safe_col])
        return jnp.zeros(n, jnp.float32).at[perm].set(val)

    return _forward


def _build_pack_packed(L: int):
    """Packed-route (transformer / quantized) pack: chunk each trace
    into <= L-span segments, then next-fit segments into rows."""

    def _pack(cat, cont, start_lo, start_hi, thi_lo, thi_hi, tlo_lo,
              tlo_hi, frame, *, rows):
        import jax
        import jax.numpy as jnp

        perm, pad_s, new_trace, pos_in_trace = _sorted_trace_layout(
            start_lo, start_hi, thi_lo, thi_hi, tlo_lo, tlo_hi, frame)
        cat_s = cat[perm]
        cont_s = cont[perm]
        C = cat.shape[1]
        D = cont.shape[1]
        n = frame.shape[0]
        pos_in_chunk = (pos_in_trace % L).astype(jnp.int32)
        seg_new = pos_in_chunk == 0
        span_seg = jnp.cumsum(seg_new) - 1
        seg_len = jax.ops.segment_sum(
            jnp.ones(n, jnp.int32), span_seg, num_segments=n)
        cum = jnp.cumsum(seg_len)
        cum_prev = cum - seg_len
        # next-fit: a row starting at segment s ends before the
        # first segment whose cumulative length exceeds the row
        # budget — the device twin of the host's bisect_right over
        # cum (side="right" also skips the zero-length tail)
        nxt = jnp.minimum(
            jnp.searchsorted(cum, cum_prev + L, side="right"),
            n).astype(jnp.int32)
        # row starts = the orbit of segment 0 under nxt, computed by
        # pointer doubling (log2 rounds replace the host's per-row
        # Python loop); n is the self-looping "done" sentinel
        ptr = jnp.concatenate([nxt, jnp.full((1,), n, jnp.int32)])
        marked = jnp.zeros(n + 1, bool).at[0].set(True)
        for _ in range(max(int(n).bit_length() + 1, 1)):
            hit = jax.ops.segment_sum(
                marked.astype(jnp.int32), ptr,
                num_segments=n + 1) > 0
            marked = marked | hit
            ptr = ptr[ptr]
        is_start = marked[:n]
        row_of_seg = jnp.cumsum(is_start.astype(jnp.int32)) - 1
        base = jax.lax.cummax(jnp.where(is_start, cum_prev, 0))
        seg_off = cum_prev - base
        seg_idx = jnp.arange(n)
        seg_slot = (seg_idx - jax.lax.cummax(
            jnp.where(is_start, seg_idx, 0)) + 1).astype(jnp.int32)
        span_row = row_of_seg[span_seg]
        span_col = seg_off[span_seg] + pos_in_chunk
        row_eff = jnp.where(pad_s, rows, span_row)
        catp = jnp.zeros((rows, L, C), jnp.int32) \
            .at[row_eff, span_col].set(cat_s, mode="drop")
        contp = jnp.zeros((rows, L, D), jnp.float32) \
            .at[row_eff, span_col].set(cont_s, mode="drop")
        segs = jnp.zeros((rows, L), jnp.int32) \
            .at[row_eff, span_col].set(seg_slot[span_seg],
                                       mode="drop")
        poss = jnp.zeros((rows, L), jnp.int32) \
            .at[row_eff, span_col].set(pos_in_chunk, mode="drop")
        return catp, contp, segs, poss, perm, row_eff, span_col, pad_s

    return _pack


def _build_forward_packed(model, quantized):
    """Packed-route forward: the (possibly int8-quantized) transformer
    matmul core — the Pallas seam — plus the inverse scatter."""

    def _forward(variables, catp, contp, segs, poss, perm, row_eff,
                 span_col, pad_s, *, rows):
        import jax.numpy as jnp

        L = catp.shape[1]
        n = perm.shape[0]
        if quantized is not None:
            mat = quantized.score_packed(catp, contp, segs, poss)
        else:
            mat = model.score_packed(variables, catp, contp, segs,
                                     poss)
        safe_row = jnp.minimum(row_eff, rows - 1)
        safe_col = jnp.clip(span_col, 0, L - 1)
        val = jnp.where(pad_s, 0.0, mat[safe_row, safe_col])
        return jnp.zeros(n, jnp.float32).at[perm].set(val)

    return _forward
