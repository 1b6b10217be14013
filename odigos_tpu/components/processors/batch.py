"""Batch processor.

Every generated pipeline in the reference ends its processor chain with
`batch` (autoscaler/controllers/clustercollector/configmap.go base config;
SURVEY.md §3.3). Ours accumulates SpanBatches and flushes a single
concatenated batch when either `send_batch_size` spans are pending or
`timeout_s` elapses — the concat is the cheap columnar merge from pdata, so
downstream stages (featurizer!) always see large, TPU-friendly batches.

`timeout_s` bounds how long a span is held for batching on its way through
the PROCESS, not by each batch processor it meets (a gateway's data-stream
pipeline and its destination pipeline each end in one): a batch that leaves
here carries the instant its oldest member first entered a batch processor,
and a downstream one counts its own `timeout_s` from that instant. A batch
from anywhere else carries none and is held from the moment it is consumed.
"""

from __future__ import annotations

import copy
import threading
import time
from typing import Any, Optional

from ...pdata import concat_any
from ...pdata.spans import SpanBatch
from ...selftelemetry.flow import FlowContext
from ...utils.telemetry import labeled_key, meter
from ..api import Capabilities, ComponentKind, Factory, Processor, register

FLUSH_METRIC = "odigos_batch_flush_total"
# why a buffer was let go: it reached send_batch_size; its timer fired;
# or the time its oldest member had been held upstream had already used
# up this processor's timeout_s (no timer was armed for it)
FLUSH_REASONS = ("size", "timeout", "inherited")

# the instant rides in the batch's __dict__, beside the frozen pdata
# classes' own caches: not a field, so no column, no attribute, not in
# eq/repr, and gone after dataclasses.replace — a stage that rebuilds
# the batch drops it and the next hold counts from its own consume
_SINCE = "_batched_since"


def batched_since(batch: Any) -> Optional[float]:
    """``time.monotonic()`` at which the oldest span of ``batch`` first
    entered a batch processor of this process; None if it has met none."""
    return batch.__dict__.get(_SINCE)


class BatchProcessor(Processor):
    capabilities = Capabilities(mutates_data=False)

    # incremental hot reload (ISSUE 14): every sizing knob retunes live
    # — buffered spans are kept, the next consume/tick sees new bounds
    RECONFIGURABLE_KEYS = frozenset({
        "send_batch_size", "send_batch_max_size", "timeout_s"})

    def __init__(self, name: str, config: dict[str, Any]):
        super().__init__(name, config)
        self._lock = threading.Lock()
        self._pending: list[SpanBatch] = []
        self._pending_spans = 0
        # earliest batched_since of the pending batches; None when empty
        self._since: Optional[float] = None
        self._timer: Optional[threading.Timer] = None
        self._apply_sizing(config)
        self._wm_name: str | None = None
        self._flush_keys: dict[str, str] | None = None

    def _apply_sizing(self, config: dict[str, Any]) -> None:
        # ONE parse routine for __init__ and reconfigure — a default
        # changed in one place only would otherwise retune a reloaded
        # node differently from a freshly built one
        self.send_batch_size = int(config.get("send_batch_size", 8192))
        self.send_batch_max_size = int(config.get("send_batch_max_size",
                                                  0))
        self.timeout_s = float(config.get("timeout_s", 0.2))

    def _watermark_name(self) -> str:
        # resolved lazily: the graph stamps _flow_site after construction
        name = self._wm_name
        if name is None:
            name = self._wm_name = FlowContext.watermark_name(self)
        return name

    def _count_flush(self, reason: str) -> None:
        keys = self._flush_keys
        if keys is None:
            # resolved lazily, as the watermark name is: two pipelines'
            # `batch` stages must not share a series
            site = getattr(self, "_flow_site", None)
            keys = self._flush_keys = {
                r: labeled_key(FLUSH_METRIC, processor=self.name,
                               pipeline=site[0] if site else "(none)",
                               reason=r) for r in FLUSH_REASONS}
        meter.add(keys[reason])

    def reconfigure(self, config: dict[str, Any]) -> None:
        """Live retune (ISSUE 14): pending spans are NOT dropped — a
        shrunk send_batch_size flushes immediately if the buffer
        already crosses the new bound, and the flush timer is re-armed
        for what the NEW timeout leaves of the buffer's hold (an armed
        old-timeout timer — or no timer at all when timeout was 0 —
        would keep governing the current buffer)."""
        with self._lock:
            self.config = config
            self._apply_sizing(config)
            to_send, since, reason = self._size_or_rearm_locked(
                time.monotonic())
        if to_send:
            self._send(to_send, since, reason)

    def consume(self, batch: SpanBatch) -> None:
        now = time.monotonic()
        carried = batched_since(batch)
        since = now if carried is None else min(carried, now)
        to_send: list[SpanBatch] = []
        reason = ""
        with self._lock:
            self._pending.append(batch)
            self._pending_spans += len(batch)
            FlowContext.watermark(self._watermark_name(), "pending_spans",
                                  self._pending_spans)
            # the deadline moves only when this batch opens the buffer
            # or is older than everything in it
            moved = self._since is None or since < self._since
            if moved:
                self._since = since
            if (moved or self._timer is None
                    or self._pending_spans >= self.send_batch_size):
                to_send, since, reason = self._size_or_rearm_locked(now)
        if to_send:
            self._send(to_send, since, reason)

    def _size_or_rearm_locked(
            self, now: float) -> tuple[list[SpanBatch], float, str]:
        """The one flush rule of consume and reconfigure: take the buffer
        if it is full, or if ``timeout_s`` from its oldest member's
        instant has already passed; else (re-)arm the timer for what is
        left of it. Returns (taken, their instant, reason) — nothing
        taken means the buffer is empty or waits on its timer."""
        if self._pending_spans >= self.send_batch_size:
            return *self._take_locked(), "size"
        if self._timer is not None:
            self._timer.cancel()
            self._timer = None
        if self._pending and self.timeout_s > 0:
            left = self._since + self.timeout_s - now
            if left <= 0:
                return *self._take_locked(), "inherited"
            self._timer = threading.Timer(left, self._flush_timer)
            self._timer.daemon = True
            self._timer.start()
        return [], 0.0, ""

    def _take_locked(self) -> tuple[list[SpanBatch], Optional[float]]:
        """Empty the buffer: (its batches, the earliest instant among
        them; None with nothing pending)."""
        taken, since = self._pending, self._since
        self._pending = []
        self._pending_spans = 0
        self._since = None
        # reset the CURRENT watermark reading: admission gates watch it
        # live, and a stale pre-flush peak would keep shedding upstream
        FlowContext.watermark(self._watermark_name(), "pending_spans", 0)
        if self._timer is not None:
            self._timer.cancel()
            self._timer = None
        return taken, since

    def _flush_timer(self) -> None:
        with self._lock:
            self._timer = None
            taken, since = self._take_locked()
        if taken:
            try:
                self._send(taken, since, "timeout")
            except Exception:
                # downstream refusal on the timer thread: the caller that
                # could retry is long gone — count + drop, never kill the
                # timer path (retries belong to exporters' own queues)
                meter.add("odigos_batch_dropped_on_flush_total"
                          f"{{processor={self.name}}}")

    def _send(self, batches: list[SpanBatch], since: float,
              reason: str = "") -> None:
        """Merge and forward, every piece stamped with ``since``. An
        explicit ``flush()`` gives no reason and is not counted: it is
        a drain, not a batching decision."""
        if reason:
            self._count_flush(reason)
        merged = concat_any(batches)
        if not merged:
            return
        max_size = self.send_batch_max_size
        if max_size and len(merged) > max_size:
            # contiguous chunks: slice() hands out column VIEWS (numpy
            # basic slicing + attr-store entry slices) — the old
            # take(arange(lo, hi)) copied every column per chunk
            for lo in range(0, len(merged), max_size):
                piece = merged.slice(lo, min(lo + max_size, len(merged)))
                object.__setattr__(piece, _SINCE, since)
                self.next_consumer.consume(piece)
            return
        if batched_since(merged) != since:
            if any(merged is b for b in batches):
                # a lone pending batch is handed on as it came, and the
                # caller's other consumers may hold it too: stamp a
                # shallow copy (fields and caches shared), never theirs
                merged = copy.copy(merged)
            object.__setattr__(merged, _SINCE, since)
        self.next_consumer.consume(merged)

    def flush(self) -> None:
        with self._lock:
            taken, since = self._take_locked()
        if taken:
            self._send(taken, since)

    def flow_pending(self) -> int:
        """Spans buffered here, not yet forwarded — the conservation
        checker's in-flight term (selftelemetry/flow.py). A downstream
        refusal on the timer path needs no extra ledger call: the
        out-edge already counted those spans as failed."""
        with self._lock:
            return self._pending_spans

    def shutdown(self) -> None:
        self.flush()
        super().shutdown()


register(Factory(
    type_name="batch",
    kind=ComponentKind.PROCESSOR,
    create=BatchProcessor,
    default_config=lambda: {
        "send_batch_size": 8192, "send_batch_max_size": 0, "timeout_s": 0.2},
))
