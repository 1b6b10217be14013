"""XLA cost/efficiency ledger (ISSUE 20 device-plane observability).

The fused route (PR 17) made the hot path one opaque jitted call; this
module gives it a measurement basis. At warm time — the ladder warming
pass, or a fused bucket's first (cold-key) dispatch, which *is* that
bucket's warm moment — the jit site's lowered computation is asked for
XLA's own cost model (``cost_analysis()`` of the lowering, or of the
executable on a TPU, which prices only those: FLOPs and bytes accessed
for the whole fusion) and, when the capture is armed for it, the
compiled executable's ``memory_analysis()`` (argument/output/temp
bytes). Rows are keyed ``(site, bucket)`` where the bucket is the
padded XLA shape the site compiled for (``r{rows}x{len}`` on the packed
route, ``r{rung}`` on the warm ladder).

At serve time the engine feeds each fused frame's measured device stamp
back in; the ledger publishes:

* ``odigos_xla_flops`` / ``odigos_xla_bytes_accessed`` — the static
  expectation per site x bucket;
* ``odigos_xla_flop_waste_frac`` — FLOPs spent on padding rows
  (1 - n_real/n_padded), the FLOP twin of ``padding_waste_frac``;
* ``odigos_xla_achieved_efficiency`` — achieved FLOP/s for the frame
  joined against the best FLOP/s ever observed for the site
  (self-normalized: the best-known bucket reads 1.0, everything else
  reads its fraction of that — how far each bucket runs from what the
  hardware demonstrably does on this very computation).

Capture runs on the serving path (a cold dispatch), so it never
raises — but it tells its two ways of writing no row apart. A backend
that exposes no analysis (jax returns ``None``) is *unavailable*: the
skip is counted. Anything that raises — a callable that is not a jit,
a lowering the compiler refuses — is a *failure*: counted separately,
its exception text kept in the snapshot and logged, so that an empty
ledger on a new backend says why it is empty.
Deliberately jax-free at import time, like jitstats.
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Any, Optional

from ..utils.telemetry import labeled_key, meter

XLA_FLOPS_METRIC = "odigos_xla_flops"
XLA_BYTES_METRIC = "odigos_xla_bytes_accessed"
XLA_WASTE_METRIC = "odigos_xla_flop_waste_frac"
XLA_EFFICIENCY_METRIC = "odigos_xla_achieved_efficiency"

# keep the ledger bounded: sites x buckets is small by construction (the
# bucket ladder caps live shapes), but a misbehaving caller must not
# grow an unbounded dict
MAX_ROWS = 256

_log = logging.getLogger(__name__)


class CostLedger:
    """Expected-vs-achieved cost rows per jit site x shape bucket."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._rows: dict[tuple, dict] = {}
        self._best_flops_per_s: dict[str, float] = {}
        self._skipped = 0
        self._failed = 0
        self._last_error: Optional[str] = None

    # ---------------------------------------------------------- capture

    def capture(self, site: str, bucket: str, fn: Any, args: tuple = (),
                kwargs: Optional[dict] = None, *,
                n_real: Optional[int] = None,
                n_padded: Optional[int] = None,
                memory: bool = False) -> Optional[dict]:
        """Lower ``fn`` (a jitted callable) for ``args`` and record
        XLA's cost model for the (site, bucket). Backends that price a
        lowering answer from ``Lowered.cost_analysis()``; those that
        only price executables (the TPU: it returns ``None`` there) are
        asked again through ``Lowered.compile()``, which ``memory=True``
        needs anyway for ``memory_analysis()``. Callers capture right
        after the jit's own first call for the shape, and jax then
        hands ``compile()`` the executable that call built: no second
        XLA compile (v5e, persistent cache off: 4-11 ms a capture,
        0.03 s for four rungs and a fused key). Returns the row, or
        None when no row was written."""
        try:
            lowered = fn.lower(*args, **(kwargs or {}))
            cost = lowered.cost_analysis()
            compiled = lowered.compile() \
                if (cost is None or memory) else None
            if cost is None:
                cost = compiled.cost_analysis()
            mem = None
            if memory:
                stats = compiled.memory_analysis()
                if stats is not None:
                    mem = {
                        k: int(getattr(stats, f"{k}_in_bytes"))
                        for k in ("generated_code_size", "argument_size",
                                  "output_size", "temp_size")}
        except Exception as e:  # noqa: BLE001 — a cold dispatch keeps serving
            error = f"{site}[{bucket}]: {type(e).__name__}: {e}"
            with self._lock:
                self._failed += 1
                self._last_error = error
            _log.warning("xla cost capture failed for %s", error)
            return None
        if cost is None:  # backend exposes no analysis
            with self._lock:
                self._skipped += 1
            return None
        flops = float(cost.get("flops", 0.0))
        bytes_accessed = float(cost.get("bytes accessed", 0.0))
        if flops <= 0.0 and bytes_accessed <= 0.0:
            with self._lock:
                self._skipped += 1
            return None
        row = {
            "site": site,
            "bucket": bucket,
            "flops": flops,
            "bytes_accessed": bytes_accessed,
            "memory": mem,
            "n_real": n_real,
            "n_padded": n_padded,
            "flop_waste_frac": self._waste(n_real, n_padded),
            "observations": 0,
            "last_device_ms": None,
            "achieved_flops_per_s": None,
            "efficiency": None,
            "t": time.time(),
        }
        with self._lock:
            if (site, bucket) not in self._rows and \
                    len(self._rows) >= MAX_ROWS:
                self._skipped += 1
                return None
            self._rows[(site, bucket)] = row
        meter.set_gauge(labeled_key(XLA_FLOPS_METRIC,
                                    site=site, bucket=bucket), flops)
        meter.set_gauge(labeled_key(XLA_BYTES_METRIC,
                                    site=site, bucket=bucket),
                        bytes_accessed)
        if row["flop_waste_frac"] is not None:
            meter.set_gauge(labeled_key(XLA_WASTE_METRIC,
                                        site=site, bucket=bucket),
                            row["flop_waste_frac"])
        return row

    @staticmethod
    def _waste(n_real: Optional[int], n_padded: Optional[int]):
        if not n_real or not n_padded or n_padded <= 0:
            return None
        return round(max(0.0, 1.0 - float(n_real) / float(n_padded)), 6)

    # ---------------------------------------------------------- observe

    def observe_device_ms(self, site: str, bucket: str, device_ms: float,
                          *, n_real: Optional[int] = None,
                          n_padded: Optional[int] = None) -> Optional[float]:
        """Join a measured device stamp against the captured expectation
        and publish the live efficiency gauge. Returns the efficiency
        (or None when the (site, bucket) was never captured)."""
        if device_ms <= 0.0:
            return None
        with self._lock:
            row = self._rows.get((site, bucket))
            if row is None:
                return None
            achieved = row["flops"] / (device_ms / 1e3) \
                if row["flops"] > 0 else 0.0
            best = max(self._best_flops_per_s.get(site, 0.0), achieved)
            if achieved > 0:
                self._best_flops_per_s[site] = best
            efficiency = round(achieved / best, 4) if best > 0 else None
            row["observations"] += 1
            row["last_device_ms"] = round(device_ms, 4)
            row["achieved_flops_per_s"] = achieved
            row["efficiency"] = efficiency
            if n_real is not None:
                row["n_real"] = n_real
            if n_padded is not None:
                row["n_padded"] = n_padded
            waste = self._waste(row["n_real"], row["n_padded"])
            row["flop_waste_frac"] = waste
        if efficiency is not None:
            meter.set_gauge(labeled_key(XLA_EFFICIENCY_METRIC,
                                        site=site, bucket=bucket),
                            efficiency)
        if waste is not None:
            meter.set_gauge(labeled_key(XLA_WASTE_METRIC,
                                        site=site, bucket=bucket), waste)
        return efficiency

    # --------------------------------------------------------- read side

    def row(self, site: str, bucket: str) -> Optional[dict]:
        with self._lock:
            row = self._rows.get((site, bucket))
            return dict(row) if row else None

    def snapshot(self) -> dict:
        with self._lock:
            rows = [dict(r) for r in self._rows.values()]
            best = dict(self._best_flops_per_s)
            skipped, failed = self._skipped, self._failed
            last_error = self._last_error
        rows.sort(key=lambda r: (r["site"], r["bucket"]))
        return {"rows": rows, "best_flops_per_s": best,
                "captures_skipped": skipped, "captures_failed": failed,
                "last_error": last_error}

    def reset(self) -> None:
        with self._lock:
            self._rows.clear()
            self._best_flops_per_s.clear()
            self._skipped = 0
            self._failed = 0
            self._last_error = None


cost_ledger = CostLedger()
