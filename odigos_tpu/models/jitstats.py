"""Per-jit-site compile accounting (ISSUE 3 device-runtime telemetry).

Every jitted scoring/training entry point already declares its shape-
bucketing strategy (``SHAPE_BUCKETING``, package-hygiene test); this
module adds the runtime half: which jit sites exist as live compiled
functions, how many cached executables each holds (one per traced input
shape — the cache growing past the declared bucket ladder is the
unbounded-recompile hazard showing up live), and how many cumulative
seconds each site has spent compiling (observed where code can see a
compile happen: ladder warming, a fused cold key).

Deliberately jax-free at import time: the DeviceRuntimeCollector reads
these tables from a telemetry thread that must never be the reason jax
(or a device runtime) gets initialized. Tracked functions are held by
weakref — accounting must not extend executable lifetimes.
"""

from __future__ import annotations

import threading
import time
import weakref
from collections import deque
from typing import Any, Callable, Optional

_lock = threading.Lock()
# site -> weakref to the jitted callable (PjitFunction exposes
# _cache_size(); absent/changed API degrades to "size unknown")
_tracked: dict[str, Any] = {}
# site -> cumulative observed compile seconds
_compile_seconds: dict[str, float] = {}

# ---- compile events (ISSUE 20): every compile this module already
# times is also a first-class event — ring-buffered here for the
# /api/device surface and the latency ledger's exemplar join, mirrored
# into the flight recorder's always-on ring, and watched by a rolling
# storm detector that freezes an incident bundle when unplanned
# (non-warm) recompiles burst mid-soak.
COMPILE_RING = 64
STORM_WINDOW_S = 30.0
# >= this many *unplanned* compiles inside the window trips the trigger
# (ladder warming and attribution sub-stage first-compiles are recorded
# warm=True and never count — a planned warm pass is not a storm)
STORM_THRESHOLD = 4
# startup grace: cold shape ramp right after the first compile of the
# process (fused buckets warming off real traffic) is expected, not a
# storm — only events this long after the first one arm the detector
STORM_GRACE_S = 90.0
COMPILE_EVENTS_METRIC = "odigos_jit_compile_events_total"

_compile_events: deque = deque(maxlen=COMPILE_RING)
_storm_times: deque = deque()
_storm_shapes: deque = deque(maxlen=STORM_THRESHOLD * 2)
_first_event_mono: Optional[float] = None


def track_jit(site: str, fn: Callable) -> Callable:
    """Register a jitted callable under a stable site name and return it
    unchanged (wrap-at-assignment idiom: the jit site passes its freshly
    built compiled function through here)."""
    try:
        ref = weakref.ref(fn)
    except TypeError:  # some wrappers refuse weakrefs: drop tracking
        return fn
    with _lock:
        _tracked[site] = ref
    return fn


def record_compile_seconds(site: str, seconds: float) -> None:
    """Accumulate observed compile time for a site (ladder warm passes,
    a fused cold key's dispatch)."""
    if seconds <= 0:
        return
    with _lock:
        _compile_seconds[site] = _compile_seconds.get(site, 0.0) + seconds


def record_compile_event(site: str, seconds: float, *,
                         shape: Optional[str] = None,
                         trace_id: Optional[str] = None,
                         warm: bool = False) -> None:
    """A compile happened: accumulate its seconds, ring-buffer the event
    (site / bucket shape / duration / the triggering frame's self-trace
    id), mirror it into the flight recorder, and feed the storm
    detector. ``warm=True`` marks planned compiles (ladder warming,
    attribution sub-stage first-builds) which never count toward a
    storm. Never raises — this runs on the scoring path."""
    if seconds <= 0:
        return
    record_compile_seconds(site, seconds)
    now = time.time()
    mono = time.monotonic()
    event = {
        "site": site,
        "seconds": round(float(seconds), 6),
        "shape": shape,
        "trace_id": trace_id,
        "warm": bool(warm),
        "t": now,
    }
    storm_shapes: Optional[list] = None
    global _first_event_mono
    with _lock:
        if _first_event_mono is None:
            _first_event_mono = mono
        _compile_events.append(dict(event, t_mono=mono))
        if not warm and mono - _first_event_mono > STORM_GRACE_S:
            _storm_times.append(mono)
            _storm_shapes.append(f"{site}:{shape}" if shape else site)
            while _storm_times and mono - _storm_times[0] > STORM_WINDOW_S:
                _storm_times.popleft()
            if len(_storm_times) >= STORM_THRESHOLD:
                storm_shapes = sorted(set(_storm_shapes))
    try:
        from ..utils.telemetry import labeled_key, meter
        meter.add(labeled_key(COMPILE_EVENTS_METRIC,
                              site=site, warm=str(bool(warm)).lower()))
        from ..selftelemetry.flightrecorder import flight_recorder
        flight_recorder.record("compile", **event)
        if storm_shapes is not None:
            flight_recorder.trigger(
                "compile_storm",
                detail=(f"{len(storm_shapes)} shape(s) recompiled within "
                        f"{STORM_WINDOW_S:.0f}s: {', '.join(storm_shapes)}"),
                rule="jitstats.compile_storm",
                expr=(f"unwarmed_compiles >= {STORM_THRESHOLD} "
                      f"in {STORM_WINDOW_S:.0f}s"),
                shapes=storm_shapes, site=site)
    except Exception:  # noqa: BLE001 — accounting must never break scoring
        pass


def recent_compiles(site: Optional[str] = None,
                    shape: Optional[str] = None) -> list:
    """Ring-buffered compile events, newest first, optionally filtered
    by site and/or bucket shape (the latency ledger's exemplar join asks
    for the worst fused frame's bucket)."""
    with _lock:
        events = list(_compile_events)
    out = []
    for ev in reversed(events):
        if site is not None and ev["site"] != site:
            continue
        if shape is not None and ev["shape"] != shape:
            continue
        out.append({k: v for k, v in ev.items() if k != "t_mono"})
    return out


def cache_sizes() -> dict[str, int]:
    """Live jit-cache executable count per tracked site. Dead refs are
    pruned; callables without a readable cache size report -1 (tracked,
    size unknown) rather than vanishing."""
    out: dict[str, int] = {}
    with _lock:
        dead = []
        for site, ref in _tracked.items():
            fn = ref()
            if fn is None:
                dead.append(site)
                continue
            size = getattr(fn, "_cache_size", None)
            try:
                out[site] = int(size()) if callable(size) else -1
            except Exception:  # noqa: BLE001 — private API drifted
                out[site] = -1
        for site in dead:
            del _tracked[site]
    return out


def compile_seconds() -> dict[str, float]:
    with _lock:
        return dict(_compile_seconds)


def reset() -> None:
    """Test hook: drop accumulated seconds, the event ring, and the
    storm detector's state. ``_tracked`` is deliberately KEPT: sites
    register at module import (zscore/autoencoder kernels) — exactly
    once per process — so clearing the registry here would permanently
    blind ``cache_sizes()`` to them for every later test in the suite.
    Dead refs are pruned on read; stale entries cost nothing."""
    with _lock:
        _compile_seconds.clear()
        _compile_events.clear()
        _storm_times.clear()
        _storm_shapes.clear()
        global _first_event_mono
        _first_event_mono = None
