"""What arrived against what was sent, and against the plain reference.

``tally`` reads the exporter's arrivals of one window: per frame the
arrival time of its last span and how many of its spans came, came once
and came with a score. ``compare`` sets every served score beside the
reference's for the same span and returns the numbers ``correct`` is
decided by, each with its limit.
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Any, Optional

import numpy as np

from . import gen

SCORE_ATTR = "odigos.anomaly.score"   # the program's span attribute


@dataclass
class Tally:
    serial: np.ndarray        # (F,) the window's frames
    pool_index: np.ndarray    # (F,)
    sent_spans: np.ndarray    # (F,) spans each frame holds
    arrived: np.ndarray       # (F,) distinct spans of it that arrived
    scored: np.ndarray        # (F,) of those, how many carry a score
    copies: int               # arrivals beyond the first of a span
    strangers: int            # arrivals that belong to no frame sent
    last_arrival: np.ndarray  # (F,) host clock, nan if none arrived
    # every distinct arrived span: frame position, local id, served score
    span_frame: np.ndarray
    span_local: np.ndarray
    span_score: np.ndarray    # nan where the span carries no score

    @property
    def whole(self) -> np.ndarray:
        """Frames all of whose spans arrived once, scored."""
        return (self.arrived == self.sent_spans) \
            & (self.scored == self.sent_spans)

    @property
    def delivery_faults(self) -> int:
        """Spans sent that never arrived, plus the copies and strangers
        that should not have arrived at all: the exactly-once guarantee.
        (A span that arrives unscored is late, not wrong: it counts in
        ``failed``, not here.)"""
        return int((self.sent_spans - self.arrived).sum()) + self.copies \
            + self.strangers


def tally(log, records: list[tuple[float, Any]], sizes: list[int]) -> Tally:
    serial = np.asarray(log.serial, np.int64)
    pool_index = np.asarray(log.pool_index, np.int64)
    F = len(serial)
    sent_spans = np.asarray([sizes[i] for i in pool_index], np.int64)
    first = int(serial[0]) if F else 0
    his, ids, vals, ts = [], [], [], []
    for t, batch in records:
        n = len(batch)
        if not n:
            continue
        his.append(batch.col("trace_id_hi").astype(np.int64))
        ids.append(batch.col("span_id").astype(np.int64))
        v, present = batch.attrs().column(SCORE_ATTR)
        v = np.asarray(v, np.float64).copy()
        v[~np.asarray(present, bool)] = np.nan
        vals.append(v)
        ts.append(np.full(n, t))
    if his:
        hi, sid = np.concatenate(his), np.concatenate(ids)
        val, t = np.concatenate(vals), np.concatenate(ts)
    else:
        hi = sid = np.zeros(0, np.int64)
        val = t = np.zeros(0)
    pos = hi - first                       # serials are consecutive
    known = (pos >= 0) & (pos < F)
    strangers = int((~known).sum())
    pos, sid, val, t = pos[known], sid[known], val[known], t[known]
    local = sid - (pos + first) * gen.SERIAL_STRIDE
    key = pos * gen.SERIAL_STRIDE + local
    _, keep = np.unique(key, return_index=True)
    copies = int(len(key) - len(keep))
    last_arrival = np.full(F, np.nan)
    np.fmax.at(last_arrival, pos, t)
    pos_u, local_u, val_u = pos[keep], local[keep], val[keep]
    arrived = np.bincount(pos_u, minlength=F)[:F]
    scored = np.bincount(pos_u[np.isfinite(val_u)], minlength=F)[:F]
    return Tally(serial, pool_index, sent_spans, arrived, scored, copies,
                 strangers, last_arrival, pos_u, local_u, val_u)


SLOPE_FLOOR = 0.01


def logit_gap(score: np.ndarray, reference: np.ndarray) -> np.ndarray:
    """The gap between a score and the reference's, in units of the
    logit: the difference over the sigmoid's slope at the reference,
    ``r (1 - r)``. A randomly initialised model's scores sit within a
    few hundredths of one seed-dependent level, so the same error in the
    logit reads five times smaller in the score at 0.96 than at 0.7; in
    the logit it reads alike on every seed. The slope is floored at 0.01
    (|logit| 4.6) so that beyond it the tagger's rounding of the served
    score to 1e-4 cannot be read as an error of the model."""
    reference = np.asarray(reference, np.float64)
    return (np.asarray(score, np.float64) - reference) / np.maximum(
        reference * (1.0 - reference), SLOPE_FLOOR)


def pool_rows(frame, local: np.ndarray) -> np.ndarray:
    """The row of ``frame`` that each local span id names, -1 where it
    names none."""
    row_of = np.full(int(frame.span_id.max()) + 2, -1, np.int64)
    row_of[frame.span_id.astype(np.int64)] = np.arange(len(frame))
    valid = (local >= 0) & (local < len(row_of))
    return np.where(valid, row_of[np.clip(local, 0, len(row_of) - 1)], -1)


def served_by(tl: Tally, pool: list, scores: list[np.ndarray]) -> Tally:
    """``tl`` as it would read had ``scores`` (one array per pool frame,
    in the frame's span order) been served for every span that arrived
    with a score: the control put in the program's place."""
    out = tl.span_score.copy()
    pidx = tl.pool_index[tl.span_frame]
    for p, frame in enumerate(pool):
        m = (pidx == p) & np.isfinite(out)
        rows = pool_rows(frame, tl.span_local[m])
        out[m] = np.where(rows >= 0, scores[p][np.maximum(rows, 0)], np.nan)
    return replace(tl, span_score=out)


def compare(tl: Tally, pool: list, reference_scores: list[np.ndarray],
            limits: dict[str, float]) -> tuple[bool, dict[str, dict]]:
    """The numbers compared, each beside its limit, and whether all
    that have one hold. ``gap_max`` and ``gap_rms`` are over every span
    of the window that arrived with a score, of ``logit_gap`` between
    the served score and the reference's for that span; a number whose
    limit is None is printed and not judged."""
    ok = np.isfinite(tl.span_score)
    pidx = tl.pool_index[tl.span_frame[ok]]
    local = tl.span_local[ok]
    served = tl.span_score[ok]
    gap = np.zeros(int(ok.sum()))
    unknown = 0
    for p, frame in enumerate(pool):
        m = pidx == p
        if not m.any():
            continue
        rows = pool_rows(frame, local[m])
        unknown += int((rows < 0).sum())
        gap[m] = np.where(
            rows >= 0,
            logit_gap(served[m],
                      reference_scores[p][np.maximum(rows, 0)]),
            1.0 / SLOPE_FLOOR)
    n = max(len(gap), 1)
    numbers = {
        "delivery_faults": float(tl.delivery_faults + unknown),
        "gap_rms": float(np.sqrt(np.sum(gap * gap) / n)),
        "gap_max": float(np.max(np.abs(gap))) if len(gap)
        else 1.0 / SLOPE_FLOOR,
        "spans_compared": float(len(gap)),
    }
    out, good = {}, len(gap) > 0
    for name, value in numbers.items():
        limit = limits.get(name)
        out[name] = {"value": value, "limit": limit}
        if limit is not None and not value <= limit:
            good = False
    return good, out


def latencies_ms(log, tl: Tally, deadline_ms: float) -> np.ndarray:
    """Per frame: due time to the arrival of its last span; a frame that
    failed (a span missing or unscored) counts as the deadline."""
    lat = (tl.last_arrival - np.asarray(log.due)) * 1e3
    return np.where(tl.whole & np.isfinite(lat), lat, deadline_ms)
