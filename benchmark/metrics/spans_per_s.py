"""Spans of the window that reached the exporter scored, over the
window (first in-window send to last in-window arrival)."""


def read(obs):
    return obs.scored_spans / obs.window_s if obs.window_s > 0 else None
