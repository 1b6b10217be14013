"""Scoring engine, the call behind the one ahead: from the end of a call's
engine/enqueue annotation to the start of the executable run that served
it, mean over the window's joined calls, in ms. Not reported where under
95% of the calls join a run (hosttrace.JOIN_FLOOR)."""

from benchmark.hosttrace import JOIN_FLOOR


def read(obs):
    host = getattr(obs, "host", None)
    if host is None or host.joined_share < JOIN_FLOOR:
        return None
    return host.queue_ms
