"""Scoring engine, the result's way back: from the end of a call's
executable run to the end of its engine/scatter annotation (device to
host, np.asarray, scatter to span rows, the frames signalled), mean over
the window's joined calls, in ms. Not reported where under 95% of the
calls join a run (hosttrace.JOIN_FLOOR)."""

from benchmark.hosttrace import JOIN_FLOOR


def read(obs):
    host = getattr(obs, "host", None)
    if host is None or host.joined_share < JOIN_FLOOR:
        return None
    return host.fetch_ms
