"""Model step on the device's own clock: mean duration of the window's
executable runs (XLA Modules events), in ms. Read by hosttrace.py from
the traced window (obs.host); nothing to read in a trace without the
program's engine/* annotations."""


def read(obs):
    host = getattr(obs, "host", None)
    return None if host is None else host.step_ms
