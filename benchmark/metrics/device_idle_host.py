"""Device idle that the engine's worker stood in the way of: time in which
no executable runs on the chip while the worker is inside engine/pack,
enqueue, harvest or scatter, as a share of the traced window, in percent.
What is left of device_idle.* is idle with the worker in engine/collect
(no work offered), before the first and after the last run, or between
the operations of one executable."""


def read(obs):
    host = getattr(obs, "host", None)
    if host is None or not host.window_s:
        return None
    return 100.0 * host.idle_host_s / host.window_s
