"""Device idle share of the traced window, in percent: 1 minus the union
of device operations over the window, on the idlest chip."""


def read(obs):
    if obs.device is None:
        return None
    return 100.0 * obs.device.idle_share_max
