"""Scoring engine, the host's wait for the device: device + harvest stages
(dispatch to scores fetched), mean per frame. Host clock: it holds the
device call and whatever the call queued behind, not device time."""


def read(obs):
    return obs.stage_mean_ms("device", "harvest")
