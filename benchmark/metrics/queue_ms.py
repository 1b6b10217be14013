"""Scoring engine queue and coalescer: enqueue + queue stages, mean per
frame."""


def read(obs):
    return obs.stage_mean_ms("enqueue", "queue")
