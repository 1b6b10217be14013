"""95th percentile over every frame of the window of
due-to-last-arrival; a failed frame counts as the deadline."""
import numpy as np


def read(obs):
    return float(np.percentile(obs.latency_ms, 95)) if len(obs.latency_ms) \
        else None
