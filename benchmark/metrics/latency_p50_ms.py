"""Median over every frame of the window of due-to-last-arrival."""
import numpy as np


def read(obs):
    return float(np.percentile(obs.latency_ms, 50)) if len(obs.latency_ms) \
        else None
