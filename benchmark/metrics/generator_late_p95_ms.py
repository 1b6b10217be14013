"""How late the generator ran: 95th percentile of sent minus due."""
import numpy as np


def read(obs):
    return float(np.percentile(obs.late_ms, 95)) if len(obs.late_ms) else None
