"""The paced window's own rate: it must equal the offered rate."""


def read(obs):
    return obs.scored_spans / obs.window_s if obs.window_s > 0 else None
