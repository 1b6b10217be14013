"""Ingest fast path: featurize + pack stages, mean per frame."""


def read(obs):
    return obs.stage_mean_ms("featurize", "pack")
