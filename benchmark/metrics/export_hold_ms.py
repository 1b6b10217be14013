"""Retirement lanes, tag, export: what a frame's due-to-arrival holds that
no stage of the program's waterfall does, mean per frame: the wall from the
lane's downstream consume returning to the frame's last span reaching the
terminal exporter (the batch processors' holds, and the wire before
admission). Mean latency less how late the generator sent, less every stage
from admission to forward; None where one of them was not stamped."""

STAGES = ("admission", "decode", "submit", "featurize", "enqueue", "queue",
          "pack", "device", "harvest", "wait", "tag", "forward")


def read(obs):
    if not len(obs.latency_ms) or not len(obs.late_ms):
        return None
    if any(obs.stages.get(s, (0.0, 0))[1] <= 0 for s in STAGES):
        return None
    return float(obs.latency_ms.mean() - obs.late_ms.mean()
                 - obs.stage_mean_ms(*STAGES))
