"""Retirement lanes, tag, export: wait + tag + forward stages, mean per
frame (scores landed to the downstream consume returning)."""


def read(obs):
    return obs.stage_mean_ms("wait", "tag", "forward")
