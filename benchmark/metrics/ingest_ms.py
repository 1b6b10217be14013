"""Wire receiver and admission: admission + decode + submit stages of
the program's waterfall, mean per frame (host clock)."""


def read(obs):
    return obs.stage_mean_ms("admission", "decode", "submit")
