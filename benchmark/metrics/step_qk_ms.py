"""Model step, the per-head norms and the rotary between the projections
and the attention core: XLA Ops time under the scopes the architecture
folds into ``qk`` (for ``postnorm_moe_decoder``: the RMS norm over each
head's columns of q and of k in every layer, and their rotation in the
layers that rotate), mean per executable run of the window, in ms. None
where the architecture has no such part."""


def read(obs):
    host = getattr(obs, "host", None)
    if host is None or "qk" not in host.fold.values():
        return None
    return host.part_ms("qk")
