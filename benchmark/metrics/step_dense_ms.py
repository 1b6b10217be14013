"""Model step, the dense feed-forwards beside the routed experts: XLA Ops
time under the scopes the architecture folds into ``dense`` (for
``latent_moe_decoder``: the shared expert every span takes in a routed
layer and the leading dense layers' feed-forward), mean per executable
run of the window, in ms. None where the architecture has no such part."""


def read(obs):
    host = getattr(obs, "host", None)
    if host is None or "dense" not in host.fold.values():
        return None
    return host.part_ms("dense")
