"""Model step, the latent projections: XLA Ops time under the scopes the
architecture folds into ``latent`` (for ``latent_moe_decoder``: the four
low-rank products that make queries, keys and values, the two latent
norms, the rotary of the rotary columns, the shared key's broadcast and
the concatenations), mean per executable run of the window, in ms. None
where the architecture has no such part."""


def read(obs):
    host = getattr(obs, "host", None)
    if host is None or "latent" not in host.fold.values():
        return None
    return host.part_ms("latent")
