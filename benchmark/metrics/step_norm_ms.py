"""Model step, the norms: XLA Ops time under the scopes the architecture
folds into ``norm`` (for ``looped_decoder``: the four RMS norms of every
block and the final norm that closes every pass, element-wise and bound
by bandwidth), mean per executable run of the window, in ms. None where
the architecture has no such part."""


def read(obs):
    host = getattr(obs, "host", None)
    if host is None or "norm" not in host.fold.values():
        return None
    return host.part_ms("norm")
