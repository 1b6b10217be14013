"""Model step, routing: XLA Ops time under the scopes the architecture
folds into ``route`` (for ``moe_decoder``: the router's product, the top-k
and its softmax, the sort by expert, the gather into expert order and the
weighted combine back), mean per executable run of the window, in ms.
None where the architecture has no such part."""


def read(obs):
    host = getattr(obs, "host", None)
    if host is None or "route" not in host.fold.values():
        return None
    return host.part_ms("route")
