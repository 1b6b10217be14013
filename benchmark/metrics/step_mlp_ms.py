"""Model step, feed-forward: XLA Ops time under the scopes the
architecture folds into ``mlp`` (for ``encoder_preln``: LayerNorm, both
Dense, GELU and residual of every block), mean per executable run of the
window, in ms."""


def read(obs):
    host = getattr(obs, "host", None)
    return None if host is None else host.part_ms("mlp")
