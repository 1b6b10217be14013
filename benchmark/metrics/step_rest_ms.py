"""Model step, everything else: XLA Ops time under the scopes the
architecture folds into ``rest`` (for ``encoder_preln``: ``embed``,
``attn_mask``, ``final_norm``, ``head``) and under no scope (copies,
hoisted converts), mean per executable run of the window, in ms. With
step_attn_ms and step_mlp_ms it sums to device_step_ms less the gaps
inside a run."""


def read(obs):
    host = getattr(obs, "host", None)
    return None if host is None else host.part_ms("rest")
