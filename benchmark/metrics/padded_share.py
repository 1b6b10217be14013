"""Share of the slots the engine dispatched that held no span, from the
program's own tpu/score spans (batch.spans, device.shape), in percent."""


def read(obs):
    slots = sum(rows * length for _, rows, length in obs.score_calls)
    if not slots:
        return None
    return 100.0 * (1.0 - sum(s for s, _, _ in obs.score_calls) / slots)
