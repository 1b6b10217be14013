"""The experts' grouped products against the chip's roofline: the least
time the chip could take for them (the larger of their operations over
the bf16 peak and their least bytes over the memory's peak, for the real
spans scored in the traced window; both from the architecture's own
``experts_flops`` and ``experts_bytes``, so the same work whatever
implements the products) over the summed device time of the operations
that fold into ``mlp``, in percent. None where the architecture states no
such work or the trace holds no such part."""


def read(obs):
    from benchmark import opcount

    host, arch = getattr(obs, "host", None), getattr(obs, "arch", None)
    if host is None or not hasattr(arch, "experts_flops") \
            or not obs.piece_lengths:
        return None
    spent = host.part_s("mlp")            # chip-seconds, every chip
    if spent <= 0:
        return None
    peaks = opcount.peaks(obs.device_kind)
    spans = sum(obs.piece_lengths)
    least = max(
        arch.experts_flops(obs.model, spans) / peaks["bf16_flops_per_s"],
        arch.experts_bytes(obs.model, spans, host.n_runs * host.n_dev)
        / peaks["hbm_bytes_per_s"])
    return 100.0 * least / spent
