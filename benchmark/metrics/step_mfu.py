"""Whole step against the chip's peak: operations the configuration
needs for the real spans scored in the traced window (opcount.py) over
the device time of the executables that ran (XLA Modules, summed over
the chips) times the bf16 peak of the device kind, in percent."""


def read(obs):
    if obs.device is None or not obs.piece_lengths:
        return None
    module_s = sum(obs.device.module_s)
    if module_s <= 0:
        return None
    return 100.0 * obs.flops_needed() / (module_s * obs.peak_flops())
