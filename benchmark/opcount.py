"""Operations a configuration needs for given traffic, and the chips'
peaks. The count is the architecture's (``architectures/<name>.py``
``flops_by_part``): a function of the model's sizes and of the traces
scored, never of the program's shapes. Padding, rungs and recomputation
do not count."""

from __future__ import annotations

import json
import os
from typing import Any, Iterable

HERE = os.path.dirname(os.path.abspath(__file__))


def flops_needed(arch: Any, model: dict[str, Any],
                 piece_lengths: Iterable[int]) -> float:
    """All operations for traces cut into pieces of these lengths: the
    sum of the architecture's parts, so that the whole and the parts
    cannot drift."""
    return sum(arch.flops_by_part(model, piece_lengths).values())


def peaks(device_kind: str) -> dict[str, float]:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["by_device_kind"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in "
                       f"benchmark/peaks.json; add it with its source")
    return table[device_kind]
