"""Operations the configuration needs for given traffic: a function of
the model's sizes and of the traces scored, never of the program's
shapes. Padding, rungs and recomputation do not count."""

from __future__ import annotations

import json
import os
from typing import Any, Iterable

HERE = os.path.dirname(os.path.abspath(__file__))


def flops_per_span(model: dict[str, Any]) -> float:
    """Matrix products one span passes through: per layer the four
    d x d attention projections and the two d x d_ff feed-forward
    products, 2 operations per multiply-add; the embedder's continuous
    projection and the span head on top."""
    d, ff, n = model["d_model"], model["d_ff"], model["n_layers"]
    return 2.0 * n * (4 * d * d + 2 * d * ff) + 2.0 * (3 * d + d)


def attention_flops(model: dict[str, Any], length: int) -> float:
    """Attention over one trace piece of ``length`` spans: per layer
    q k^T and a v, each length^2 x d multiply-adds."""
    return 2.0 * model["n_layers"] * 2 * length * length * model["d_model"]


def flops_needed(model: dict[str, Any], piece_lengths: Iterable[int],
                 ) -> float:
    """All operations for traces cut into pieces of these lengths (a
    trace of up to ``max_len`` spans is one piece)."""
    per_span = flops_per_span(model)
    return sum(n * per_span + attention_flops(model, n)
               for n in piece_lengths)


def peaks(device_kind: str) -> dict[str, float]:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["by_device_kind"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in "
                       f"benchmark/peaks.json; add it with its source")
    return table[device_kind]
