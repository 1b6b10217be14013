"""What one run observed, in the form the metric readers take it.

A reader is a file ``metrics/<metric name>.py``, or one for the whole
quantity, ``metrics/<name before the last dot>.py``, with one function
``read(obs)`` that returns the metric's value, or ``None`` where it finds
nothing to read (the harness then leaves the metric out of the line).
"""

from __future__ import annotations

import importlib.util
import os
from dataclasses import dataclass, field
from typing import Any, Optional

import numpy as np

from . import opcount

HERE = os.path.dirname(os.path.abspath(__file__))


@dataclass
class Observation:
    model: dict[str, Any]            # the configuration's whole model_config
    chips: int
    device_kind: str
    deadline_ms: float
    window_s: float                  # first in-window send to last arrival
    scored_spans: int                # spans of the window that arrived scored
    latency_ms: np.ndarray           # per frame, due to last arrival
    late_ms: np.ndarray              # per frame, sent minus due
    # per stage of the program's waterfall: (summed ms, frames) in the window
    stages: dict[str, tuple[float, int]] = field(default_factory=dict)
    counters: dict[str, float] = field(default_factory=dict)
    # the program's tpu/score spans of the window: (spans, rows, row length)
    score_calls: list[tuple[int, int, int]] = field(default_factory=list)
    piece_lengths: list[int] = field(default_factory=list)  # scored traces
    device: Any = None               # tracered.DeviceTime of the traced run
    host: Any = None                 # hosttrace.HostTrace of the traced run
    arch: Any = None                 # the configuration's architecture module

    def stage_mean_ms(self, *names: str) -> Optional[float]:
        """Mean per frame of the summed stages; None if none was stamped."""
        got = [self.stages[n] for n in names if n in self.stages
               and self.stages[n][1] > 0]
        if not got:
            return None
        return float(sum(s / c for s, c in got))

    def flops_needed(self) -> float:
        return opcount.flops_needed(self.arch, self.model,
                                    self.piece_lengths)

    def peak_flops(self) -> float:
        return opcount.peaks(self.device_kind)["bf16_flops_per_s"]


def percentile(values: np.ndarray, q: float) -> Optional[float]:
    return float(np.percentile(values, q)) if len(values) else None


def load_reader(metric: str):
    """The reader of ``metric``: ``metrics/<metric>.py``, or the reader
    of its quantity, ``metrics/<name before the last dot>.py``, which
    serves every split of it (``queue_ms.steady``, ``queue_ms.backlog``)."""
    names = [metric] + ([metric.rpartition(".")[0]] if "." in metric else [])
    path = next((p for p in (os.path.join(HERE, "metrics", n + ".py")
                             for n in names) if os.path.exists(p)), None)
    if path is None:
        raise FileNotFoundError(
            f"metric {metric!r} has no reader under {HERE}/metrics "
            f"(looked for {', '.join(n + '.py' for n in names)})")
    spec = importlib.util.spec_from_file_location(
        "benchmark_metric_" + metric.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
