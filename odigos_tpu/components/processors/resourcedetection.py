"""``resourcedetection`` processor — stamp detected environment facts
onto every resource.

Upstream's resourcedetectionprocessor (collector/builder-config.yaml:79)
runs a detector chain at startup (env, system, process, cloud...) and
merges the detected attributes into each batch's resources.  Detection
here happens ONCE at build time (upstream does the same — detectors run
in Start), then process() is a cheap merge over the resource side-list.
The ``tpu`` detector is the exception: it may only read device facts
once THIS process owns a JAX backend, so it resolves on the first batch
that arrives after the process's own engine has initialised one.

Config::

    resourcedetection:
      detectors: [env, system, process]   # order = precedence (first wins
                                          # unless override)
      override: false                     # replace existing keys?
      attributes: {extra.key: value}      # static additions (ours)

Detectors:

* ``env``     — OTEL_RESOURCE_ATTRIBUTES (k=v,k=v; the upstream env
                detector contract)
* ``system``  — host.name, os.type
* ``process`` — process.pid, process.executable.name,
                process.runtime.name/version
* ``tpu``     — odigos.tpu.present + device count when this process's
                own scoring engine runs on accelerator devices
                (tpu-native analog of the upstream gcp/eks cloud
                detectors). A collector whose scorer is the remote
                sidecar detects nothing: the chip belongs to one process
                at a time, and looking would take it from the sidecar.
"""

from __future__ import annotations

import os
import platform
import sys
from typing import Any

from ...utils.jaxruntime import backend_initialized
from ..api import Capabilities, ComponentKind, Factory, Processor, register


def _detect_env() -> dict[str, Any]:
    raw = os.environ.get("OTEL_RESOURCE_ATTRIBUTES", "")
    out: dict[str, Any] = {}
    for pair in raw.split(","):
        if "=" in pair:
            k, v = pair.split("=", 1)
            if k.strip():
                out[k.strip()] = v.strip()
    return out


def _detect_system() -> dict[str, Any]:
    return {"host.name": platform.node(),
            "os.type": sys.platform}


def _detect_process() -> dict[str, Any]:
    return {
        "process.pid": os.getpid(),
        "process.executable.name": os.path.basename(sys.executable),
        "process.runtime.name": platform.python_implementation().lower(),
        "process.runtime.version": platform.python_version(),
    }


def _detect_tpu() -> dict[str, Any]:
    """Device facts of the backend this process ALREADY owns; callers
    check ``backend_initialized()`` first, so this never creates one."""
    import jax

    accel = [d for d in jax.devices() if d.platform not in ("cpu",)]
    if not accel:
        return {}
    return {"odigos.tpu.present": True,
            "odigos.tpu.device_count": len(accel),
            "odigos.tpu.platform": accel[0].platform}


_DETECTORS = {
    "env": _detect_env,
    "system": _detect_system,
    "process": _detect_process,
}
# resolved lazily in process(), never at build time (see module docstring)
_DEFERRED_DETECTORS = {"tpu": _detect_tpu}


class ResourceDetectionProcessor(Processor):
    """See module docstring."""

    capabilities = Capabilities(mutates_data=True)

    def __init__(self, name: str, config: dict[str, Any]):
        super().__init__(name, config)
        names = config.get("detectors") or ["env", "system"]
        known = {**_DETECTORS, **_DEFERRED_DETECTORS}
        unknown = [n for n in names if n not in known]
        if unknown:
            raise ValueError(
                f"unknown resource detectors {unknown}; "
                f"available: {sorted(known)}")
        self.override = bool(config.get("override", False))
        detected: dict[str, Any] = {}
        # first listed detector wins on key collisions (upstream order
        # precedence), so later detectors only setdefault
        for n in names:
            if n in _DETECTORS:
                for k, v in _DETECTORS[n]().items():
                    detected.setdefault(k, v)
        self._pending_tpu = "tpu" in names
        for k, v in (config.get("attributes") or {}).items():
            detected.setdefault(str(k), v)
        self.detected = detected

    def process(self, batch: Any) -> Any:
        if self._pending_tpu and backend_initialized():
            self._pending_tpu = False
            for k, v in _detect_tpu().items():
                self.detected.setdefault(k, v)
        if not self.detected or not hasattr(batch, "resources"):
            return batch
        if not len(batch):
            return batch
        from dataclasses import replace

        resources = []
        changed = False
        for r in batch.resources:
            merged = dict(r)
            for k, v in self.detected.items():
                if self.override:
                    if merged.get(k) != v:
                        merged[k] = v
                        changed = True
                elif k not in merged:
                    merged[k] = v
                    changed = True
            resources.append(merged)
        if not changed:
            return batch
        return replace(batch, resources=tuple(resources))


register(Factory(
    type_name="resourcedetection",
    kind=ComponentKind.PROCESSOR,
    create=ResourceDetectionProcessor,
    default_config=lambda: {"detectors": ["env", "system"]},
))
