"""Train→serve checkpoint bridge (the serving-bundle format).

The reference keeps all resumable state in CRDs and has no model artifacts
(SURVEY.md §5.4); model checkpoints are the durability requirement the TPU
scoring stage adds. This module is the seam between the trainer's
step-indexed orbax CheckpointManager (training/trainer.py) and the serving
engine (serving/engine.py SequenceBackend): an exported **serving bundle**
is a directory holding

    <dir>/variables/   orbax StandardCheckpointer tree (model variables only,
                       no optimizer state)
    <dir>/model.json   {"model": "transformer" | "autoencoder",
                        "config": {<dataclass fields, dtype by name>}}

so serving rebuilds the exact model geometry (vocab sizes, d_model, max_len)
from the artifact instead of requiring the pipeline config to re-specify it —
the config→processor seam of the reference's
odigossamplingprocessor/factory.go:13, where the factory alone knows how to
turn config into a runnable component.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Any, Optional

MODEL_META_FILE = "model.json"
VARIABLES_DIR = "variables"


# ------------------------------------------------------------- model config

def _dtype_name(dtype: Any) -> str:
    import numpy as np

    return np.dtype(dtype).name


def _resolve_dtype(name: str) -> Any:
    import jax.numpy as jnp

    table = {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
             "float16": jnp.float16, "float64": jnp.float64}
    try:
        return table[name]
    except KeyError:
        raise ValueError(f"unsupported checkpoint dtype {name!r} "
                         f"(known: {sorted(table)})") from None


def config_to_dict(model_config: Any) -> dict[str, Any]:
    """JSON-safe dict of a TransformerConfig/AutoencoderConfig."""
    d = dataclasses.asdict(model_config)
    for key in ("dtype", "param_dtype"):
        if key in d:
            d[key] = _dtype_name(d[key])
    return d


def make_model_config(model: str, fields: Optional[dict[str, Any]] = None):
    """Build the frozen config dataclass for ``model`` from plain-dict
    fields (e.g. a pipeline-config ``model_config`` block or a bundle's
    model.json). Unknown keys are rejected so config typos fail loudly."""
    fields = dict(fields or {})
    for key in ("dtype", "param_dtype"):
        if isinstance(fields.get(key), str):
            fields[key] = _resolve_dtype(fields[key])
    if model == "transformer":
        from ..models import TransformerConfig

        return TransformerConfig(**fields)
    if model == "autoencoder":
        from ..models import AutoencoderConfig

        return AutoencoderConfig(**fields)
    raise ValueError(f"model {model!r} has no config class "
                     "(known: transformer, autoencoder)")


# ----------------------------------------------------------------- save/load

def save_bundle(path: str, variables: Any, *, model: str,
                model_config: Any) -> str:
    """Write a serving bundle; returns the absolute bundle path."""
    import jax
    import orbax.checkpoint as ocp

    path = os.path.abspath(path)
    os.makedirs(path, exist_ok=True)
    ck = ocp.StandardCheckpointer()
    vdir = os.path.join(path, VARIABLES_DIR)
    # the artifact must be device-agnostic: numpy leaves carry no sharding
    # metadata, so a bundle trained on TPU restores in a CPU-only process
    # (and vice versa) without device resolution
    import numpy as np

    ck.save(vdir, jax.tree.map(np.asarray, variables), force=True)
    ck.wait_until_finished()
    meta = {"model": model, "config": config_to_dict(model_config)}
    with open(os.path.join(path, MODEL_META_FILE), "w") as f:
        json.dump(meta, f, indent=2, sort_keys=True)
    return path


@dataclasses.dataclass(frozen=True)
class ServingBundle:
    model: str            # "transformer" | "autoencoder"
    model_config: Any     # TransformerConfig | AutoencoderConfig
    variables: Any        # restored variables pytree


def load_bundle(path: str) -> ServingBundle:
    """Load a serving bundle written by :func:`save_bundle`."""
    path = os.path.abspath(path)
    meta_path = os.path.join(path, MODEL_META_FILE)
    if not os.path.exists(meta_path):
        raise FileNotFoundError(
            f"{path} is not a serving bundle (missing {MODEL_META_FILE}); "
            "export one with Trainer.export() / save_bundle()")
    with open(meta_path) as f:
        meta = json.load(f)
    cfg = make_model_config(meta["model"], meta.get("config"))
    return ServingBundle(model=meta["model"], model_config=cfg,
                         variables=restore_variables(path))


def restore_variables(path: str, template: Any = None) -> Any:
    """Restore the variables pytree from a bundle directory (or directly
    from an orbax StandardCheckpointer directory)."""
    import orbax.checkpoint as ocp

    path = os.path.abspath(path)
    vdir = os.path.join(path, VARIABLES_DIR)
    if not os.path.isdir(vdir):
        vdir = path  # raw orbax dir, no bundle wrapper
    ck = ocp.StandardCheckpointer()
    if template is None:
        # derive a host-side template from checkpoint metadata so restore
        # never resolves saved device/sharding info (a TPU-trained bundle
        # must load in a CPU-only sidecar)
        try:
            import jax
            import numpy as np

            tree = ck.metadata(vdir).item_metadata.tree
            template = jax.tree.map(
                lambda m: np.zeros(m.shape, m.dtype), tree)
        except Exception:
            return ck.restore(vdir)  # pre-metadata orbax: best effort
    return ck.restore(vdir, template)
