"""Serving-bundle bridge (training/checkpoint.py): save/restore round trip,
metadata-driven model rebuild, and the engine's checkpoint_path seam — the
fast-path coverage for the loop that tests/test_northstar_auc.py proves at
full model scale (round-1 review item 1).
"""

import numpy as np
import pytest

from odigos_tpu.models import TransformerConfig
from odigos_tpu.pdata import synthesize_traces
from odigos_tpu.serving import EngineConfig, ScoringEngine
from odigos_tpu.training import (
    TrainConfig, Trainer, load_bundle, make_model_config, save_bundle)


TINY = {"d_model": 64, "n_layers": 1, "d_ff": 128, "n_heads": 2,
        "max_len": 16}


@pytest.fixture(scope="module")
def tiny_bundle(tmp_path_factory):
    cfg = TrainConfig(steps=2, traces_per_step=8, max_len=16, seed=3,
                      warmup_steps=1, model_kwargs=dict(TINY))
    tr = Trainer(cfg)
    res = tr.train()
    path = tr.export(str(tmp_path_factory.mktemp("ck") / "b"), res.variables)
    return tr, res, path


def test_bundle_round_trip(tiny_bundle):
    tr, res, path = tiny_bundle
    b = load_bundle(path)
    assert b.model == "transformer"
    assert b.model_config.d_model == 64 and b.model_config.max_len == 16
    import jax

    leaves_saved = jax.tree.leaves(res.variables)
    leaves_back = jax.tree.leaves(b.variables)
    assert len(leaves_saved) == len(leaves_back)
    for a, c in zip(leaves_saved, leaves_back):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))


def test_engine_loads_bundle_geometry(tiny_bundle):
    _, res, path = tiny_bundle
    eng = ScoringEngine(EngineConfig(model="transformer",
                                     checkpoint_path=path))
    backend = eng.backend
    assert backend.model.cfg.d_model == 64
    assert backend.max_len == 16  # model geometry wins over engine default
    batch = synthesize_traces(5, seed=9)
    from odigos_tpu.features import featurize

    scores = backend.score(batch, featurize(batch))
    assert scores.shape == (len(batch),)
    assert np.isfinite(scores).all() and (scores >= 0).all()


def test_engine_rejects_model_mismatch(tiny_bundle):
    _, _, path = tiny_bundle
    with pytest.raises(ValueError, match="transformer"):
        ScoringEngine(EngineConfig(model="autoencoder",
                                   checkpoint_path=path))


def test_load_bundle_rejects_non_bundle(tmp_path):
    with pytest.raises(FileNotFoundError, match="serving bundle"):
        load_bundle(str(tmp_path))


def test_make_model_config_validation():
    cfg = make_model_config("transformer", {"d_model": 32, "dtype": "float32"})
    assert isinstance(cfg, TransformerConfig) and cfg.d_model == 32
    with pytest.raises(TypeError):
        make_model_config("transformer", {"not_a_field": 1})
    with pytest.raises(ValueError, match="unsupported checkpoint dtype"):
        make_model_config("transformer", {"dtype": "int8"})
    with pytest.raises(ValueError, match="no config class"):
        make_model_config("zscore", {})


def test_processor_model_config_from_pipeline_config():
    from odigos_tpu.components.processors.tpuanomaly import TpuAnomalyProcessor

    proc = TpuAnomalyProcessor("tpuanomaly", {
        "model": "transformer", "model_config": dict(TINY),
        "shared_engine": False})
    assert proc.engine_cfg.model_config.d_model == 64
    assert proc.engine.backend.max_len == 16
