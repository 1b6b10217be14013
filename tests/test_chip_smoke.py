"""chip_smoke.py rehearsed on the CPU (ISSUE 21).

The smoke itself only ever passes on a TPU. What tier-1 can hold is the
rest of its contract: the device gate refuses any other platform with a
non-zero exit that names what it found; the same body, handed a
miniature geometry and the platform this suite runs on, passes every
other gate on both scoring routes; and a run that could not have scored
its spans in time (an impossible admission deadline) fails. This is also
the rehearsal the on-chip-measurement guide asks for before chip time is
spent.
"""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

# the miniature lives HERE, not in chip_smoke.py: on the chip nothing
# overrides the rendered stanza
TINY = dict(
    engine_overrides={
        "model_config": {"d_model": 32, "n_heads": 2, "n_layers": 1,
                         "d_ff": 64, "max_len": 16, "dtype": "float32"},
        "max_len": 16, "trace_bucket": 16, "bucket_ladder": 2,
        "max_batch": 256,
        # the engine dies with its collector: a shared one would outlive
        # the test in the live-engine registry other suites read
        "shared_engine": False},
    traces_per_frame=16, senders=2, warm_frames_per_sender=1,
    frames_per_sender=4, attrib_frames_per_sender=3,
    min_frames=8, min_spans=500, settle_s=30.0)


@pytest.fixture
def clean_ledgers():
    """run() resets the process-global ledgers before it starts; leave
    them clean for whichever test runs next, too."""
    yield
    from odigos_tpu.models import jitstats
    from odigos_tpu.models.costmodel import cost_ledger
    from odigos_tpu.selftelemetry.flightrecorder import flight_recorder
    from odigos_tpu.selftelemetry.flow import flow_ledger
    from odigos_tpu.selftelemetry.latency import latency_ledger
    from odigos_tpu.utils.telemetry import meter

    for ledger in (flow_ledger, meter, latency_ledger, flight_recorder,
                   cost_ledger, jitstats):
        ledger.reset()


def _run_script(script, tmp_path, **env):
    return subprocess.run(
        [sys.executable, script], capture_output=True, text=True,
        timeout=120, cwd=os.path.dirname(script),
        env={**os.environ, "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cc"),
             **env})


def _last_line(stdout):
    lines = stdout.strip().splitlines()
    return lines[-1] if lines else ""


class TestDeviceGate:
    def test_refuses_a_platform_that_is_not_tpu(self, tmp_path):
        r = _run_script(os.path.join(REPO, "chip_smoke.py"), tmp_path,
                        JAX_PLATFORMS="cpu")
        assert r.returncode not in (0, None), r.stdout + r.stderr
        assert "found platform 'cpu'" in r.stdout
        assert "nothing was built or measured" in r.stdout
        # it printed what JAX reports, and no result
        assert "device: platform=cpu" in r.stdout
        assert "versions: jax=" in r.stdout
        assert '"ok"' not in _last_line(r.stdout)
        # and it stopped before building anything: no cache was written
        assert not (tmp_path / "cc").exists() \
            or not os.listdir(tmp_path / "cc")

    def test_in_process_gate_is_the_default(self):
        with pytest.raises(SystemExit) as e:
            chip_smoke.run(chip_smoke.Geometry(**TINY))
        assert e.value.code == 2

    def test_alone_in_a_directory_it_fails_without_a_result(self, tmp_path):
        lonely = tmp_path / "lonely"
        lonely.mkdir()
        shutil.copy(os.path.join(REPO, "chip_smoke.py"), lonely)
        r = _run_script(str(lonely / "chip_smoke.py"), tmp_path,
                        PYTHONPATH="")
        assert r.returncode not in (0, None)
        assert "not importable" in r.stderr
        assert '"ok"' not in r.stdout

    def test_no_cpu_route_in_the_scripts(self):
        """The bring-up gate may not take itself off the accelerator."""
        with open(os.path.join(REPO, "chip_smoke.py")) as f:
            src = f.read()
        assert not re.search(
            r"jax_platforms|ensure_host_devices|"
            r"(environ|setdefault|putenv)[^\n]*JAX_PLATFORMS", src)


class TestRehearsal:
    def test_tiny_geometry_passes_every_gate_on_both_routes(
            self, clean_ledgers):
        report = chip_smoke.run(chip_smoke.Geometry(**TINY),
                                expect_platform="cpu")
        assert report["device"]["platform"] == "cpu"
        assert report["geometry"]["d_model"] == 32
        assert report["geometry"]["ladder"] == [16, 32]
        for leg in ("host", "fused", "attrib"):
            got = report["legs"][leg]
            assert got["spans_sent"] == got["spans_exported"] \
                == got["spans_scored"] > 0, (leg, got)
        assert report["legs"]["host"]["frames"] == 8
        assert report["legs"]["attrib"]["sampler"]["sampled"] >= 1
        assert report["parity"]["precision"] == "float32"
        assert report["parity"]["groups"] \
            and all(g["ok"] for g in report["parity"]["groups"])
        # a cost row per warmed rung and per fused key
        rows = report["cost_ledger_rows"]
        assert {"transformer.score_packed[r16]",
                "transformer.score_packed[r32]"} <= set(rows)
        assert any(r.startswith("fused.score_packed[") for r in rows)
        json.dumps(report, default=str)  # the report line serialises

    def test_an_impossible_deadline_fails_the_smoke(self, clean_ledgers):
        """Frames that expire forward unscored — the product's contract,
        and exactly what the smoke must not certify."""
        g = chip_smoke.Geometry(**{**TINY, "deadline_ms": 0.001,
                                   "settle_s": 5.0})
        with pytest.raises(chip_smoke.SmokeFailure) as e:
            chip_smoke.run(g, expect_platform="cpu")
        text = " ".join(e.value.failures)
        assert "[host]" in text
        assert "passthrough grew" in text or "deadline_expired grew" in text

    def test_mesh_leg_on_virtual_devices(self, clean_ledgers):
        """The four-chip leg on this suite's virtual CPU devices: the
        host route serves through the dp plan and the scores span four
        devices. The one gate a CPU cannot meet is the per-device byte
        count (its backend reports no memory stats) — so that, and only
        that, fails here."""
        with pytest.raises(chip_smoke.SmokeFailure) as e:
            chip_smoke.run(chip_smoke.Geometry(**TINY),
                           expect_platform="cpu", mesh_data=4)
        assert len(e.value.failures) == 1, e.value.failures
        assert e.value.failures[0].startswith(
            "[mesh] a device reports no bytes_in_use")

    def test_cost_ledger_is_not_gated_under_a_mesh(self, capsys):
        """A mesh plan has no fused kernel and prices no rung: the
        smoke says so instead of passing a gate that expects nothing."""
        from types import SimpleNamespace

        failures: list = []
        chip_smoke.cost_ledger_gate(
            SimpleNamespace(mesh=object(), backend=None), failures)
        assert not failures
        assert "cost ledger: not gated under a mesh" in capsys.readouterr().out

    def test_a_mesh_wider_than_the_host_fails(self):
        with pytest.raises(chip_smoke.SmokeFailure, match="needs 64"):
            chip_smoke.run(chip_smoke.Geometry(**TINY),
                           expect_platform="cpu", mesh_data=64)
