"""Process-level JAX set-up (ISSUE 21): where the compile cache goes,
and who may ask jax about devices.

The cache helper is exercised in fresh interpreters — it must be
observed before a first compile, and this suite's own process has long
since compiled. The ownership guard is exercised both ways: a process
that only imported jax owns nothing; one that asked for its devices
does.
"""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PROBE = (
    "import json, sys; sys.path.insert(0, {repo!r});"
    "from odigos_tpu.utils import jaxruntime as jr;"
    "assert 'jax' not in sys.modules, 'importing the helper imported jax';"
    "used = jr.configure_compile_cache(); import jax;"
    "print(json.dumps({{'used': used,"
    " 'config': jax.config.jax_compilation_cache_dir,"
    " 'min_s': jax.config.jax_persistent_cache_min_compile_time_secs,"
    " 'default': jr.DEFAULT_CACHE_DIR,"
    " 'owned': jr.backend_initialized()}}))"
).format(repo=REPO)


def _probe(**env):
    base = {k: v for k, v in os.environ.items()
            if k not in ("JAX_COMPILATION_CACHE_DIR",
                         "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS")}
    r = subprocess.run([sys.executable, "-c", _PROBE], capture_output=True,
                       text=True, timeout=120, env={**base, **env},
                       cwd="/")
    assert r.returncode == 0, r.stderr
    return json.loads(r.stdout.strip().splitlines()[-1])


class TestCompileCache:
    def test_variable_set_wins_and_nothing_else_is_set(self, tmp_path):
        want = str(tmp_path / "placed-from-outside")
        got = _probe(JAX_COMPILATION_CACHE_DIR=want)
        assert got["used"] == got["config"] == want
        assert got["config"] != got["default"]

    def test_unset_is_one_fixed_path_in_the_checkout(self):
        first, second = _probe(), _probe()
        assert first["used"] == first["config"] == first["default"]
        # identical across processes (the path is part of the cache key)
        assert second["config"] == first["config"]
        assert first["config"] == os.path.join(REPO, ".jax_cache")

    def test_small_programs_are_stored_too(self, tmp_path):
        # the default 1 s floor would never store the fused sub-jits or
        # the floor rung
        for env in ({}, {"JAX_COMPILATION_CACHE_DIR": str(tmp_path)}):
            assert _probe(**env)["min_s"] == 0.0

    def test_a_floor_set_from_outside_is_left_alone(self):
        got = _probe(JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="2.5")
        assert got["min_s"] == 2.5

    def test_fixed_path_is_ignored_by_git_and_by_the_chip_copy(self):
        for listing in (".gitignore", ".chiprunignore"):
            with open(os.path.join(REPO, listing)) as f:
                assert ".jax_cache/" in f.read().split(), listing


class TestBackendOwnership:
    def test_importing_jax_is_not_owning_a_backend(self):
        assert _probe()["owned"] is False

    def test_a_process_that_touched_its_devices_owns_one(self):
        import jax

        from odigos_tpu.utils.jaxruntime import backend_initialized

        jax.devices()
        assert backend_initialized() is True

    def test_remote_scoring_collector_never_claims_a_backend(self, tmp_path):
        """``model: remote`` hands the chip to the sidecar process. A
        collector in front of it — jax imported (a model_config mapping
        does that), the device-runtime collector sampling, the ``tpu``
        resource detector configured — must end its life never having
        initialised a backend of its own."""
        code = f"""
import sys, threading
sys.path.insert(0, {REPO!r})
from odigos_tpu.pdata import synthesize_traces
from odigos_tpu.pipeline.service import Collector
from odigos_tpu.selftelemetry.profiler import DeviceRuntimeCollector
from odigos_tpu.serving import EngineConfig, ScoringEngine
from odigos_tpu.serving.sidecar import SidecarServer
from odigos_tpu.utils.jaxruntime import backend_initialized

sock = {str(tmp_path / "score.sock")!r}
# the sidecar's side, in a thread here only so the test needs one
# process: a mock engine, which never touches jax either
server = SidecarServer(ScoringEngine(EngineConfig(model="mock")), sock)
threading.Thread(target=server.serve_forever, daemon=True).start()
cfg = {{
    "receivers": {{"otlpwire": {{"port": 0}}}},
    "processors": {{
        "resourcedetection": {{"detectors": ["system", "tpu"]}},
        "tpuanomaly": {{"model": "remote", "socket_path": sock,
                        "timeout_ms": 5000.0, "shared_engine": False}}}},
    "exporters": {{"tracedb": {{}}}},
    "service": {{
        "telemetry": {{"device_runtime": {{"enabled": True,
                                           "interval_s": 0.05}}}},
        "pipelines": {{"traces/in": {{
            "receivers": ["otlpwire"],
            "processors": ["resourcedetection", "tpuanomaly"],
            "exporters": ["tracedb"]}}}}}}}}
import jax  # imported, as a model_config mapping would; nothing more
collector = Collector(cfg).start()
try:
    recv = collector.graph.receivers["otlpwire"]
    for seed in range(3):
        recv.next_consumer.consume(synthesize_traces(4, seed=seed))
    db = collector.graph.exporters["tracedb"]
    assert db.wait_for_spans(1, timeout=20.0)
    out = DeviceRuntimeCollector().collect_once(publish=False)
    assert not any(k.startswith("odigos_device_") for k in out), out
    spans = db.all_spans()
    assert not any("odigos.tpu.present" in r for r in spans.resources)
finally:
    collector.shutdown()
    server.shutdown()
assert not backend_initialized(), "the collector initialised a backend"
print("never owned a backend")
"""
        r = subprocess.run([sys.executable, "-c", code],
                           capture_output=True, text=True, timeout=180)
        assert r.returncode == 0, r.stdout + r.stderr
        assert "never owned a backend" in r.stdout
