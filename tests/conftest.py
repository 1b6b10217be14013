"""Test harness configuration.

Tests run on a virtual 8-device CPU mesh (the analog of the reference testing
multi-node topologies on a single machine via KinD multi-node,
tests/common/apply/kind-config.yaml — SURVEY.md §4 item 5). Environment must be
set before jax is imported anywhere.
"""

import os

# Tests always run on the virtual CPU mesh, whatever the machine holds: a
# suite that grabbed the chip would also take it from anything else on the
# host. JAX_PLATFORMS=cpu in the environment is enough when it is set before
# jax is first imported (the tier-1 command does); the config update below
# holds the same line if a plugin imported jax before this file ran.
# XLA_FLAGS is read when the backend initialises, which no import does.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    # registered markers: tier-1 runs `-m 'not slow'`, so `chaos`
    # (the fault-injection scenario matrix, ISSUE 13) is IN tier-1 by
    # default — robustness regressions fail CI, not a nightly
    config.addinivalue_line(
        "markers", "slow: long-running tests excluded from tier-1")
    config.addinivalue_line(
        "markers",
        "chaos: fault-injection scenario matrix (deterministic "
        "injections, seeded via --chaos-seed)")


def pytest_addoption(parser):
    parser.addoption(
        "--chaos-seed", type=int, default=0,
        help="seed for every randomized choice inside chaos scenarios "
             "(jittered backoffs, storm payloads) — the same seed "
             "replays the same fault schedule")


@pytest.fixture
def chaos_seed(request):
    """The deterministic seed chaos scenarios thread through every
    randomized injection (ISSUE 13)."""
    return int(request.config.getoption("--chaos-seed"))


@pytest.fixture(scope="session")
def demo_batch():
    """A medium synthetic batch shared across tests (session-scoped: cheap)."""
    from odigos_tpu.pdata import synthesize_traces

    return synthesize_traces(64, seed=7)


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)
