# Developer entrypoints (reference: Makefile at the repo root).
# No install step: the package runs from the repo root.

.PHONY: test test-fast chip-smoke dryrun ui preflight

CPU_MESH = JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8

test:            ## full suite on the 8-device virtual CPU mesh (~10 min)
	$(CPU_MESH) python -m pytest tests/ -x -q

test-fast:       ## everything but the slow parallel/e2e/auc suites
	$(CPU_MESH) python -m pytest tests/ -x -q --ignore=tests/test_parallel.py \
	  --ignore=tests/test_northstar_auc.py --ignore=tests/test_anomaly_e2e.py

chip-smoke:      ## wire-to-score path on one TPU chip; exits non-zero anywhere else
	python chip_smoke.py

dryrun:          ## multi-chip sharding compile+execute on 8 virtual CPU devices
	$(CPU_MESH) python -c "import __graft_entry__ as g; g.dryrun_multichip(8)"

ui:              ## operator dashboard over the local install
	python -m odigos_tpu.cli ui

preflight:       ## installation health checks
	python -m odigos_tpu.cli preflight
