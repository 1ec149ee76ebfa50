# Developer entry points. The same commands CI runs; no magic.

PY ?= python

.PHONY: lint verify test test-fast opt-test serve-test multihost cluster-test check chaos wire-test preempt-test obs-test shuffle-test failover-test fabric-test tune-test

# Framework-invariant static analysis (tools/ddl_lint, docs/LINT.md).
# Exit 0 = clean; findings print as file:line:col: DDL0xx message.
lint:
	$(PY) -m tools.ddl_lint ddl_tpu/ tests/

# Whole-program verifier (tools/ddl_verify, docs/VERIFY.md): lock-order
# graph + deadlock cycles (VP001), blocking-under-lock (VP002), the
# env-knob contract (VP003), control-protocol exhaustiveness (VP004).
verify:
	$(PY) -m tools.ddl_verify ddl_tpu/

# Full tier-1 suite (CPU-simulated 8-device mesh).
test:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q -m 'not slow'

# Transport + lint gate only: the quick pre-push loop.
test-fast:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_transport.py \
	    tests/test_py_ring.py tests/test_lint.py -q

# Serve control-plane suite alone (admission/fair-share/autoscaler units,
# concurrent-consumer fairness, the serve fault-site chaos rows).
serve-test:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_serve.py -q

# The full multi-process jax.distributed matrix: virtual-mesh legs
# (dp, dp×sp, pp×dp, dp×ep), checkpoint resume, packed-stream fit, and
# the cross-host elastic chaos leg (slow legs included).
multihost:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_multihost.py -q

# Cluster control-plane suite alone (membership/view-change/placement
# units + the in-process host-loss recovery ladder).
cluster-test:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_cluster.py -q

# The one-shot local gate: static analysis (per-module lint +
# whole-program verify) + the tier-1 suite.
check: lint verify test

# Chaos suite: deterministic fault matrix + randomized multi-fault soak
# (includes slow PROCESS-mode spawns; docs/ROBUSTNESS.md) + the cache
# corruption/backend-failure ladder (tests/test_cache.py) + the ICI
# DMA-failure → xla-fallback rung (tests/test_ici.py) + the preemption
# notice/checkpoint-corruption rows (tests/test_resilience.py).
chaos:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_faults.py tests/test_cache.py tests/test_ici.py tests/test_cluster.py tests/test_serve.py tests/test_resilience.py tests/test_obs.py tests/test_supervision.py -q

# Distributed-optimizer suite alone (parity matrix, collective units,
# the 4B fits-only-with-zero1 accounting test).
opt-test:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_optimizer.py -q

# Wire-format suite alone (codec/quantizer units, trailer roundtrip,
# slot/exchange/ICI wire paths, the wire chaos rows).
wire-test:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_wire.py -q

# Preemption-tolerance suite alone (async checkpointer units, the
# restore quarantine/fallback ladder, revocation, SIGTERM/notice drain
# e2e in THREAD and forced-py-ring PROCESS mode; docs/ROBUSTNESS.md).
preempt-test:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_resilience.py -q

# Survivable-control-plane suite alone (supervisor journal replay,
# the acked/fenced envelope seam, lease-expiry HA promotion incl. the
# split-brain row, scheduler-fairness continuity, the mid-stream
# supervisor-kill e2e; docs/ROBUSTNESS.md "Control-plane failover").
failover-test:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_supervision.py -q

# Multi-job ingest fabric unit + property tests (tests/test_fabric.py:
# supervisor-resident admission, journal-replay failover, per-job
# isolation seams, chaos-matrix rows for the fabric fault kinds).
fabric-test:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_fabric.py -q

# Self-tuning unit/e2e matrix (ddl_tpu/tune; docs/TUNING.md):
# hysteresis, cooldown, never-worse revert, deadline-bounded
# calibration, parity flip, drift replan, knob seams.
tune-test:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_tune.py -q

# Device-exchange suite alone (seed parity across geometries, the DMA
# -failure/peer-loss chaos rungs, resolution surface, end-to-end
# stream identity in THREAD and PROCESS modes).
shuffle-test:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_device_shuffle.py -q

# Tracing-layer suite alone (Metrics histograms, SpanLog/Chrome export,
# cross-process aggregation, flight recorder, the doc-reflection test;
# docs/OBSERVABILITY.md).
obs-test:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_obs.py -q
