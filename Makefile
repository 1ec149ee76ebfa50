# Developer entry points. The same commands CI runs; no magic.

PY ?= python

.PHONY: lint verify test test-fast bench-smoke cache-bench ici-bench ici-dryrun opt-bench opt-dryrun opt-test placement-bench tenancy-bench serve-test multihost cluster-test check chaos wire-bench wire-dryrun wire-test preempt-test preempt-bench obs-bench obs-test shuffle-bench shuffle-dryrun shuffle-test failover-test failover-bench fabric-test fabric-bench tune-test tune-bench

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

# Ingest bench at tiny CPU geometry: asserts the JSON line parses and
# carries the staged-ingest extras (tools/bench_smoke.py).
bench-smoke:
	$(PY) tools/bench_smoke.py

# Shard-cache cold/warm A/B over the throttled backend, full geometry
# (docs/CACHING.md; headline = warm/cold speedup).
cache-bench:
	DDL_BENCH_MODE=cache DDL_BENCH_PLATFORM=cpu $(PY) bench.py

# ICI distribution A/B (Pallas fan-out + redistribution vs the XLA
# scatter; docs/PERF_NOTES.md "ICI ingest").  Needs a multi-chip TPU
# host; DDL_BENCH_PLATFORM=cpu asks for the interpret-mode contract run
# on the virtual mesh instead (not a device measurement).
ici-bench:
	DDL_BENCH_MODE=ici $(PY) bench.py

# Fan-out kernel dry run in interpret mode on the CPU virtual mesh
# (both modes + one full redistribution) — the mirror of
# tools/probe_ingest.py for the post-H2D hop.
ici-dryrun:
	DDL_BENCH_PLATFORM=cpu $(PY) tools/probe_ici.py

# Distributed-optimizer A/B (zero1 vs replicated state, fp32 vs int8
# grad comm; docs/PERF_NOTES.md "Distributed optimizer").  Loss parity
# asserted in the artifact; winner is the headline.
opt-bench:
	DDL_BENCH_MODE=opt $(PY) bench.py

# Optimizer-state/grad-comm sweep on the CPU virtual mesh: bytes/replica
# at small scale, analytic v5e-32 pricing for the 8B/4B configs — the
# mirror of tools/probe_ici.py for the optimizer tier.
opt-dryrun:
	DDL_BENCH_PLATFORM=cpu $(PY) tools/probe_opt.py

# Topology-aware vs naive producer→consumer placement A/B over the
# simulated fabric (ddl_tpu/cluster/placement.py; Cloud Collectives
# rank reordering) + the membership chaos counters.
placement-bench:
	DDL_BENCH_MODE=placement DDL_BENCH_PLATFORM=cpu $(PY) bench.py

# Multi-tenant ingest-service A/B (K concurrent tenants over the shared
# fair-share scheduler, autoscaled vs static pool; docs/SERVING.md) +
# the tenant-burst/host-loss chaos leg.
tenancy-bench:
	DDL_BENCH_MODE=tenancy DDL_BENCH_PLATFORM=cpu $(PY) bench.py

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
# whole-program verify) + bench JSON contract (the bench-smoke contract
# includes the cache block's byte-identity and >=2x warm-vs-cold
# assertions).
check: lint verify bench-smoke

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

# Data-plane wire-format A/B (raw vs int8 vs codec exchange wire over a
# simulated constrained link; docs/PERF_NOTES.md "Wire format").
# Lossless byte identity + int8 loss parity asserted in the artifact;
# winner is the headline.
wire-bench:
	DDL_BENCH_MODE=wire DDL_BENCH_PLATFORM=cpu $(PY) bench.py

# Per-dtype/per-codec encode/decode bytes/s + compression ratios on
# real shard data, break-even link speeds, and the analytic ICI wire
# pricing — the mirror of probe_ici/probe_opt for the wire tier.
wire-dryrun:
	DDL_BENCH_PLATFORM=cpu $(PY) tools/probe_wire.py

# Wire-format suite alone (codec/quantizer units, trailer roundtrip,
# slot/exchange/ICI wire paths, the wire chaos rows).
wire-test:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_wire.py -q

# Preemption-tolerance suite alone (async checkpointer units, the
# restore quarantine/fallback ladder, revocation, SIGTERM/notice drain
# e2e in THREAD and forced-py-ring PROCESS mode; docs/ROBUSTNESS.md).
preempt-test:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_resilience.py -q

# Preemption tolerance priced end to end: async-vs-sync checkpoint
# stall A/B, notice→resumed recovery wall time, hard-kill lost-work
# bound — byte-identical resume asserted in the artifact.
preempt-bench:
	DDL_BENCH_MODE=preempt DDL_BENCH_PLATFORM=cpu $(PY) bench.py

# Survivable-control-plane suite alone (supervisor journal replay,
# the acked/fenced envelope seam, lease-expiry HA promotion incl. the
# split-brain row, scheduler-fairness continuity, the mid-stream
# supervisor-kill e2e; docs/ROBUSTNESS.md "Control-plane failover").
failover-test:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_supervision.py -q

# Control-plane failover priced end to end: mid-stream supervisor kill
# with standby takeover wall time as the headline — byte-identical
# stream, zero watchdog failures, envelope drop/dup dedup counters and
# scheduler-fairness continuity asserted in the artifact.
failover-bench:
	DDL_BENCH_MODE=failover DDL_BENCH_PLATFORM=cpu $(PY) bench.py

# Multi-job ingest fabric unit + property tests (tests/test_fabric.py:
# supervisor-resident admission, journal-replay failover, per-job
# isolation seams, chaos-matrix rows for the fabric fault kinds).
fabric-test:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_fabric.py -q

# The fleet soak end to end: 50 Zipf-weighted jobs / 100 simulated host
# bindings against ONE supervisor-resident scheduler over the acked
# control plane — weighted-share deviation headline, scale-reaction and
# preemption-drain SLOs, per-job cache accounting, and the supervisor-
# kill leg's bit-identical admission order in the artifact.
fabric-bench:
	DDL_BENCH_MODE=fabric DDL_BENCH_PLATFORM=cpu $(PY) bench.py

# Self-tuning unit/e2e matrix (ddl_tpu/tune; docs/TUNING.md):
# hysteresis, cooldown, never-worse revert, deadline-bounded
# calibration, parity flip, drift replan, knob seams.
tune-test:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_tune.py -q

# Self-tuned vs shipped-defaults from a mis-matched cold start (raw
# wire on a throttled link, starved prefetch seed): Calibrator at boot
# + KnobController live, interleaved A/B, never-slower gated by
# bench_smoke.
tune-bench:
	DDL_BENCH_MODE=autotune DDL_BENCH_PLATFORM=cpu $(PY) bench.py

# Host-vs-device global-shuffle exchange A/B (ThreadExchangeShuffler
# over the rendezvous boards vs the on-mesh DeviceExchangeShuffler;
# docs/PERF_NOTES.md "Device-side global shuffle").  Byte identity of
# the post-exchange pools asserted per rep; winner is the headline.
# Here: the interpret-mode contract run on the CPU virtual mesh (the
# host path usually wins there — the contract, not the speedup, is
# what CI gates on); drop DDL_BENCH_PLATFORM on a multi-chip TPU host.
shuffle-bench:
	DDL_BENCH_MODE=shuffle DDL_BENCH_PLATFORM=cpu $(PY) bench.py

# Analytic exchange pricing (device ICI bytes vs host boards raw/wire
# per plan_exchange) across ring widths + a live byte-identity parity
# run for both impls on the virtual mesh — the mirror of
# probe_ici/probe_wire for the shuffle tier.
shuffle-dryrun:
	DDL_BENCH_PLATFORM=cpu $(PY) tools/probe_shuffle.py

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

# The tracing layer priced end to end: armed-vs-disarmed span/recorder
# overhead A/B (ceiling <= 2%, byte-identical), histogram percentiles
# in the armed report, and the seeded-corruption flight-record leg.
obs-bench:
	DDL_BENCH_MODE=obs DDL_BENCH_PLATFORM=cpu $(PY) bench.py
