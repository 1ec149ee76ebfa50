"""Benchmark: loader→HBM ingest throughput + flagship train-step MFU.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...extras}.

Two measurements (BASELINE.md north-star + VERDICT r1 items 1-2):

1. **Ingest** — samples/sec of the full pipeline: producer workers filling
   window rings, consumer draining zero-copy and streaming batches into
   device HBM while a jitted consumer computation runs.  ``vs_baseline``
   compares against a faithful re-creation of the *reference's* design
   point on identical hardware: single-buffered strict alternation (its
   one-window-per-producer token protocol, reference
   ``ddl/datapusher.py:147-170``) with synchronous per-batch transfers and
   no overlap.  The reference itself publishes no numbers (BASELINE.md).
2. **Train MFU** — tokens/sec and model-FLOPs-utilization of the jitted
   Llama fwd+bwd+update step (``parallel/train.make_train_step``), flash
   and dense attention.

Platform (``ddl_tpu.bringup``): JAX is brought up once, in this
process, and the run requires a TPU.  ``DDL_BENCH_PLATFORM=cpu`` asks
for the CPU by name (``make bench-smoke`` does: a contract run at tiny
sizes whose numbers are not device numbers); without it a machine with
no TPU makes the bench exit non-zero with the reason.  No child process
probes the backend — a chip belongs to one process at a time.  A phase
that fails is recorded in ``errors`` and the JSON line is still
printed, but on a TPU run the exit code is then non-zero.

Headline contract: every JSON line stamps ``platform`` and
``git_head``; the ingest headline COMPETES across
prefetch / no-prefetch / prefetch-inline / PROCESS and records
``headline_config`` (never a config the same run measured slower —
bench_smoke enforces); ``vs_baseline`` is measured INTERLEAVED with
winner re-runs; and ``ingest.process_vs_thread`` ships with a per-leg
``core_attach`` record so starved-box ratios are distinguishable from
transport regressions.

Env knobs: DDL_BENCH_PLATFORM=cpu (ask for the CPU), DDL_BENCH_MODE=
ingest|train|all|big|stream|decode|cache|ici (default all; "big" runs
ONLY the HBM-filling train config, "stream" ONLY the window-stream
configs — the window-size sweep — "decode" ONLY the
serving-phase prefill+decode config, "cache" the shard-cache cold/warm
A/B, "ici" the device-side distribution A/B: Pallas fan-out +
redistribution vs the XLA scatter, DDL_BENCH_ICI_MIB /
DDL_BENCH_ICI_REPS geometry, and "tenancy" the multi-tenant
ingest-service A/B: K concurrent tenants over the shared fair-share
scheduler, autoscaled vs static pool, DDL_BENCH_TENANCY_TENANTS /
_BASE / _FILL_MS / _ROWS / _REPS geometry),
DDL_BENCH_STREAM_MIB / DDL_BENCH_LOOKAHEAD /
DDL_BENCH_NSLOTS (stream geometry), DDL_BENCH_DECODE_BATCH (serving
batch for the decode configs; default 8 on TPU).  Pipeline knobs that
shape the measured paths: DDL_TPU_INPLACE (write-once producer fills),
DDL_TPU_SHM_STAGING (slot-aliasing staged transfers), DDL_TPU_STAGED.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

import numpy as np

# -- ingest workload geometry -------------------------------------------------
# Env-overridable so `make bench-smoke` can run the full pipeline with a
# tiny geometry on CPU (defaults are the published bench shape).
N_DATA = int(os.environ.get("DDL_BENCH_NDATA", "8192"))  # samples/window
N_VALUES = int(os.environ.get("DDL_BENCH_NVALUES", "256"))  # f32/sample
BATCH = int(os.environ.get("DDL_BENCH_BATCH", "2048"))
EPOCHS_MEASURED = int(os.environ.get("DDL_BENCH_EPOCHS", "24"))
N_PRODUCERS = 2

# -- backend selection --------------------------------------------------------

# Published per-device peaks by device_kind substring (first match
# wins): dense bf16 matmul FLOP/s, HBM bytes/s, and per-LINK ICI bytes/s
# one direction (per-chip totals over the link count: v2 496/4, v3
# 656/4, v4 2400/6, v5e 1600/4, v5p 4800/6, v6e 3584/4 Gbps).  Source:
# Google Cloud TPU documentation, per-generation system pages.  A
# device that is not in the table is an error, not a default — a
# utilization over a guessed peak is not a measurement.
_PEAKS = (
    # (kind substring, flops, hbm, ici link)
    ("v6", 918e12, 1640e9, 112e9),  # Trillium / v6e
    ("v5p", 459e12, 2765e9, 100e9),
    ("v5", 197e12, 819e9, 50e9),  # v5e / "TPU v5 lite"
    ("v4", 275e12, 1228e9, 50e9),
    ("v3", 61.5e12, 900e9, 20.5e9),  # per-core device
    ("v2", 22.5e12, 700e9, 15.5e9),  # per-core device
)


def _peak(device_kind: str, column: int) -> float:
    kind = device_kind.lower()
    for row in _PEAKS:
        if row[0] in kind:
            return row[column]
    raise LookupError(
        f"no published peaks for device_kind {device_kind!r}: add its row "
        "to bench._PEAKS (with the source) before reporting a utilization"
    )


def _peak_flops(device_kind: str) -> float:
    return _peak(device_kind, 1)


def _peak_hbm(device_kind: str) -> float:
    return _peak(device_kind, 2)


def _peak_ici_link(device_kind: str) -> float:
    return _peak(device_kind, 3)


def best_of(n: int, fn, key):
    """Run ``fn`` n times and return the result minimising ``key``.

    The one timing estimator for this bench: contention on a shared
    host is one-sided noise (it only ever slows a run), so the best
    observation is taken as the estimate of real cost.
    """
    results = [fn() for _ in range(n)]
    return min(results, key=key)


def best_valid(n: int, fn, key):
    """``best_of`` over runs that may individually fail a plausibility
    gate (``fn`` raises): artifact runs are discarded and the best VALID
    run wins; only if every run is rejected does the failure propagate.
    A gate-after-selection would let the artifact run win selection and
    throw away its valid companions."""
    results, errs = [], []
    for _ in range(n):
        try:
            results.append(fn())
        except Exception as e:  # noqa: BLE001 - re-raised if all fail
            errs.append(e)
    if not results:
        raise errs[0]
    return min(results, key=key)


#: Achieved/measured-link ratios above this are physically impossible —
#: a transfer-timing artifact (the round-2 failure class), not a result.
_UTIL_GATE = 1.05


def _gate_utilization(ns: dict, label: str) -> dict:
    util = ns.get("bandwidth_utilization", 0.0)
    if util > _UTIL_GATE:
        raise RuntimeError(
            f"implausible {label} utilization {util:.3f} (> 1) — "
            "measurement rejected"
        )
    return ns


def bring_up(cpu_devices: int = 1) -> str:
    """THE platform bring-up for bench and every probe tool
    (``ddl_tpu.bringup.bring_up``): initialise JAX in this process,
    require a TPU unless ``DDL_BENCH_PLATFORM=cpu`` asked for the CPU by
    name, place the compile cache.  Returns the platform; exits
    non-zero with the reason when a TPU was wanted and not found.
    ``cpu_devices`` > 1 gives a CPU run that many virtual devices (the
    multi-device contract modes) — set before the backend's first
    touch, which happens here."""
    from ddl_tpu.bringup import bring_up as _bring_up

    request = os.environ.get("DDL_BENCH_PLATFORM")
    if request == "cpu" and cpu_devices > 1:
        _ensure_virtual_mesh(cpu_devices)
    return _bring_up(request)


def _git_head() -> "str | None":
    """Short HEAD hash of the repo the bench ran from, stamped into every
    JSON line; ``None`` where the tree is not a git checkout (the chip
    machine's copy) or git is missing."""
    try:
        out = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10, cwd=REPO,
        )
        return out.stdout.strip() or None if out.returncode == 0 else None
    except (OSError, subprocess.TimeoutExpired):
        return None


def _core_attach(n_workers: int = None) -> dict:
    """The measurement box's core attach, recorded per ingest leg.

    ``starved`` is the structural verdict: the PROCESS-vs-THREAD stream
    comparison needs every producer process AND the consumer on its own
    core (``n_workers`` defaults to the bench's producers + 1); with
    fewer attached cores a <1x ratio is preemption, not ring overhead
    (docs/PERF_NOTES.md "PROCESS-mode ingest vs THREAD mode"), and the
    bench_smoke ratio gate accepts the starvation proof instead.
    """
    need = (N_PRODUCERS + 1) if n_workers is None else n_workers
    try:
        affinity = len(os.sched_getaffinity(0))
    except (AttributeError, OSError):  # pragma: no cover - non-linux
        affinity = os.cpu_count()
    try:
        load_1m = round(os.getloadavg()[0], 2)
    except (AttributeError, OSError):  # pragma: no cover - non-unix
        load_1m = None
    return {
        "cpu_count": os.cpu_count(),
        "affinity": affinity,
        "load_avg_1m": load_1m,
        "cores_needed": need,
        "starved": bool(affinity is not None and affinity < need),
    }


# -- ingest bench -------------------------------------------------------------


try:  # import lazily-guarded so `import bench` works before deps resolve
    from ddl_tpu import DataProducerOnInitReturn, ProducerFunctionSkeleton

    class BenchProducer(ProducerFunctionSkeleton):
        """Module-level (picklable): PROCESS mode ships it to spawned
        producer processes, exactly like user producer functions."""

        def on_init(self, producer_idx=0, **kw):
            self._rng = np.random.default_rng(producer_idx)
            self._data = self._rng.random((N_DATA, N_VALUES), np.float32)
            return DataProducerOnInitReturn(
                nData=N_DATA, nValues=N_VALUES, shape=(N_DATA, N_VALUES),
                splits=(N_VALUES - 1, 1),
            )

        def post_init(self, my_ary, **kw):
            np.copyto(my_ary, self._data)

        def execute_function(self, my_ary, **kw):
            # Representative per-window producer work: local in-place
            # shuffle (what the reference example does per refill,
            # reference tests/run_ddl.py:163-167).
            self._rng.shuffle(my_ary)

    # Stream-config geometry: big windows amortize per-transfer cost (the
    # link saturates only at >=8 MiB per put — tools/probe_ingest.py).
    # DDL_BENCH_STREAM_MIB sweeps the window size (utilization-gap
    # diagnosis, VERDICT r4 item 2); DDL_BENCH_LOOKAHEAD deepens the
    # stream pipeline (pair with DDL_BENCH_NSLOTS >= lookahead+1).
    # Defaults are the chip-sweep winner (64 MiB, 3-deep lookahead):
    # this geometry measured 0.915 of the link in a stable window —
    # the BASELINE.md >=0.9 north star (bench-stream-northstar-*.json);
    # 32 MiB / lookahead 1 left ~10% on the table.
    STREAM_MIB = int(os.environ.get("DDL_BENCH_STREAM_MIB", "64"))
    # Rounded to a whole number of batches (serving truncates ragged tails).
    N_DATA_STREAM = max(
        BATCH, STREAM_MIB * (1 << 20) // (N_VALUES * 4) // BATCH * BATCH
    )
    EPOCHS_STREAM = 16
    STREAM_LOOKAHEAD = int(os.environ.get("DDL_BENCH_LOOKAHEAD", "3"))
    # Default derives from the lookahead so deepening the pipeline via
    # DDL_BENCH_LOOKAHEAD alone cannot silently under-provision the ring.
    STREAM_NSLOTS = int(
        os.environ.get("DDL_BENCH_NSLOTS", str(STREAM_LOOKAHEAD + 1))
    )

    class StreamBenchProducer(ProducerFunctionSkeleton):
        """Zero-copy fill: writes each window straight into the ring slot
        from a pregenerated bank — shard-reader-style refill where the
        per-window producer work is one sequential copy (serving
        pre-materialized shards from page cache)."""

        inplace_fill = True

        def on_init(self, producer_idx=0, **kw):
            rng = np.random.default_rng(100 + producer_idx)
            self._bank = rng.random(
                (2 * N_DATA_STREAM, N_VALUES), np.float32
            )
            self._off = 0
            return DataProducerOnInitReturn(
                nData=N_DATA_STREAM, nValues=N_VALUES,
                shape=(N_DATA_STREAM, N_VALUES), splits=(N_VALUES - 1, 1),
            )

        def post_init(self, my_ary, **kw):
            np.copyto(my_ary, self._bank[:N_DATA_STREAM])

        def execute_function(self, my_ary, **kw):
            self._off = (self._off + N_DATA_STREAM // 4) % N_DATA_STREAM
            np.copyto(
                my_ary, self._bank[self._off : self._off + N_DATA_STREAM]
            )

except Exception as _e:  # pragma: no cover - only hit on broken installs
    BenchProducer = None  # type: ignore[assignment]
    StreamBenchProducer = None  # type: ignore[assignment]
    _producer_import_error: Exception = _e


def _make_producer():
    if BenchProducer is None:
        raise RuntimeError(
            "ddl_tpu failed to import at bench startup"
        ) from _producer_import_error
    return BenchProducer()


def _consumer_compute():
    """A small jitted reduction standing in for the training step's
    consumption of the batch (keeps the device busy so overlap matters)."""
    import jax

    @jax.jit
    def f(x, y):
        return (x @ x.T).sum() + y.sum()

    return f


def _run_ingest(
    nslots: int,
    n_producers: int,
    sync_every_batch: bool,
    mode: str = "thread",
    use_prefetch: bool = False,
    link_bytes_per_sec: float = 0.0,
    staged: bool | None = None,
):
    """Returns (samples/sec, north-star metric dict) for one config.

    ``mode="process"`` runs the producers as spawned OS processes over the
    native C++ shm ring — the §2.4 native component's perf number (VERDICT
    r2 Weak #3: it previously had none).  On a 1-core host PROCESS trails
    THREAD by construction (preemptive cache thrash, not ring overhead —
    measured analysis in docs/PERF_NOTES.md); compare the two only where
    ``nproc > n_producers``.  ``use_prefetch`` drains each window via
    ``loader.prefetch()`` (depth-2 lookahead) instead of plain
    ``__getitem__`` iteration.  ``staged`` pins the ingest discipline per
    run (None = the DDL_TPU_STAGED env default) — the bench publishes
    staged vs inline side by side.
    """
    import jax

    from ddl_tpu import DistributedDataLoader, Marker, distributed_dataloader
    from ddl_tpu.ingest import north_star_report
    from ddl_tpu.observability import Metrics

    compute = _consumer_compute()
    metrics = Metrics()
    n_epochs = EPOCHS_MEASURED + 2  # first two epochs are warmup

    @distributed_dataloader(n_producers=n_producers, mode=mode, nslots=nslots)
    def main(env):
        loader = DistributedDataLoader(
            _make_producer(), batch_size=BATCH, connection=env.connection,
            n_epochs=n_epochs, output="jax", metrics=metrics,
            staged=staged,
        )
        t0 = None
        samples = 0
        out = None
        for epoch in range(n_epochs):
            if epoch == 2:  # warmup done (compile + first fills)
                if out is not None:
                    jax.block_until_ready(out)
                metrics.reset()  # steady-state north-star window
                t0 = time.perf_counter()
                samples = 0
            it = loader.prefetch(2) if use_prefetch else loader
            for x, y in it:
                out = compute(x, y)
                if sync_every_batch:
                    jax.block_until_ready(out)
                if t0 is not None:
                    samples += BATCH
                loader.mark(Marker.END_OF_BATCH)
            loader.mark(Marker.END_OF_EPOCH)
        jax.block_until_ready(out)
        # Snapshot the north-star report at the SAME instant the wall
        # clock stops — still inside the consumer role, BEFORE the
        # decorator's producer teardown.  Computing it after main()
        # returned let Metrics.elapsed_s() run through worker joins,
        # deflating bytes/s by the teardown time (seconds in PROCESS
        # mode), so process runs could report more samples/s yet fewer
        # bytes/s than thread runs (VERDICT r4 Weak #3).
        rate = samples / (time.perf_counter() - t0)
        return rate, north_star_report(
            metrics, link_bytes_per_sec=link_bytes_per_sec
        )

    return main()


def _run_ingest_stream(link_bytes_per_sec: float = 0.0, mode: str = "thread"):
    """The zero-copy streaming path: ``loader.windows()`` transfers whole
    windows straight out of ring slots (no host memcpy between producer
    fill and HBM), producers fill slots in place.  This is the config that
    evaluates BASELINE.md's ">=90% bandwidth utilization" target — per-
    batch per-column puts can never reach it on a link with fixed
    per-transfer cost (measured: tools/probe_ingest.py).

    ``mode="process"`` is the production shape on a real TPU host:
    producer processes fill native shm ring slots on their own cores
    while the consumer streams slots into HBM (on the 1-core bench box
    it trails THREAD for the docs/PERF_NOTES.md reasons).
    """
    import jax
    import jax.numpy as jnp

    from ddl_tpu import DistributedDataLoader, Marker, distributed_dataloader
    from ddl_tpu.ingest import north_star_report
    from ddl_tpu.observability import Metrics

    metrics = Metrics()
    # First two windows are warmup/compile; the last STREAM_LOOKAHEAD
    # are the pipeline drain, excluded from the measured span (below).
    n_epochs = EPOCHS_STREAM + 2 + STREAM_LOOKAHEAD

    @jax.jit
    def consume(w):
        return jnp.sum(w[..., -1])

    @distributed_dataloader(
        n_producers=N_PRODUCERS, mode=mode, nslots=STREAM_NSLOTS
    )
    def main(env):
        loader = DistributedDataLoader(
            StreamBenchProducer(), batch_size=BATCH,
            connection=env.connection, n_epochs=n_epochs, output="jax",
            metrics=metrics,
        )
        t0 = None
        samples = 0
        out = None
        seen = 0
        rate = None
        report = None
        for win in loader.windows(lookahead=STREAM_LOOKAHEAD):
            if seen == 2:
                if out is not None:
                    jax.block_until_ready(out)
                metrics.reset()
                t0 = time.perf_counter()
            elif t0 is not None and report is None:
                # The window yielded at the clock start was already on
                # device when the clock started — only count later ones.
                samples += N_DATA_STREAM
            out = consume(win)
            seen += 1
            # Stop BOTH clocks while dispatches still continue — i.e.
            # with the lookahead pipeline as full at the stop as it was
            # at the start.  Ending the span in the drain (the old
            # accounting) counted the start cohort's pre-clock transfer
            # work with nothing offsetting it at the tail, inflating
            # the rate by up to lookahead/EPOCHS_STREAM; with matched
            # in-flight depth at both edges, completions-per-second
            # over the span IS the steady-state throughput.
            if report is None and seen == n_epochs - STREAM_LOOKAHEAD:
                jax.block_until_ready(out)
                rate = samples / (time.perf_counter() - t0)
                # Same-span report (see _run_ingest): registry rates
                # snapshot at the same instant, inside the consumer
                # role, so neither drain nor teardown leaks in.  With
                # completion-time byte accounting (put_window
                # defer_metrics), registry bytes and wall-clock samples
                # cover identical windows: bytes/s == samples/s *
                # bytes_per_sample by construction.
                report = north_star_report(
                    metrics, link_bytes_per_sec=link_bytes_per_sec
                )
            loader.mark(Marker.END_OF_EPOCH)
        jax.block_until_ready(out)  # drain windows run uncounted
        return rate, report

    return main()


# -- train/MFU bench ----------------------------------------------------------


def _train_config(platform: str, size: str = "small"):
    """MXU-saturating single-chip config on TPU; tiny on CPU.

    ``size="big"`` (TPU only) is the HBM-filling credibility config
    (VERDICT r3 item 7): ~1.4B params in bf16 storage (params + adamw
    moments ≈ 8.4 GiB of v5e's 16 GiB), per-layer remat, seq 2048 — MFU
    at a geometry representative of the BASELINE.md 8B-class north-star
    workloads, not a 4-layer toy.
    """
    from ddl_tpu.models.llama import LlamaConfig

    if platform == "tpu" and size == "big":
        import jax.numpy as jnp

        from ddl_tpu.config import TrainConfig

        # Selective remat by default (DDL_TPU_TRAIN_REMAT sweeps the
        # policy): full-layer remat paid the whole-layer recompute —
        # MFU 0.5574 at 1.39B vs 0.6255 at 285M (VERDICT r5 weak #3);
        # "selective" keeps the attention outputs saved so the backward
        # never re-runs the flash kernel.
        tc = TrainConfig(
            remat=os.environ.get("DDL_TPU_TRAIN_REMAT", "selective")
        )
        return (
            tc.model_config(LlamaConfig(
                vocab=32768, d_model=2048, n_layers=20, n_heads=16,
                n_kv_heads=8, d_ff=8192, max_seq=2048,
                param_dtype=jnp.bfloat16,
            )),
            4,  # batch
            2048,  # seq
            6,  # measured steps (~0.5-1s each: big model, remat refwd)
        )
    if platform == "tpu":
        return (
            LlamaConfig(
                vocab=8192, d_model=2048, n_layers=4, n_heads=16,
                n_kv_heads=8, d_ff=8192, max_seq=2048,
            ),
            4,  # batch
            2048,  # seq
            20,  # measured steps (~140ms each; dispatch overhead < 3%)
        )
    return (
        LlamaConfig(
            vocab=512, d_model=128, n_layers=2, n_heads=4, n_kv_heads=2,
            d_ff=352, max_seq=256,
        ),
        4, 128, 4,
    )


def _attn_lm_head_flops_per_token(cfg, seq: int) -> float:
    """Forward matmul FLOPs per token for the parts every decoder family
    shares — attention (qkv/out projections + causal-half scores and
    attn@v, the standard MFU convention: masked positions are not model
    FLOPs) across all layers, plus the lm_head.  Family probes add
    their own per-layer MLP term (dense SwiGLU here; router + top-k
    experts in tools/probe_moe.py) so the accounting cannot drift
    between the published MFU numbers."""
    d, hd = cfg.d_model, cfg.head_dim
    per_layer = (
        2 * d * (cfg.n_heads + 2 * cfg.n_kv_heads) * hd  # qkv proj
        + 2 * cfg.n_heads * hd * d  # out proj
        + 2 * 2 * seq * cfg.n_heads * hd / 2  # scores + attn@v, causal half
    )
    return cfg.n_layers * per_layer + 2 * d * cfg.vocab


def _model_flops_per_token(cfg, seq: int) -> float:
    """Analytic matmul model-FLOPs per token, fwd+bwd (bwd = 2x fwd)."""
    mlp = cfg.n_layers * 3 * 2 * cfg.d_model * cfg.d_ff  # gate/up/down
    return 3.0 * (_attn_lm_head_flops_per_token(cfg, seq) + mlp)


def _run_train(platform: str, attn_impl: str, size: str = "small"):
    """Returns dict with tokens/sec, step time, MFU for one attention impl.

    Timing is ``make_multistep``: all measured steps run chained inside ONE
    jitted program (``lax.scan``), serialized by the params data
    dependence, and the clock stops only after a *host read-back* of the
    final loss.  Async dispatch cannot fake any part of that — the round-2
    bench trusted ``block_until_ready`` after a python loop and published a
    0.55 ms "step" that really took ~200 ms (VERDICT r2 Missing #1).

    Every measurement passes plausibility gates before being reported:
    the step time cannot beat the analytic FLOPs floor (flops/peak, i.e.
    MFU must be < 1), MFU must be positive, and the loss must be finite.
    Gate violations raise, so the caller records an error instead of a
    number.
    """
    import jax
    import optax

    from ddl_tpu.models import llama
    from ddl_tpu.parallel.mesh import make_mesh
    from ddl_tpu.parallel.train import make_multistep

    cfg, batch, seq, steps = _train_config(platform, size)
    cfg = type(cfg)(**{**cfg.__dict__, "attn_impl": attn_impl})
    # Distributed-optimizer knobs ride the standard TrainConfig env
    # (DDL_TPU_TRAIN_OPTIMIZER_SHARDING=zero1 / _GRAD_COMM=int8).
    # zero1 needs a dp
    # axis: with it requested AND a multi-device attach, the mesh spans
    # every local device (the batch dp-shards with it); the default
    # stays the single-chip dp=1 geometry of every prior BENCH_* line.
    import math

    from ddl_tpu.config import TrainConfig

    tc = TrainConfig.load()
    # The dp extent must divide the batch (P(("dp",)) shards its leading
    # axis) — clamp to the gcd so a batch-4 config on a v5e-8 attach
    # runs dp=4 over 4 chips instead of crashing in _reshard.
    n_dp = (
        math.gcd(len(jax.local_devices()), batch)
        if tc.optimizer_sharding == "zero1"
        else 1
    )
    mesh = make_mesh({"dp": n_dp}, devices=jax.local_devices()[:n_dp])
    # mesh=None for the loss: single-chip attention needs no shard_map (and
    # a dp=1 mesh would only trigger the replicated-attention warning path).
    init_fn, multi_fn = make_multistep(
        lambda p, b: llama.next_token_loss(p, b[0], cfg, mesh=None),
        optax.adamw(3e-4), mesh, llama.param_specs(cfg), n_steps=steps,
        **tc.optimizer_kwargs(),
    )
    rng = np.random.default_rng(0)
    batch_tokens = (rng.integers(0, cfg.vocab, (batch, seq)).astype(np.int32),)

    state_box = [init_fn(llama.init_params(cfg, jax.random.key(0)))]
    state_box[0], losses = multi_fn(state_box[0], batch_tokens)  # compile
    first_loss = float(losses[0])  # step-1 loss, before numeric drift

    def _timed_window():
        t0 = time.perf_counter()
        state_box[0], losses = multi_fn(state_box[0], batch_tokens)
        fl = float(losses[-1])  # host sync INSIDE the timed window
        return (time.perf_counter() - t0) / steps, fl

    dt, final_loss = best_of(2, _timed_window, key=lambda r: r[0])

    tokens_per_step = batch * seq
    flops_per_step = _model_flops_per_token(cfg, seq) * tokens_per_step
    kind = jax.local_devices()[0].device_kind
    # A CPU contract run has no device peak and reports no MFU.
    peak = _peak_flops(kind) if platform == "tpu" else None
    mfu = flops_per_step / dt / peak if peak else None
    # -- plausibility gates (fail loudly, never publish nonsense) ---------
    if not np.isfinite(final_loss):
        raise RuntimeError(f"non-finite loss {final_loss}")
    if mfu is not None and not (0.0 < mfu < 1.0):
        raise RuntimeError(
            f"implausible MFU {mfu:.3f} (step {dt * 1e3:.2f} ms vs "
            f"FLOPs floor {flops_per_step / peak * 1e3:.2f} ms) — "
            "timing artifact, measurement rejected"
        )
    n_params = sum(
        int(np.prod(np.shape(x)))
        for x in jax.tree.leaves(state_box[0].params)
    )
    from ddl_tpu.models.remat import resolve as _resolve_remat

    return {
        "attn_impl": attn_impl,
        "size": size,
        "remat": _resolve_remat(cfg.remat),
        "optimizer_sharding": tc.optimizer_sharding,
        "grad_comm": tc.grad_comm,
        "dp": n_dp,
        "params_billions": round(n_params / 1e9, 3),
        "tokens_per_sec": round(tokens_per_step / dt, 1),
        "step_time_ms": round(dt * 1e3, 2),
        "model_tflops_per_sec": round(flops_per_step / dt / 1e12, 2),
        "mfu": round(mfu, 4) if mfu is not None else None,
        "device_kind": kind,
        "first_loss": round(first_loss, 4),
        "final_loss": round(final_loss, 4),
    }


def decode_trial(
    gen_call, gen_short_call, batch: int, prompt_len: int,
    new_tokens: int, short_tokens: int, vocab: int,
):
    """One timed serving trial, shared by the bench and tools/
    probe_moe.py so the decode method cannot drift between published
    numbers.

    Decode is timed DIRECTLY as the delta of two generate calls that
    differ only in ``max_new_tokens`` (``new_tokens`` vs
    ``short_tokens``): both programs run the identical prefill, so the
    difference is purely ``new_tokens - short_tokens`` decode steps.
    The previous method — subtracting a SEPARATELY-JITTED prefill from
    the total — understated decode (and inflated MBU): the standalone
    prefill program carries its own dispatch/readback overhead and XLA
    fuses it differently than the in-program prefill it was standing in
    for (advisor r5).  ``prefill_s`` is now the derived remainder
    (total minus the per-step cost times the full step count).

    Validates the generated tokens of BOTH calls and the spans; returns
    ``(decode_s, prefill_s)`` where ``decode_s`` covers the full
    program's ``new_tokens - 1`` scanned steps.  Raises on invalid
    tokens or an implausible span — run it under :func:`best_valid` so
    an artifact trial can never win selection.  Both calls are
    host-synchronized HERE (``np.asarray``) so a caller passing bare
    async jitted functions cannot accidentally time dispatch only."""
    if not 0 < short_tokens < new_tokens:
        raise RuntimeError(
            f"short_tokens {short_tokens} must lie in (0, {new_tokens})"
        )
    t0 = time.perf_counter()
    out = np.asarray(gen_call())
    total_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    out_short = np.asarray(gen_short_call())
    short_s = time.perf_counter() - t0

    for toks, n in ((out, new_tokens), (out_short, short_tokens)):
        gen_tok = toks[:, prompt_len:]
        if gen_tok.shape != (batch, n) or not (
            (gen_tok >= 0) & (gen_tok < vocab)
        ).all():
            raise RuntimeError("decode produced invalid tokens")
    delta_s = total_s - short_s
    if delta_s <= 0:
        # The implausibility guard, on the new quantity: the longer
        # program measuring faster than the shorter one is a timing
        # artifact, never physics.
        raise RuntimeError(
            f"implausible decode delta {delta_s * 1e3:.2f} ms (full "
            f"{total_s * 1e3:.2f}, short {short_s * 1e3:.2f}) — "
            "timing artifact, rejected"
        )
    step_s = delta_s / (new_tokens - short_tokens)
    decode_s = step_s * (new_tokens - 1)
    prefill_s = total_s - decode_s
    if prefill_s <= 0:
        raise RuntimeError(
            f"implausible derived prefill {prefill_s * 1e3:.2f} ms "
            f"(total {total_s * 1e3:.2f}, decode {decode_s * 1e3:.2f}) "
            "— timing artifact, rejected"
        )
    return decode_s, prefill_s


def _run_decode(platform: str, size: str = "small"):
    """Serving-phase benchmark: KV-cache prefill + autoregressive decode.

    Measures the inference path (``models.llama.generate``: one cached
    prefill forward, then ``lax.scan`` decode steps) the way a server
    runs it — bf16 weight storage, greedy decode, the whole
    prefill+decode program under one ``jax.jit`` so the clock spans a
    single device program and stops only after a host read-back of the
    generated tokens.  Decode-only time comes from the delta of two
    generate programs differing only in ``max_new_tokens`` (see
    :func:`decode_trial`) — the in-program prefill cancels exactly,
    unlike the old separately-jitted prefill subtraction.

    Decode steps are memory-bound (every token streams the full bf16
    parameter set from HBM), so the quality metric is model-bandwidth
    utilization: ``mbu_params = param_bytes * steps_per_sec /
    peak_hbm`` — a lower bound, ignoring the KV-cache read.  The same
    plausibility gating as training applies: MBU must land in (0, 1)
    or the measurement is rejected, and generated tokens must be valid
    vocab ids.
    """
    import jax
    import jax.numpy as jnp

    from ddl_tpu.models import llama

    base, _, _, _ = _train_config(platform, size)
    cfg = dataclasses.replace(base, param_dtype=jnp.bfloat16)
    if platform == "tpu":
        batch, prompt_len, new_tokens, trials = 8, 512, 256, 2
    else:
        # Two trials even on CPU: the delta method rejects a trial on
        # either span's noise, so one spare keeps the gate stable.
        batch, prompt_len, new_tokens, trials = 2, 32, 16, 2
    # Serving batch is the MBU lever (weight reads amortize over the
    # batch); sweepable for the batch-scaling record.
    batch = int(os.environ.get("DDL_BENCH_DECODE_BATCH", batch))

    params = llama.init_params(cfg, jax.random.key(0))
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(
        rng.integers(0, cfg.vocab, (batch, prompt_len)).astype(np.int32)
    )

    # Half the steps for the short program: enough step-count contrast
    # for a stable delta, same prefill, same cache geometry class.
    short_tokens = max(1, new_tokens // 2)

    @jax.jit
    def gen(p, toks):
        return llama.generate(p, toks, cfg, max_new_tokens=new_tokens)

    @jax.jit
    def gen_short(p, toks):
        return llama.generate(p, toks, cfg, max_new_tokens=short_tokens)

    np.asarray(gen(params, prompt))  # compile + warm
    np.asarray(gen_short(params, prompt))

    n_params = sum(
        int(np.prod(np.shape(x))) for x in jax.tree.leaves(params)
    )
    # MBU byte count EXCLUDES the embedding table: decode gathers one
    # row per generated token (B rows of d_model), not the (vocab, d)
    # table — counting it overstated MBU by ~5-6% at the bench configs
    # (advisor r5).  Every other weight streams fully per step.
    mbu_params = n_params - cfg.vocab * cfg.d_model
    kind = jax.local_devices()[0].device_kind
    peak_hbm = _peak_hbm(kind) if platform == "tpu" else None
    steps = new_tokens - 1

    def _one_trial():
        """One gated measurement: both generate programs timed so the
        plausibility gate runs per trial INSIDE ``best_valid`` — a
        gate-after-selection would let an artifact run win selection
        and discard its valid companions (see ``best_valid``)."""
        # Decode-only span via the two-program delta (the in-program
        # prefill cancels); max_new_tokens - 1 scanned forward steps
        # produce the remaining tokens (the last needs no forward of
        # its own).
        decode_s, prefill_s = decode_trial(
            lambda: gen(params, prompt),
            lambda: gen_short(params, prompt),
            batch, prompt_len, new_tokens, short_tokens, cfg.vocab,
        )
        mbu = (
            mbu_params * 2 * (steps / decode_s) / peak_hbm
            if peak_hbm else None
        )
        if mbu is not None and not (0.0 < mbu < 1.0):
            raise RuntimeError(
                f"implausible decode MBU {mbu:.3f} (per-step "
                f"{decode_s / steps * 1e3:.3f} ms vs param-read floor "
                f"{mbu_params * 2 / peak_hbm * 1e3:.3f} ms) — timing "
                "artifact, measurement rejected"
            )
        return decode_s, prefill_s, mbu

    decode_s, prefill_s, mbu = best_valid(
        trials, _one_trial, key=lambda r: r[0]
    )
    return {
        "size": size,
        "params_billions": round(n_params / 1e9, 3),
        "batch": batch,
        "prompt_len": prompt_len,
        "new_tokens": new_tokens,
        "prefill_ms": round(prefill_s * 1e3, 2),
        "prefill_tokens_per_sec": round(batch * prompt_len / prefill_s, 1),
        "decode_tokens_per_sec": round(batch * steps / decode_s, 1),
        "decode_step_ms": round(decode_s / steps * 1e3, 3),
        # mbu_params: non-embedding param bytes per step over peak HBM
        # (the embedding is a per-token row gather, not a full read).
        "mbu_params": round(mbu, 4) if mbu is not None else None,
        "mbu_param_bytes": int(mbu_params * 2),
        "device_kind": kind,
    }


def _run_fit(platform: str, attn_impl: str = "flash"):
    """End-to-end training throughput THROUGH the framework: producer
    workers → window rings → zero-copy window stream → one scanned
    multistep per window (``Trainer.fit(window_stream=True)``).  The
    delta against ``train_*``'s pipeline-less multistep ceiling IS the
    input-pipeline overhead.

    Timing: one warm fit compiles the scan (the Trainer caches it per
    window geometry), then a SHORT and a LONG fit on the same Trainer
    are both timed wall-to-wall and differenced — the fixed per-fit cost
    (worker spawn, handshake, first fills) cancels out, leaving the
    steady-state per-window cost: transfer + scan + loss read-back.

    ISSUE 12 — the FUSED vs UNFUSED A/B: the same geometry is measured
    under both dispatch disciplines, interleaved within each rep.
    Fused (``DDL_TPU_FUSED`` default) is the fused compute/ingest step
    — the data plane dispatched under the train step, slot release
    gated on the consuming step's done-future, loss read-back deferred
    one window; unfused (``fused=False``) is the synchronous
    discipline — the window lands (``block_until_ready``), then the
    scan runs to a blocking loss read-back — so measured fused step
    time ≈ max(compute, ingest) while unfused ≈ compute + ingest.
    Both stream the same deterministic windows; a separate untimed
    pass CRCs every window through the ``window_hook`` seam to assert
    ``byte_identical``.  The published ``tokens_per_sec`` is the
    winner's (never-slower invariant; ``winner`` names it), while
    ``pipeline_overhead`` stays the FUSED leg's gated number.
    """
    import optax

    from ddl_tpu import DataProducerOnInitReturn, ProducerFunctionSkeleton
    from ddl_tpu.models import llama
    from ddl_tpu.parallel.mesh import make_mesh
    from ddl_tpu.trainer import Trainer

    import jax

    cfg, batch, seq, _steps = _train_config(platform)
    cfg = type(cfg)(**{**cfg.__dict__, "attn_impl": attn_impl})
    # Steps per window: 8 on TPU; 4 on CPU — deep enough that the scan
    # dominates the window (the production shape), small enough for the
    # smoke-geometry runtime.
    bpw = 8 if platform == "tpu" else 4
    rows = bpw * batch
    short_windows, long_windows = 2, 10

    class TokenWindows(ProducerFunctionSkeleton):
        def on_init(self, producer_idx=0, **kw):
            self._rng = np.random.default_rng(producer_idx)
            return DataProducerOnInitReturn(
                nData=rows, nValues=seq, shape=(rows, seq), splits=(seq,),
                dtype=np.int32,
            )

        def post_init(self, my_ary, **kw):
            my_ary[:] = self._rng.integers(0, cfg.vocab, my_ary.shape)

        def execute_function(self, my_ary, **kw):
            # Representative refill: fresh tokens each window.
            my_ary[:] = self._rng.integers(0, cfg.vocab, my_ary.shape)

    from ddl_tpu.ingest import north_star_report
    from ddl_tpu.observability import Metrics

    mesh = make_mesh({"dp": 1}, devices=jax.local_devices()[:1])
    # Private registries: window-wait / overlap spans must cover ONLY
    # this measurement, and the fused leg's overlap-health counters
    # must not be polluted by the unfused leg's DELIBERATE blocking
    # waits — one trainer (and registry) per discipline, same init,
    # same compiled-scan geometry.
    fit_metrics = Metrics()
    unfused_metrics = Metrics()

    def make_trainer(metrics):
        return Trainer(
            loss_fn=lambda p, b: llama.next_token_loss(
                p, b[0], cfg, mesh=None
            ),
            optimizer=optax.adamw(3e-4),
            mesh=mesh,
            param_specs=llama.param_specs(cfg),
            init_params=llama.init_params(cfg, jax.random.key(0)),
            watchdog=False,
            metrics=metrics,
        )

    trainer = make_trainer(fit_metrics)
    trainer_u = make_trainer(unfused_metrics)

    # Simulated DMA landing wait (CPU A/B only; 0 on real chips, where
    # the H2D + ICI fan-out latency is the genuine article).  A 1-core
    # CPU host cannot overlap CPU-bound ingest with CPU-bound compute
    # no matter the dispatch discipline, so the A/B prices the landing
    # latency as an off-CPU timer at the step's entry — the
    # ThrottledBackend / SimulatedFabric wire-sleep pattern.  This
    # makes the leg a PROTOCOL contract test: the fused discipline
    # must hide a given landing latency under the still-running
    # previous scan; the unfused discipline exposes it serially.  The
    # latency rides the window_hook seam (applied before each window's
    # scan) and is recorded in the JSON as simulated_dma_ms.
    dma_ms = float(os.environ.get(
        "DDL_BENCH_FUSED_DMA_MS", "0" if platform == "tpu" else "30"
    ))

    def dma_hook(win):
        if dma_ms:
            time.sleep(dma_ms / 1e3)
        return win

    def one_fit(n, fused=True, hook=dma_hook):
        t = trainer if fused else trainer_u
        return t.fit(
            TokenWindows(), batch_size=batch, n_epochs=n, n_producers=2,
            mode="thread", output="jax", window_stream=True,
            fused=fused, window_hook=hook,
        )

    one_fit(short_windows, fused=True)  # compile + cache the scan
    one_fit(short_windows, fused=False)

    def timed(n, fused=True):
        t0 = time.perf_counter()
        res = one_fit(n, fused=fused)
        dt = time.perf_counter() - t0
        if not all(np.isfinite(v) for v in res.losses):
            raise RuntimeError(f"non-finite fit losses {res.losses}")
        return dt, res

    # Byte-identity A/B (untimed): the same deterministic producers
    # through both disciplines, every window CRC'd at the window_hook
    # seam — the fused protocol may change dispatch timing, never
    # bytes.  Hashing host-syncs per window, so it never shares a run
    # with the timed legs.
    import zlib

    def hashed_windows(fused):
        hashes = []

        def hook(w):  # untimed pass: no simulated landing wait
            hashes.append(zlib.crc32(np.asarray(w).tobytes()))
            return w

        one_fit(short_windows + 1, fused=fused, hook=hook)
        return hashes

    h_fused = hashed_windows(True)
    h_unfused = hashed_windows(False)
    byte_identical = bool(h_fused) and h_fused == h_unfused

    # MATCHED ceiling: the same per-window scan geometry (n_steps=bpw,
    # per_step=True, sharded device input, deferred loss read-back)
    # driven from ONE pre-staged in-memory window — no producers, no
    # rings, no stream.  pipeline_overhead against THIS is the input
    # pipeline's true cost; the old comparison against the train_*
    # multistep (different scan length, host-numpy input) bundled in
    # call-amortization differences bigger than the thing measured
    # (r5: the "overhead" swung -0.04..+0.10 on identical code).
    from jax.sharding import PartitionSpec as P

    from ddl_tpu.parallel.train import _named, make_multistep

    _, ceil_fn = make_multistep(
        trainer._loss_fn, optax.adamw(3e-4), mesh,
        llama.param_specs(cfg), n_steps=bpw,
        # Matched to the stream loops: window-stream scans run
        # undonated on the CPU client (donated calls execute
        # synchronously there — see Trainer._fit_windows), and the
        # ceiling must price the same compiled program shape.
        donate=platform == "tpu",
    )
    rng = np.random.default_rng(1)
    fixed_win = jax.device_put(
        rng.integers(0, cfg.vocab, (bpw, batch, seq)).astype(np.int32),
        _named(mesh, P(None, ("dp",))),
    )
    ceil_state = trainer._init_fn(
        llama.init_params(cfg, jax.random.key(1))
    )

    def ceiling_run(n):
        nonlocal ceil_state
        pending = None
        t0 = time.perf_counter()
        for _ in range(n):
            ceil_state, losses = ceil_fn(
                ceil_state, (fixed_win,), per_step=True
            )
            # Reduction dispatched right behind its scan — the fused
            # loop's discipline (an in-dispatch-order backend would
            # queue a read-time mean behind the NEXT scan); the ceiling
            # must match the thing it is a ceiling FOR.
            loss_mean = losses.mean()
            if pending is not None:
                float(pending)
            pending = loss_mean
        float(pending)
        return time.perf_counter() - t0

    ceiling_run(short_windows)  # compile + warm
    n_ceil = long_windows - short_windows

    # INTERLEAVED paired sampling: the shared-box noise is one-sided
    # AND drifts minute to minute (measured: identical pure loops swing
    # 320-500 ms/window on an idle 2-core box), so BOTH fit disciplines
    # and the ceiling are sampled back-to-back within each rep — fused
    # short/long, ceiling loop, unfused short/long, all inside a few
    # seconds of each other — and each leg's published overhead is the
    # MEDIAN of its per-rep paired estimates.  Cross-rep
    # min-of-each-side (the naive best_of composition) let the sides
    # pick different noise regimes and swung the ratio by more than the
    # thing measured.
    fit_metrics.reset()  # wait spans cover the measured fits only
    unfused_metrics.reset()
    reps = []  # (fused window_s, unfused window_s, ceiling window_s)
    res = None
    for _ in range(3):
        # Ceiling BETWEEN the fused and unfused pairs: the slow
        # within-rep drift then brackets every leg from both sides.
        dt_short_f = timed(short_windows, fused=True)[0]
        dt_long_f, res = timed(long_windows, fused=True)
        ceil_s = ceiling_run(n_ceil)
        dt_short_u = timed(short_windows, fused=False)[0]
        dt_long_u, _ = timed(long_windows, fused=False)
        df = dt_long_f - dt_short_f
        du = dt_long_u - dt_short_u
        if df <= 0 or du <= 0:
            continue  # a noise spike swallowed a short run; drop rep
        n_timed = long_windows - short_windows
        reps.append((df / n_timed, du / n_timed, ceil_s / n_ceil))
    if not reps:
        raise RuntimeError(
            "implausible fit timings: every interleaved rep had "
            f"{long_windows}-window wall <= {short_windows}-window wall"
        )

    # ONE rep publishes everything: the rep whose FUSED overhead (the
    # gated leg) is the median.  Selecting each leg's median rep
    # independently would compare fused and unfused samples from
    # different noise regimes — exactly the cross-rep composition the
    # interleaving above exists to prevent — and could flip the winner
    # label on a drifting box (the fused/unfused delta is smaller than
    # the documented drift).
    overs = sorted(1.0 - r[2] / r[0] for r in reps)
    med = overs[len(overs) // 2]
    rep = [r for r in reps if 1.0 - r[2] / r[0] == med][0]
    window_s, window_u, ceiling_window_s = rep
    ceiling_u = ceiling_window_s
    tokens_per_window = bpw * batch * seq
    tps_fused = tokens_per_window / window_s
    tps_unfused = tokens_per_window / window_u
    winner = "fused" if tps_fused >= tps_unfused else "unfused"
    fused_report = north_star_report(fit_metrics)
    return {
        "attn_impl": attn_impl,
        # Never-slower invariant: the published rate is the measured
        # winner's; ``winner`` names it.  Every other top-level key
        # stays the FUSED leg's (the default dispatch discipline).
        "tokens_per_sec": round(max(tps_fused, tps_unfused), 1),
        "winner": winner,
        "windows_timed": long_windows - short_windows,
        "steps_per_window": bpw,
        "window_time_ms": round(window_s * 1e3, 2),
        "ceiling_tokens_per_sec": round(
            tokens_per_window / ceiling_window_s, 1
        ),
        "ceiling_window_ms": round(ceiling_window_s * 1e3, 2),
        # Input-pipeline cost vs the MATCHED no-loader ceiling above
        # (>= 0 means the pipeline costs throughput; the FUSED leg is
        # gated <= 0.02 on CPU by tools/bench_smoke.py, at a geometry
        # where the unfused leg must show >= 0.10 — the A/B proves the
        # overlap, not just the absence of overhead).
        "pipeline_overhead": round(
            1.0 - ceiling_window_s / window_s, 4
        ),
        "fused": {
            "tokens_per_sec": round(tps_fused, 1),
            "window_time_ms": round(window_s * 1e3, 2),
            "pipeline_overhead": round(
                1.0 - ceiling_window_s / window_s, 4
            ),
        },
        "unfused": {
            "tokens_per_sec": round(tps_unfused, 1),
            "window_time_ms": round(window_u * 1e3, 2),
            "pipeline_overhead": round(1.0 - ceiling_u / window_u, 4),
            # The unfused window_wait is the EXPOSED ingest (the
            # block_until_ready on each window lands in it).
            "window_wait_s": round(
                unfused_metrics.timer("trainer.window_wait").total_s, 4
            ),
        },
        "fused_vs_unfused": round(tps_fused / tps_unfused, 3),
        "byte_identical": byte_identical,
        "simulated_dma_ms": dma_ms,
        "final_loss": round(res.losses[-1], 4),
        # Overlap health (ISSUE 5 + 12): trainer time spent waiting for
        # the next window + loader time in forced transfer-completion
        # waits — near zero when the data plane hides behind the
        # scanned steps — the measured ingest-overlap lower bound, the
        # fused-window count, the landing-slot high-water (0 on this
        # single-device CPU geometry; the ICI two-slot occupancy is a
        # chip/virtual-mesh measurement — see DDL_BENCH_MODE=ici), and
        # the pipeline-schedule gauges (zero: no pp axis here).
        "window_wait_s": round(
            fit_metrics.timer("trainer.window_wait").total_s, 4
        ),
        "release_wait_s": round(
            fit_metrics.timer("ingest.release_wait").total_s, 4
        ),
        "ingest_overlap_s": round(fused_report["ingest_overlap_s"], 4),
        "fused_windows": fused_report["fused_windows"],
        "slots_in_flight": fused_report["slots_in_flight"],
        "schedule": "none",
        # Process-level gauge (last compiled pipeline schedule; zero
        # here — this bench geometry has no pp axis).
        "pp_bubble": fused_report["pp_bubble"],
    }


# -- attention seq-length sweep ----------------------------------------------

# One harness shared with tools/probe_attn.py (which imports these), so the
# committed audit probe and the published bench numbers cannot diverge.
ATTN_H, ATTN_HKV, ATTN_D = 16, 8, 128  # bench model geometry
# In-jit chained iterations per dispatch: a call has a fixed host cost
# and iterations inside the scan have none, so a long chain keeps the
# per-iteration share of it small (pessimistic, never flattering).
ATTN_CHAIN = 64


def sweep_batch(T: int) -> int:
    """Batch size at each sweep length (memory-capped above 4k)."""
    return 4 if T <= 4096 else max(1, 4 * 4096 // T)


def attn_measure(impl, B, T, block_q=None, block_k=None, steps=2,
                 chain=ATTN_CHAIN):
    """Seconds per attention fwd+bwd at one geometry, artifact-hostile:
    ``chain`` data-dependent iterations inside ONE jitted scan, clock
    stopped only after a host read-back of the result.  Best of ``steps``
    timed calls — contention on the shared chip is one-sided noise."""
    import jax
    import jax.numpy as jnp

    from ddl_tpu.ops import flash_attention
    from ddl_tpu.parallel.ring_attention import attention_reference

    kv_repeat = ATTN_H // ATTN_HKV
    kq, kk, kv = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(kq, (B, T, ATTN_H, ATTN_D), jnp.bfloat16)
    k = jax.random.normal(kk, (B, T, ATTN_HKV, ATTN_D), jnp.bfloat16)
    v = jax.random.normal(kv, (B, T, ATTN_HKV, ATTN_D), jnp.bfloat16)
    if impl == "flash":
        kw = {}
        if block_q:
            kw["block_q"] = block_q
        if block_k:
            kw["block_k"] = block_k
        f = functools.partial(
            flash_attention, causal=True, kv_repeat=kv_repeat, **kw
        )
    else:
        f = functools.partial(
            attention_reference, causal=True, kv_repeat=kv_repeat
        )

    @jax.jit
    def chained(q, k, v):
        def body(carry, _):
            qq = q * (1.0 + carry * 1e-12).astype(q.dtype)
            l, grads = jax.value_and_grad(
                lambda a, b, c: jnp.sum(
                    f(a, b, c).astype(jnp.float32) ** 2
                ),
                argnums=(0, 1, 2),
            )(qq, k, v)
            return l + sum(
                jnp.sum(g.astype(jnp.float32)) for g in grads
            ), None

        tot, _ = jax.lax.scan(body, jnp.float32(0), None, length=chain)
        return tot

    _ = float(chained(q, k, v))  # compile + warmup (host sync)
    times = []
    for _i in range(steps):
        t0 = time.perf_counter()
        out = float(chained(q, k, v))
        times.append(time.perf_counter() - t0)
        if not np.isfinite(out):
            raise RuntimeError(f"non-finite output {out}")
    return float(np.min(times)) / chain


def _attn_sweep(seqs=(2048, 4096, 8192)):
    """Flash vs dense attention fwd+bwd across sequence lengths — shows
    where the Pallas kernel's linear memory beats XLA dense's T²
    (VERDICT r2 item 2)."""
    rows = []
    for T in seqs:
        B = sweep_batch(T)
        row: dict = {"T": T, "B": B}
        for impl in ("flash", "dense"):
            try:
                row[f"{impl}_ms"] = round(attn_measure(impl, B, T) * 1e3, 2)
            except Exception as e:  # noqa: BLE001 - dense may OOM at 8k+
                row[f"{impl}_err"] = f"{type(e).__name__}: {e}"[:120]
        if "flash_ms" in row and "dense_ms" in row:
            row["flash_speedup"] = round(
                row["dense_ms"] / row["flash_ms"], 3
            )
        rows.append(row)
    return rows


# -- shard-cache cold/warm A/B ------------------------------------------------


class _ThrottledRendezvous:
    """The ThrottledBackend pattern applied to the exchange wire: a
    Rendezvous wrapper whose ``put`` pays ``nbytes / link_bytes_per_sec``
    of simulated link time — so the wire-format A/B measures what a
    CONSTRAINED link (DCN between hosts, a shared NIC) actually sees:
    fewer bytes = faster rounds.  Take/discard/retire delegate."""

    span = "thread"

    def __init__(self, inner, link_bytes_per_sec: float):
        self.inner = inner
        self.link = float(link_bytes_per_sec)

    def put(self, key, rows):
        if self.link > 0:
            time.sleep(rows.nbytes / self.link)
        self.inner.put(key, rows)

    def take(self, *a, **kw):
        return self.inner.take(*a, **kw)

    def discard(self, key):
        self.inner.discard(key)

    def retire(self, key):
        self.inner.retire(key)


def _run_obs_ab() -> dict:
    """The tracing layer priced (ISSUE 15: ddl_tpu.obs) — three legs.

    1. **Armed-vs-disarmed overhead A/B** (measured, interleaved): the
       same deterministic THREAD window stream with span tracing + the
       flight recorder armed vs fully disarmed, per-window
       block_until_ready (the synchronous discipline — dispatch-timing
       noise cannot hide a per-window emission cost), best-of per side
       inside each rep.  Gated <= MAX_OBS_OVERHEAD by bench_smoke.
    2. **Byte identity** (untimed): armed and disarmed streams CRC'd
       per window — arming observability must never change data.
    3. **Chaos flight-record leg**: a seeded RING_CORRUPTION with the
       recorder armed — quarantine+replay keeps the stream
       byte-correct AND the corruption leaves a parseable post-mortem
       artifact naming the faulted window's (producer_idx, seq).

    The armed leg's north-star report must carry the histogram keys
    (window_latency_p50/p99, stage_breakdown) with a nonzero span
    count — documented percentiles that nothing emits would rot.
    """
    import tempfile
    import zlib

    from ddl_tpu import DistributedDataLoader, Marker, distributed_dataloader
    from ddl_tpu import faults
    from ddl_tpu.faults import FaultKind, FaultPlan, FaultSpec
    from ddl_tpu.ingest import north_star_report
    from ddl_tpu.obs import recorder as obs_recorder
    from ddl_tpu.obs import spans as obs_spans
    from ddl_tpu.observability import Metrics

    import jax

    n_windows = EPOCHS_STREAM
    n_epochs = n_windows + 2  # first two windows are warmup

    def run_stream(m, crcs=None, n=n_epochs):
        """One THREAD window stream; returns steady-state samples/s
        (None when ``crcs`` is given — identity legs are untimed)."""

        @distributed_dataloader(
            n_producers=2, mode="thread", nslots=STREAM_NSLOTS
        )
        def main(env):
            loader = DistributedDataLoader(
                StreamBenchProducer(), batch_size=BATCH,
                connection=env.connection, n_epochs=n, output="jax",
                metrics=m,
            )
            t0 = None
            seen = 0
            samples = 0
            for win in loader.windows(lookahead=STREAM_LOOKAHEAD):
                # Synchronous discipline: the window lands before the
                # next acquire, so the A/B prices the emission sites
                # themselves, not dispatch-queue timing.
                jax.block_until_ready(win)
                if crcs is not None:
                    crcs.append(
                        zlib.crc32(np.asarray(win).tobytes())
                    )
                seen += 1
                if seen == 2:
                    t0 = time.perf_counter()
                elif t0 is not None:
                    samples += N_DATA_STREAM
                loader.mark(Marker.END_OF_EPOCH)
            return (
                samples / (time.perf_counter() - t0)
                if t0 is not None and samples
                else None
            )

        return main()

    flight_dir = tempfile.mkdtemp(prefix="ddl-obs-bench-")

    def timed_leg(armed):
        m = Metrics()
        if armed:
            with obs_spans.tracing() as slog, obs_recorder.armed(
                directory=flight_dir
            ):
                rate = run_stream(m)
                report = north_star_report(m)
                return rate, report, slog.appended
        return run_stream(m), None, 0

    # -- leg 1: interleaved armed/disarmed overhead -----------------------
    # PAIRED estimates: each rep runs armed and disarmed back-to-back
    # and contributes ONE ratio; the published overhead is the median
    # rep's.  Cross-rep best-of-each-side (the naive composition) lets
    # the two sides pick different regimes of the box's one-sided
    # drift and swings the ratio by more than the thing measured —
    # the same pathology the fit bench's interleaving fixed (PR 12).
    pairs = []  # (armed rate, disarmed rate) per rep
    armed_report = None
    span_events = 0
    for _ in range(5):
        r_a, rep, n_spans = timed_leg(True)
        if rep is not None:
            armed_report = rep
            span_events = max(span_events, n_spans)
        r_d = timed_leg(False)[0]
        pairs.append((r_a, r_d))
    ratios = sorted(a / d for a, d in pairs)
    med_ratio = ratios[len(ratios) // 2]
    armed_rate, disarmed_rate = [
        p for p in pairs if p[0] / p[1] == med_ratio
    ][0]
    overhead = 1.0 - med_ratio

    # -- leg 2: byte identity (untimed) -----------------------------------
    crcs_armed: "list[int]" = []
    crcs_plain: "list[int]" = []
    with obs_spans.tracing(), obs_recorder.armed(directory=flight_dir):
        run_stream(Metrics(), crcs=crcs_armed, n=4)
    run_stream(Metrics(), crcs=crcs_plain, n=4)
    byte_identical = bool(crcs_armed) and crcs_armed == crcs_plain

    # -- leg 3: seeded corruption leaves a flight record ------------------
    chaos_m = Metrics()
    chaos_crcs: "list[int]" = []
    plan = FaultPlan(
        [FaultSpec(
            "producer.commit", FaultKind.RING_CORRUPTION, at=3, param=16,
        )],
        seed=7,
    )
    with obs_spans.tracing(), obs_recorder.armed(
        directory=flight_dir
    ) as rec, faults.armed(plan):
        run_stream(chaos_m, crcs=chaos_crcs, n=6)
    if not plan.fired:
        raise RuntimeError("obs chaos leg: corruption spec never fired")
    flight = {"written": False}
    for path in rec.dumped_paths:
        # Prefer the artifact that names the faulted window's full
        # (producer_idx, seq) identity — the consumer-side corruption
        # dump; the fault-trip dump (producer side) has no seq yet.
        with open(path) as f:
            record = json.load(f)
        win = record.get("window", {})
        flight = {
            "written": True,
            "path": path,
            "reason": record.get("reason"),
            "producer_idx": win.get("producer_idx"),
            "seq": win.get("seq"),
            "ring_events": len(record.get("events", [])),
        }
        if win.get("seq") is not None:
            break

    stage_breakdown = (
        armed_report.get("stage_breakdown", {}) if armed_report else {}
    )
    return {
        "windows_timed": n_windows,
        "window_mib": round(N_DATA_STREAM * N_VALUES * 4 / (1 << 20), 2),
        "disarmed_samples_per_sec": round(disarmed_rate, 1),
        "armed_samples_per_sec": round(armed_rate, 1),
        "overhead": round(overhead, 4),
        "byte_identical": byte_identical,
        "span_events": int(span_events),
        "window_latency_p50": (
            round(armed_report["window_latency_p50"], 6)
            if armed_report else None
        ),
        "window_latency_p99": (
            round(armed_report["window_latency_p99"], 6)
            if armed_report else None
        ),
        "stage_breakdown_keys": sorted(stage_breakdown),
        "chaos": {
            "corrupt_windows": chaos_m.counter(
                "integrity.corrupt_windows"
            ),
            "replays": chaos_m.counter("integrity.replays"),
            "stream_completed": len(chaos_crcs) == 6,
            "flight_dumps": chaos_m.counter("obs.flight_dumps"),
        },
        "flight_record": flight,
    }


def _run_preempt_ab() -> dict:
    """Preemption tolerance priced end to end (ISSUE 14).

    Three legs over one small deterministic window-stream geometry
    (pointnet, 4 steps/window — checkpoint cost, not model cost, is
    the thing measured):

    1. **Checkpoint-stall A/B** (measured, interleaved): the same fit
       checkpointing EVERY window through the synchronous Orbax path
       (``checkpoint_async=False`` — the fit stalls for serialize +
       fsync + rename) vs the async tier (the stall is the D2H
       snapshot alone; the write hides under training).  Published
       per-checkpoint stalls are each rep-median; the headline is the
       sync/async stall reduction.
    2. **Notice → resumed recovery** (deterministic): a seeded
       ``PREEMPT_NOTICE`` lands mid-run through the real
       ``resilience.notice`` chaos site, the guard drains (forced
       final checkpoint), and a fresh trainer resumes —
       ``recovery_wall_s`` = measured drain + restore-to-first-window
       time, with the resumed window stream BYTE-IDENTICAL and the
       loss curve bit-exact vs the uninterrupted reference.
    3. **Hard-kill lost-work bound** (deterministic): a run that dies
       with NO drain (its newest durable checkpoint one interval old)
       resumes losing exactly the windows since that checkpoint —
       ``lost_steps <= ckpt_interval * steps_per_window`` asserted in
       the block, with the replayed tail byte-identical too.
    """
    import tempfile
    import zlib as _zlib

    import optax
    from jax.sharding import PartitionSpec as P

    from ddl_tpu import faults
    from ddl_tpu.faults import FaultKind, FaultPlan, FaultSpec
    from ddl_tpu.models import pointnet
    from ddl_tpu.observability import Metrics
    from ddl_tpu.parallel.mesh import make_mesh
    from ddl_tpu.readers import ArrayProducer
    from ddl_tpu.resilience import PreemptionGuard
    from ddl_tpu.trainer import Trainer

    import jax

    cfg = pointnet.PointNetConfig(n_inputs=3, n_outputs=2)
    mesh = make_mesh({"dp": 1}, devices=jax.local_devices()[:1])
    seed, batch, window = 1234, 16, 64
    n_windows, interval, notice_at = 6, 2, 5
    bpw = window // batch  # steps per window

    def producer():
        data = np.random.default_rng(seed).random((256, 6)).astype(
            np.float32
        )
        return ArrayProducer(data, window_size=window, splits=(3, 2, 1))

    def make_trainer(ckpt_dir, metrics, every=1, **kw):
        return Trainer(
            loss_fn=lambda p, b: pointnet.weighted_mse_loss(p, b, cfg),
            optimizer=optax.adam(1e-2),
            mesh=mesh,
            param_specs=pointnet.param_specs(cfg),
            init_params=pointnet.init_params(cfg, jax.random.key(0)),
            batch_spec=P(("dp",)),
            checkpoint_dir=ckpt_dir,
            checkpoint_every_epochs=every,
            watchdog=False,
            metrics=metrics,
            **kw,
        )

    def run(trainer, n, crcs=None):
        def hook(win):
            if crcs is not None:
                crcs.append(_zlib.crc32(np.asarray(win).tobytes()))
            return win

        return trainer.fit(
            producer(), batch_size=batch, n_epochs=n, n_producers=2,
            mode="thread", output="jax", window_stream=True,
            window_hook=hook,
        )

    base = tempfile.mkdtemp(prefix="ddl-preempt-")

    # -- leg 1: per-checkpoint stall, sync vs async, interleaved -------
    def stall_rep(i):
        m_async, m_sync = Metrics(), Metrics()
        run(make_trainer(
            os.path.join(base, f"a{i}"), m_async, checkpoint_async=True,
        ), n_windows)
        run(make_trainer(
            os.path.join(base, f"s{i}"), m_sync, checkpoint_async=False,
        ), n_windows)
        ta = m_async.timer("resilience.ckpt_submit")
        ts = m_sync.timer("resilience.ckpt_sync")
        if not ta.count or not ts.count:
            raise RuntimeError("checkpoint timers never ticked")
        # The per-rep mean stalls ALSO land in the shared bounded
        # histograms (ddl_tpu.obs): the published medians below read
        # the histogram back — the stall distribution is a first-class
        # Metrics statistic now, not bench-local list sorting.
        stall_hist.observe("bench.ckpt_stall_async", ta.total_s / ta.count)
        stall_hist.observe("bench.ckpt_stall_sync", ts.total_s / ts.count)
        return ta.total_s / ta.count, ts.total_s / ts.count, ta.count

    from ddl_tpu.observability import Metrics as _Metrics

    stall_hist = _Metrics()
    reps = [stall_rep(i) for i in range(3)]
    async_stall = stall_hist.quantile("bench.ckpt_stall_async", 0.5)
    sync_stall = stall_hist.quantile("bench.ckpt_stall_sync", 0.5)

    # -- leg 2: notice → drain → byte-identical resume -----------------
    m_ref = Metrics()
    crcs_ref: list = []
    ref = run(
        make_trainer(os.path.join(base, "ref"), m_ref, every=interval),
        n_windows, crcs=crcs_ref,
    )
    m_b = Metrics()
    guard = PreemptionGuard(deadline_s=30.0, metrics=m_b)
    plan = FaultPlan([
        FaultSpec("resilience.notice", FaultKind.PREEMPT_NOTICE,
                  at=notice_at),
    ])
    crcs_b: list = []
    drain_dir = os.path.join(base, "drain")
    with faults.armed(plan):
        res_b = run(
            make_trainer(drain_dir, m_b, every=interval,
                         preemption_guard=guard),
            n_windows, crcs=crcs_b,
        )
    if not res_b.preempted:
        raise RuntimeError("injected preemption notice never drained")
    drain_s = m_b.timer("resilience.drain").total_s
    m_c = Metrics()
    crcs_c: list = []
    first_window_t: list = []
    t0 = time.perf_counter()

    def resume_hook(win):
        if not first_window_t:
            first_window_t.append(time.perf_counter() - t0)
        crcs_c.append(_zlib.crc32(np.asarray(win).tobytes()))
        return win

    t_resume = make_trainer(drain_dir, m_c, every=interval)
    res_c = t_resume.fit(
        producer(), batch_size=batch, n_epochs=n_windows, n_producers=2,
        mode="thread", output="jax", window_stream=True,
        window_hook=resume_hook,
    )
    recovery_wall_s = drain_s + (
        first_window_t[0] if first_window_t else float("nan")
    )
    drained_identical = (
        crcs_b + crcs_c == crcs_ref
        and res_b.losses + res_c.losses == ref.losses
        and res_c.state.step == ref.state.step
    )

    # -- leg 3: hard kill (no drain) — the lost-work bound -------------
    kill_dir = os.path.join(base, "kill")
    m_d = Metrics()
    run(make_trainer(kill_dir, m_d, every=interval), notice_at)
    # The run "died" at window `notice_at` with NO final checkpoint:
    # the newest durable generation is the last interval multiple.
    m_e = Metrics()
    crcs_e: list = []
    res_e = run(
        make_trainer(kill_dir, m_e, every=interval), n_windows,
        crcs=crcs_e,
    )
    resumed_from = res_e.resumed_from_epoch
    lost_windows = notice_at - resumed_from
    kill_identical = (
        crcs_e == crcs_ref[resumed_from:]
        and res_e.losses == ref.losses[resumed_from:]
    )
    if lost_windows * bpw > interval * bpw:
        raise RuntimeError(
            f"lost {lost_windows} windows > checkpoint interval "
            f"{interval} — the durability bound is broken"
        )

    return {
        "sync_ckpt_stall_s": round(sync_stall, 6),
        "async_ckpt_stall_s": round(async_stall, 6),
        "async_vs_sync": round(async_stall / sync_stall, 4),
        "stall_reduction": round(sync_stall / max(async_stall, 1e-9), 2),
        "checkpoints": int(reps[0][2]),
        "ckpt_interval_windows": interval,
        "steps_per_window": bpw,
        "windows": n_windows,
        "notice_window": notice_at,
        "drain_s": round(drain_s, 4),
        "drain_deadline_s": guard.deadline_s,
        "drained_within_deadline": bool(
            m_b.gauge("resilience.drain_within_deadline")
        ),
        "notices": m_b.counter("resilience.notices"),
        "final_ckpts": m_b.counter("resilience.final_ckpts"),
        "recovery_wall_s": round(recovery_wall_s, 4),
        "resumed_from_window": res_c.resumed_from_epoch,
        "hard_kill_resumed_from": resumed_from,
        "lost_steps": lost_windows * bpw,
        "lost_steps_bound": interval * bpw,
        "byte_identical": bool(drained_identical and kill_identical),
        "loss_bitexact": bool(
            res_b.losses + res_c.losses == ref.losses
            and res_e.losses == ref.losses[resumed_from:]
        ),
    }


def _run_failover_ab() -> dict:
    """Control-plane failover priced end to end (ISSUE 18).

    Four legs over the 2-mock-host THREAD cluster geometry (the
    tests/test_cluster.py shard ladder — control-plane cost, not data
    volume, is the thing measured):

    1. **Steady-state reference** (deterministic): journaled supervisor
       + HA stepper, leader never killed — the per-shard CRC window
       stream is the byte-identity baseline.
    2. **Mid-stream supervisor kill** (measured): the HA leader dies at
       a fixed epoch boundary; the standby's lease-expiry promotion
       replays the journal, re-fences the control channel, and re-sends
       adoptions.  ``takeover_s`` (promotion wall time + lease
       overshoot) is the headline; the window stream must complete
       BYTE-IDENTICAL to leg 1 with zero watchdog failures and the
       journal's replayed term at 2.
    3. **Envelope chaos** (deterministic counters): a host-loss
       adoption wired under ``CONTROL_MSG_DROP`` + ``CONTROL_MSG_DUP``
       at ``transport.control_send`` — the drop is absorbed by the
       acked seam's backoff retry, the dup by ``(incarnation, seq)``
       dedup (applied once, re-acked), full-shard coverage still
       byte-identical.
    4. **Scheduler fairness across the handover** (deterministic): the
       fake-clock admission script — export→adopt roundtrips bit-exact
       and the promoted scheduler grants the SAME order the
       uninterrupted one would have.
    """
    import tempfile
    import zlib as _zlib

    from ddl_tpu import (
        DataProducerOnInitReturn,
        DistributedDataLoader,
        Marker,
        ProducerFunctionSkeleton,
        distributed_dataloader,
    )
    from ddl_tpu import faults
    from ddl_tpu.cluster import (
        ClusterView,
        ElasticCluster,
        HostInfo,
        JournaledSupervisor,
        SupervisorHA,
        replay_journal,
    )
    from ddl_tpu.exceptions import StallTimeoutError
    from ddl_tpu.faults import FaultKind, FaultPlan, FaultSpec
    from ddl_tpu.observability import Metrics
    from ddl_tpu.serve import TenantSpec
    from ddl_tpu.serve.tenancy import FairShareScheduler
    from ddl_tpu.watchdog import Watchdog

    n_shards, rows, vals = 4, 8, 4
    n_epochs, kill_after = 8, 2
    lease_s = 0.3

    def shard_pattern(shard):
        return (
            shard * 1000.0
            + np.arange(rows * vals, dtype=np.float32) % 97
        ).reshape(rows, vals)

    class _ShardProducer(ProducerFunctionSkeleton):
        def __init__(self, ranges_by_producer):
            self.ranges_by_producer = dict(ranges_by_producer)
            self.ranges = ()

        def _shards(self):
            return [s for a, b in self.ranges for s in range(a, b)]

        def on_init(self, producer_idx=1, **kw):
            self.it = 0
            self.ranges = tuple(self.ranges_by_producer[producer_idx])
            return DataProducerOnInitReturn(
                nData=rows, nValues=vals, shape=(rows, vals),
                splits=(vals,),
            )

        def post_init(self, my_ary, **kw):
            my_ary[:] = 0.0

        def execute_function(self, my_ary, **kw):
            shards = self._shards()
            my_ary[:] = shard_pattern(shards[self.it % len(shards)])
            self.it += 1

        def adopt_shards(self, ranges, **kw):
            self.ranges = tuple(ranges)

    def two_host_view():
        return ClusterView.bootstrap(
            [
                HostInfo(0, loader_ranks=(1,), trainer_ranks=(0,)),
                HostInfo(1, loader_ranks=(2,)),
            ],
            n_shards=n_shards,
        )

    base = tempfile.mkdtemp(prefix="ddl-failover-")

    def drain(journal_path, m, *, kill=False, plan=None, kill_host=None,
              n=n_epochs, pace_s=0.0):
        """Run the pipeline; returns (crcs-by-shard, seen-by-shard, ha)."""
        producer = _ShardProducer({1: ((0, 2),), 2: ((2, 4),)})
        # Per-shard CRC streams: within one shard the order is the
        # producer's deterministic cycle, immune to cross-producer
        # interleave timing.
        crcs: dict = {}

        @distributed_dataloader(n_producers=2, mode="thread")
        def run(env):
            sup = JournaledSupervisor(
                two_host_view(), journal=journal_path, lease_s=30.0,
                poll_interval_s=0.05, metrics=m,
            )
            elastic = ElasticCluster(sup, workers=env.workers, metrics=m)
            ha = SupervisorHA(
                sup, elastic=elastic, lease_s=lease_s, standbys=1,
                metrics=m,
            ).start()
            loader = DistributedDataLoader(
                producer, batch_size=rows, connection=env.connection,
                n_epochs=n, output="numpy", timeout_s=60.0, metrics=m,
                cluster=elastic,
            )
            wd = Watchdog(
                env.workers, poll_interval_s=0.05, stall_budget_s=60.0,
                respawn=True, metrics=m,
            ).start()
            seen: dict = {}
            try:
                for ep in range(n):
                    for (win,) in loader:
                        shard = int(win[0, 0] // 1000)
                        crcs.setdefault(shard, []).append(
                            _zlib.crc32(
                                np.ascontiguousarray(win).tobytes()
                            )
                        )
                        seen.setdefault(shard, []).append(win.copy())
                        loader.mark(Marker.END_OF_BATCH)
                    loader.mark(Marker.END_OF_EPOCH)
                    if pace_s:
                        time.sleep(pace_s)
                    if kill and ep == kill_after:
                        ha.kill_leader()
                    if kill and ep == kill_after + 1:
                        deadline = time.monotonic() + 10.0
                        while ha.leader is None:
                            if time.monotonic() > deadline:
                                raise RuntimeError(
                                    "standby never promoted"
                                )
                            time.sleep(0.02)
                    if kill_host is not None and ep == kill_host:
                        elastic.kill_host(1)
            finally:
                wd.stop()
                ha.stop()
            return seen, ha

        if plan is not None:
            with faults.armed(plan):
                seen, ha = run()
        else:
            seen, ha = run()
        return crcs, seen, ha

    # -- legs 1+2: steady-state vs mid-stream supervisor kill ----------
    m_ref = Metrics()
    crcs_ref, _, _ = drain(os.path.join(base, "ref.jrn"), m_ref)
    m_b = Metrics()
    crcs_b, _, ha_b = drain(
        os.path.join(base, "kill.jrn"), m_b, kill=True,
    )
    if ha_b.last_takeover_s is None:
        raise RuntimeError("HA leader kill never produced a promotion")
    replayed = replay_journal(os.path.join(base, "kill.jrn"))
    byte_identical = bool(
        crcs_b == crcs_ref
        and sorted(crcs_ref) == list(range(n_shards))
    )

    # -- leg 3: adoption under envelope drop + dup chaos ---------------
    m_c = Metrics()
    plan = FaultPlan([
        FaultSpec("transport.control_send", FaultKind.CONTROL_MSG_DROP,
                  at=1),
        FaultSpec("transport.control_send", FaultKind.CONTROL_MSG_DUP,
                  at=2),
    ])
    _, seen_c, _ = drain(
        os.path.join(base, "chaos.jrn"), m_c, plan=plan, kill_host=1,
        n=14, pace_s=0.02,
    )
    if not plan.fired:
        raise RuntimeError("envelope chaos specs never fired")
    coverage_ok = sorted(seen_c) == list(range(n_shards)) and all(
        np.array_equal(w, shard_pattern(s))
        for s, wins in seen_c.items() for w in wins
    )

    # -- leg 4: scheduler fairness across the handover -----------------
    class _FakeClock:
        def __init__(self, t=100.0):
            self.t = t

        def __call__(self):
            return self.t

    def sched(clock):
        s = FairShareScheduler(
            quantum_bytes=1 << 20, metrics=Metrics(), clock=clock,
        )
        s.register(TenantSpec("heavy", weight=2.0,
                              byte_budget_per_s=float(4 << 20)))
        s.register(TenantSpec("light", weight=1.0,
                              byte_budget_per_s=float(1 << 20)))
        return s

    def script(s, clock, steps):
        trace = []
        for _ in range(steps):
            clock.t += 0.25
            for name in ("heavy", "light"):
                try:
                    s.admit(name, timeout_s=0.0)
                except StallTimeoutError:
                    trace.append((name, "throttled"))
                    continue
                s.note_served(name, 1 << 20)
                trace.append((name, "granted"))
        return trace

    c1, c2 = _FakeClock(), _FakeClock()
    uninterrupted, interrupted = sched(c1), sched(c2)
    script(uninterrupted, c1, 4)
    script(interrupted, c2, 4)
    snap = interrupted.export_state(now=c2())
    standby = FairShareScheduler(metrics=Metrics(), clock=c2)
    standby.adopt_state(snap, now=c2())
    roundtrip_exact = standby.export_state(now=c2()) == snap
    tail_a = script(uninterrupted, c1, 6)
    tail_b = script(standby, c2, 6)
    fairness_preserved = bool(
        tail_a == tail_b
        and any(t == ("light", "throttled") for t in tail_b)
    )

    dedup_evidence = (
        m_c.counter("ctrl.acked_dup") + m_c.counter("ctrl.stale_acks")
    )
    return {
        "takeover_s": round(ha_b.last_takeover_s, 4),
        "lease_s": lease_s,
        "kill_after_epoch": kill_after,
        "epochs": n_epochs,
        "journal_term": replayed.term,
        "journal_records": replayed.records,
        "promotions": int(m_b.counter("cluster.promotions")),
        "supervisor_crashes": int(
            m_b.counter("cluster.supervisor_crashes")
        ),
        "watchdog_failures": int(m_b.counter("watchdog.failures")),
        "byte_identical": byte_identical,
        "windows": sum(len(v) for v in crcs_b.values()),
        "chaos": {
            "wire_drops": int(m_c.counter("ctrl.wire_drops")),
            "wire_dups": int(m_c.counter("ctrl.wire_dups")),
            "retries": int(m_c.counter("ctrl.retries")),
            "acked": int(m_c.counter("ctrl.acked")),
            "dedup_evidence": int(dedup_evidence),
            "watchdog_failures": int(m_c.counter("watchdog.failures")),
            "coverage_byte_identical": bool(coverage_ok),
        },
        "scheduler_roundtrip_bit_exact": bool(roundtrip_exact),
        "fairness_preserved": fairness_preserved,
    }


def _run_fabric_soak() -> dict:
    """Multi-job ingest fabric soak (ISSUE 19): one supervisor-resident
    admission authority serving a simulated 100-host / 50-job fleet.

    Every admission decision in every leg rides the REAL control path —
    :class:`~ddl_tpu.serve.fabric.FabricClient` envelopes into
    :class:`~ddl_tpu.serve.fabric.IngestFabric` — never a direct
    scheduler poke (ddl-lint DDL026 bans those; this file's exemption
    covers the in-process DRR reference legs of the failover bench, not
    this one).  Legs:

    1. **Zipf fairness soak.**  ``DDL_BENCH_FABRIC_JOBS`` jobs (default
       50) with Zipf-distributed weights, byte budgets priced
       proportional to weight, probed lockstep from
       ``DDL_BENCH_FABRIC_HOSTS`` host bindings (default 100, two per
       job) under a simulated clock.  Demand exceeds every job's
       budget, so served bytes must track weights: the headline is the
       max per-job **weighted-share deviation**
       ``|observed - expected| / expected`` (bench_smoke gates it).
    2. **Scale reaction.**  A job registered mid-soak must reach 80% of
       its budgeted rate within the reaction SLO (simulated seconds
       from registration to rate attainment).
    3. **Preemption drain.**  The heaviest jobs take one in-flight
       grant each, the supervisor revokes them under
       ``DDL_TPU_FABRIC_DRAIN_SLO_S``, and the grants complete from
       other hosts while the drain waits — drained-inside-SLO is the
       gate, and a revoked job's probe must raise the typed
       ``WindowsRevoked``.
    4. **Per-job cache accounting.**  All jobs share ONE
       :class:`~ddl_tpu.cache.CacheStore` warmed through a
       :class:`~ddl_tpu.cache.backends.ThrottledBackend`-priced loader;
       the per-job ``job.<id>.cache.*`` counters must account for every
       access the store saw (isolation without partitioning the tier).
    5. **Transport pricing.**  One full window-transport round across
       the 100-host :class:`~ddl_tpu.cluster.placement.SimulatedFabric`
       (islanded link costs), measured bytes/s.
    6. **Supervisor kill.**  The same demand trace runs twice — once
       uninterrupted, once with the authority killed mid-soak and
       rebuilt via :meth:`IngestFabric.from_journal` — and the
       admission ORDER (the grant audit log) must be bit-identical, the
       rebuilt scheduler ledger bit-equal to the uninterrupted one, and
       a re-sent pre-kill envelope answered from the journaled reply
       (exactly-once across the failover boundary).
    """
    import dataclasses as _dc
    import tempfile
    import threading

    from ddl_tpu.cache import CacheKey, CacheStore
    from ddl_tpu.cache.backends import ThrottledBackend
    from ddl_tpu.cluster.placement import SimulatedFabric, measure_assignment
    from ddl_tpu.cluster.topology import LinkCosts
    from ddl_tpu.exceptions import StallTimeoutError, WindowsRevoked
    from ddl_tpu.observability import Metrics
    from ddl_tpu.serve.fabric import FabricClient, FabricJob, IngestFabric
    from ddl_tpu.serve.jobs import JobCacheView, JobSpec

    n_jobs = int(os.environ.get("DDL_BENCH_FABRIC_JOBS", "50"))
    n_hosts = int(os.environ.get("DDL_BENCH_FABRIC_HOSTS", "100"))
    steps = int(os.environ.get("DDL_BENCH_FABRIC_STEPS", "160"))
    window = 16 << 10  # small windows: fine-grained share quantization
    dt = 0.25  # simulated seconds per lockstep step
    zipf_s = 0.6  # Zipf exponent over job ranks (weight spread ~10x)
    base_rate = float(16 << 10)  # bytes/s budget per unit weight

    class _Clock:
        def __init__(self, t=1000.0):
            self.t = t

        def __call__(self):
            return self.t

    raw = [(k + 1) ** -zipf_s for k in range(n_jobs)]
    weights = [r * n_jobs / sum(raw) for r in raw]

    def build_fleet(n_j, n_h, clock, m, fab):
        """Register n_j jobs through the fabric and fan out n_h host
        bindings (hosts round-robin over jobs), every handle speaking
        the envelope protocol through its own client."""
        clients = [
            FabricClient(fab, f"host{h:03d}", metrics=m, clock=clock)
            for h in range(n_h)
        ]
        jobs = []
        for j in range(n_j):
            spec = JobSpec(
                job_id=f"job{j:02d}",
                weight=weights[j],
                byte_budget_per_s=weights[j] * base_rate,
            )
            jobs.append(clients[j % n_h].register_job(spec))
        bindings = []
        for h in range(n_h):
            j = jobs[h % n_j]
            bindings.append(
                FabricJob(clients[h], j.job_id, j.index, j.seq_base)
            )
        return clients, jobs, bindings

    def soak(bindings, clock, n_steps, served, throttled):
        """Lockstep demand: every binding probes non-blockingly each
        step; a grant is charged immediately (the loader's
        acquire→release cycle collapsed to zero simulated time)."""
        for _ in range(n_steps):
            clock.t += dt
            for b in bindings:
                try:
                    b.admit(timeout_s=0.0)
                except (StallTimeoutError, WindowsRevoked):
                    throttled[0] += 1
                    continue
                b.note_served(window)
                served[b.job_id] = served.get(b.job_id, 0) + window

    # -- leg 1: Zipf fairness soak -------------------------------------
    clock = _Clock()
    m = Metrics()
    fab = IngestFabric(journal=None, metrics=m, clock=clock)
    clients, jobs, bindings = build_fleet(n_jobs, n_hosts, clock, m, fab)
    served: dict = {}
    throttled = [0]
    soak(bindings, clock, steps, served, throttled)
    total = float(sum(served.values()))
    wsum = sum(weights)
    deviations = []
    for j in range(n_jobs):
        expected = weights[j] / wsum
        observed = served.get(f"job{j:02d}", 0) / total
        deviations.append(abs(observed - expected) / expected)
    dev_max = max(deviations)
    dev_mean = sum(deviations) / len(deviations)

    # -- leg 2: scale reaction (a job arrives mid-fleet) ----------------
    late = clients[0].register_job(
        JobSpec("late", weight=1.0, byte_budget_per_s=base_rate)
    )
    late_b = FabricJob(clients[1], late.job_id, late.index, late.seq_base)
    t_reg = clock.t
    reaction_s = None
    late_served: dict = {}
    for _ in range(40):
        soak([late, late_b], clock, 1, late_served, throttled)
        elapsed = clock.t - t_reg
        if late_served.get("late", 0) >= 0.8 * base_rate * elapsed:
            reaction_s = elapsed
            break
    if reaction_s is None:
        raise RuntimeError("late job never reached 80% of its fair rate")

    # -- leg 3: preemption drain under the SLO --------------------------
    slo_s = 2.0
    drain_jobs = [f"job{j:02d}" for j in range(3)]  # the heaviest
    clock.t += 30.0  # refill every bucket: the grants must be clean
    for b in bindings[:3]:
        b.admit(timeout_s=5.0)  # in-flight: note_served withheld
    finisher = threading.Thread(
        target=lambda: (
            time.sleep(0.05),
            [b.note_served(window) for b in bindings[:3]],
        ),
        daemon=True,
    )
    t0 = time.perf_counter()
    finisher.start()
    reply = fab.revoke_jobs(slo_s=slo_s, job_ids=drain_jobs)
    drain_s = time.perf_counter() - t0
    finisher.join(timeout=10)
    drained = bool(reply.ok and reply.value["drained"])
    revoked_probes = 0
    try:
        bindings[0].admit(timeout_s=0.0)  # still fenced out post-drain
    except WindowsRevoked:
        revoked_probes += 1
    fab.clear_job_revocations(drain_jobs)
    bindings[0].admit(timeout_s=5.0)  # the rejoin edge readmits
    bindings[0].note_aborted()

    # -- leg 4: per-job accounting on the ONE shared cache --------------
    cache_jobs = [f"job{j:02d}" for j in range(8)]
    store = CacheStore(ram_budget_bytes=32 << 20, metrics=Metrics())
    backend = ThrottledBackend(latency_s=0.001)
    with tempfile.TemporaryDirectory(prefix="ddl_fabric_cache_") as td:
        shard_path = os.path.join(td, "shard.bin")
        with open(shard_path, "wb") as f:
            f.write(np.arange(1024, dtype=np.float32).tobytes())

        def load_shard():
            with backend.open(shard_path) as fh:
                return np.frombuffer(fh.read(), dtype=np.float32).copy()

        views = {
            job_id: JobCacheView(store, job_id, metrics=m)
            for job_id in cache_jobs
        }
        accesses = 0
        for i, job_id in enumerate(cache_jobs):
            rng = np.random.default_rng(1000 + i)
            # Zipf-ish popularity over 32 shared shard keys: the head
            # keys overlap across jobs, so one job's miss is the
            # fleet's warm hit.
            for k in (rng.zipf(1.5, size=40) - 1) % 32:
                key = CacheKey(
                    source=backend.fingerprint(shard_path),
                    shard=f"shard-{k}",
                    reader="fabric-bench",
                )
                views[job_id].get_or_load(key, load_shard)
                accesses += 1
    per_job = {j: views[j].counts() for j in cache_jobs}
    hits = sum(c["hits"] for c in per_job.values())
    misses = sum(c["misses"] for c in per_job.values())
    # The store's fleet-global counters live in ITS registry; the
    # per-job views must account for every access it saw.
    accounted = bool(
        hits + misses == accesses
        and hits == store.metrics.counter("cache.hits")
        and misses == store.metrics.counter("cache.misses")
    )

    # -- leg 5: one transport round over the simulated 100-host fabric --
    bw = {}
    for a in range(n_hosts):
        for b in range(a + 1, n_hosts):
            # Islands of 10 hosts: 4 GB/s inside, 1 GB/s across.
            bw[(a, b)] = 4e9 if a // 10 == b // 10 else 1e9
    costs = LinkCosts(bw, default_bytes_per_s=1e9)
    assignment = tuple((h, (h + 1) % n_hosts) for h in range(n_hosts))
    fabric_bps = measure_assignment(
        assignment, SimulatedFabric(costs), payload_bytes=256 << 10, reps=2,
    )

    # -- leg 6: supervisor kill mid-soak --------------------------------
    kj, kh, ksteps, kill_after = 10, 10, 12, 6
    base = tempfile.mkdtemp(prefix="ddl_fabric_")

    def kill_trace(kill: bool):
        c = _Clock()
        mk = Metrics()
        journal = os.path.join(base, "kill.jrn") if kill else None
        f1 = IngestFabric(
            journal=journal, metrics=mk, clock=c, snapshot_every=1,
        )
        cl, _, binds = build_fleet(kj, kh, c, mk, f1)
        srv: dict = {}
        thr = [0]
        soak(binds, c, kill_after, srv, thr)
        dedup = 0
        if kill:
            # Capture the last applied envelope off client 0's wire,
            # then kill the authority object entirely.
            captured = {}
            orig = cl[0]._channel

            def tap(cid, env):
                captured["env"] = env
                return orig(cid, env)

            cl[0]._channel = tap
            binds[0].admit(timeout_s=5.0)
            binds[0].note_served(window)
            srv[binds[0].job_id] = srv.get(binds[0].job_id, 0) + window
            cl[0]._channel = orig
            del f1  # the leader is dead; only the journal survives
            f2 = IngestFabric.from_journal(journal, metrics=mk, clock=c)
            for one in cl:
                one.rebind(f2)
            # A post-failover retry of the captured (already applied)
            # envelope, re-fenced at the successor's term: answered
            # from the journaled reply, ledger untouched.
            before = mk.counter("fabric.dup_replies")
            retry = _dc.replace(captured["env"], fence=f2.term)
            reply2, ack2 = f2.handle(cl[0].client_id, retry)
            dedup = int(mk.counter("fabric.dup_replies") - before)
            if not (reply2.ok and ack2.seq == retry.seq):
                raise RuntimeError(
                    "post-failover duplicate was not answered from the "
                    f"journaled reply: {reply2}"
                )
            f1 = f2
        else:
            binds[0].admit(timeout_s=5.0)
            binds[0].note_served(window)
            srv[binds[0].job_id] = srv.get(binds[0].job_id, 0) + window
        soak(binds, c, ksteps - kill_after, srv, thr)
        return f1, c, srv, dedup

    ref_fab, ref_clock, ref_served, _ = kill_trace(kill=False)
    k_fab, k_clock, k_served, dedup_replies = kill_trace(kill=True)
    order_identical = bool(
        k_fab.admission_log == ref_fab.admission_log
        and len(ref_fab.admission_log) > 0
    )
    ledger_identical = bool(
        k_fab.scheduler.export_state(now=k_clock())
        == ref_fab.scheduler.export_state(now=ref_clock())
        and k_served == ref_served
    )

    return {
        "jobs": n_jobs,
        "hosts": n_hosts,
        "steps": steps,
        "window_bytes": window,
        "sim_dt_s": dt,
        "zipf_exponent": zipf_s,
        "granted_windows": int(total // window),
        "throttled_probes": int(throttled[0]),
        "decisions": fab._decisions,
        "share_deviation_max": round(dev_max, 4),
        "share_deviation_mean": round(dev_mean, 4),
        "scale_reaction_s": round(reaction_s, 3),
        "drain": {
            "jobs_revoked": len(drain_jobs),
            "drained": drained,
            "drain_s": round(drain_s, 4),
            "slo_s": slo_s,
            "revoked_probe_typed": revoked_probes == 1,
        },
        "cache": {
            "jobs": len(cache_jobs),
            "accesses": accesses,
            "hits": int(hits),
            "misses": int(misses),
            "hit_ratio": round(hits / max(accesses, 1), 4),
            "per_job_accounted": accounted,
        },
        "transport": {
            "hosts": n_hosts,
            "payload_bytes": 256 << 10,
            "measured_bytes_per_s": round(fabric_bps, 1),
        },
        "failover": {
            "jobs": kj,
            "steps": ksteps,
            "kill_after_step": kill_after,
            "admissions": len(ref_fab.admission_log),
            "admission_order_identical": order_identical,
            "scheduler_ledger_identical": ledger_identical,
            "dedup_replies": int(dedup_replies),
            "successor_term": k_fab.term,
        },
    }


def _run_wire_ab() -> dict:
    """Raw vs quantized vs compressed exchange wire over a throttled
    link (ISSUE 13, ROADMAP item 3).

    Two simulated instances run the REAL ``ThreadExchangeShuffler``
    exchange (the DCN shuffle wire) over a :class:`_ThrottledRendezvous`
    whose put pays simulated link time per byte — the ThrottledBackend
    pattern.  Three legs share one schedule: ``raw`` (fp32 lanes),
    ``int8`` (blockwise-quantized envelopes), and the best available
    lossless codec (compressible token-like float data, so compression
    has something to find).  Legs run INTERLEAVED best-of-reps; the
    winner is the headline under the never-slower invariant.

    Honesty gates baked into the block (bench_smoke enforces):
    the lossless leg's exchanged windows are byte-identical to raw's;
    the lossy leg's loss curve (a deterministic linear-probe SGD on the
    exchanged stream) passes the ``loss_parity`` gate with NONZERO
    drift (zero drift would mean the wire silently wasn't engaged);
    and the winner's ``wire_bytes`` is strictly below raw's at equal
    ``payload_bytes``.

    Geometry knobs: ``DDL_BENCH_WIRE_ROWS``/``COLS`` (window shape,
    default 256x512), ``DDL_BENCH_WIRE_ROUNDS`` (exchange rounds per
    rep, default 12), ``DDL_BENCH_WIRE_REPS`` (default 3),
    ``DDL_BENCH_WIRE_LINK_MBPS`` (simulated link, default 96).
    """
    import threading

    from ddl_tpu import wire as wire_mod
    from ddl_tpu.observability import Metrics
    from ddl_tpu.parallel.optimizer import loss_parity
    from ddl_tpu.shuffle import Rendezvous, ThreadExchangeShuffler
    from ddl_tpu.types import Topology

    rows = int(os.environ.get("DDL_BENCH_WIRE_ROWS", "256"))
    cols = int(os.environ.get("DDL_BENCH_WIRE_COLS", "512"))
    rounds = int(os.environ.get("DDL_BENCH_WIRE_ROUNDS", "12"))
    reps = int(os.environ.get("DDL_BENCH_WIRE_REPS", "3"))
    link = float(os.environ.get("DDL_BENCH_WIRE_LINK_MBPS", "96")) * (1 << 20)
    num_exchange = rows  # every row travels each round: worst-case wire
    # Token-like compressible float data (small integer vocabulary):
    # the lossless tier exists for exactly this shape of shard, and a
    # codec leg over pure noise would only measure zlib's overhead.
    base = [
        (np.random.default_rng(100 + i).integers(0, 32, (rows, cols)))
        .astype(np.float32)
        for i in range(2)
    ]

    def probe_losses(streams) -> list:
        """Deterministic linear-probe SGD over an exchanged window
        stream — the loss-parity gate's curve (one per leg)."""
        w = np.zeros(cols, np.float64)
        y = np.sin(np.arange(rows)).astype(np.float64)
        losses = []
        for win in streams:
            x = win.astype(np.float64)
            pred = x @ w
            losses.append(float(np.mean((pred - y) ** 2)))
            grad = 2.0 * x.T @ (pred - y) / rows
            w -= 1e-5 * grad
        return losses

    def run_leg(wire_dtype, codec):
        """One rep of one leg: both instances exchange `rounds` times
        over the throttled fabric; returns (samples/s, instance-0
        stream, metrics)."""
        rdv = _ThrottledRendezvous(Rendezvous(), link)
        streams = [[], []]
        metrics = [Metrics(), Metrics()]
        errors = []

        def worker(i):
            try:
                topo = Topology(
                    n_instances=2, instance_idx=i, n_producers=1
                )
                sh = ThreadExchangeShuffler(
                    topo, 1, num_exchange=num_exchange, rendezvous=rdv,
                    seed=7, wire_dtype=wire_dtype, codec=codec,
                    codec_level=1,  # wire compression wants speed
                    exchange_timeout_s=60.0,
                )
                sh.metrics = metrics[i]
                ary = base[i].copy()
                for _ in range(rounds):
                    sh.global_shuffle(ary)
                    streams[i].append(ary.copy())
            except Exception as e:  # noqa: BLE001 - surfaced below
                errors.append(e)

        t0 = time.perf_counter()
        ts = [
            threading.Thread(target=worker, args=(i,)) for i in range(2)
        ]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=120.0)
        if any(t.is_alive() for t in ts):
            raise RuntimeError("wire bench leg wedged (exchange stall)")
        if errors:
            raise errors[0]
        dt = time.perf_counter() - t0
        rate = 2 * rows * rounds / dt
        return rate, streams[0], metrics[0]

    codec = next(
        (c for c in ("zstd", "lz4", "zlib")
         if c in wire_mod.available_codecs()),
        "zlib",
    )
    legs = {"raw": ("raw", None), "int8": ("int8", None),
            codec: ("raw", codec)}
    best: dict = {k: 0.0 for k in legs}
    streams: dict = {}
    wire_stats: dict = {}
    for _ in range(reps):  # interleaved: box noise hits every leg alike
        for name, (wd, cd) in legs.items():
            rate, stream, m = run_leg(wd, cd)
            if rate > best[name]:
                best[name] = rate
            streams[name] = stream
            wire_stats[name] = m
    # Per-INSTANCE lane bytes per leg (the wire_stats registries are
    # instance 0's): num_exchange rows × cols × 4 bytes × rounds.
    raw_payload = float(num_exchange * cols * 4 * rounds)
    block: dict = {
        "link_bytes_per_sec": link,
        "rows": rows, "cols": cols, "rounds": rounds, "reps": reps,
        "codec": codec, "codec_level": 1,
        "legs": {},
    }
    for name in legs:
        m = wire_stats[name]
        enc = m.counter("wire.encoded_bytes")
        pay = m.counter("wire.payload_bytes")
        leg = {
            "samples_per_sec": round(best[name], 1),
            # The raw leg's fast path skips the envelope (and its
            # accounting): its wire bytes ARE the lane bytes.
            "wire_bytes": enc if enc else raw_payload,
            "payload_bytes": pay if pay else raw_payload,
        }
        block["legs"][name] = leg
    # Honesty gates: lossless byte identity, lossy parity (bounded AND
    # nonzero drift), encoded wire strictly below raw.
    block["byte_identical"] = all(
        np.array_equal(a, b)
        for a, b in zip(streams["raw"], streams[codec])
    )
    parity = loss_parity(
        probe_losses(streams["raw"]), probe_losses(streams["int8"])
    )
    block["parity"] = bool(parity["parity"])
    block["parity_drift"] = parity["max_rel_drift"]
    block["legs"]["int8"]["parity"] = parity
    winner = max(best, key=lambda k: best[k])
    block["winner"] = winner
    block["samples_per_sec"] = round(best[winner], 1)
    # Never-slower is a MEASUREMENT, not an argmax identity: the
    # selected winner must beat raw again in a fresh interleaved
    # confirmation pair (comparing argmax(best) against max(best) would
    # be a tautology that certifies nothing — bench_smoke asserts THIS
    # flag, retried once against box noise).
    if winner == "raw":
        block["never_slower"] = True
    else:
        confirm = {}
        for name in ("raw", winner):
            wd, cd = legs[name]
            rate, _, _ = run_leg(wd, cd)
            confirm[name] = round(rate, 1)
        block["confirm"] = confirm
        block["never_slower"] = bool(confirm[winner] >= confirm["raw"])
    block["wire_vs_raw"] = round(best[winner] / max(best["raw"], 1e-9), 3)
    w_leg = block["legs"][winner]
    block["winner_wire_below_raw"] = bool(
        winner == "raw"
        or (
            w_leg["wire_bytes"] < block["legs"]["raw"]["wire_bytes"]
            and w_leg["payload_bytes"]
            == block["legs"]["raw"]["payload_bytes"]
        )
    )
    return block


class _AsyncLinkTransfer:
    """A device-put stand-in that is genuinely IN FLIGHT: ``put``
    returns immediately and a timer thread 'lands' the batch after
    ``nbytes / link`` of simulated transfer time.  This is what gives
    prefetch depth something to buy — with a synchronous put, depth
    only changes queue length, never overlap."""

    def __init__(self, batch, link_bytes_per_sec: float):
        import threading

        self.batch = batch
        self._done = threading.Event()
        delay = (
            batch.nbytes / link_bytes_per_sec
            if link_bytes_per_sec > 0 else 0.0
        )
        t = threading.Timer(delay, self._done.set)
        t.daemon = True
        t.start()

    def wait(self):
        self._done.wait()
        return self.batch


def _run_autotune() -> dict:
    """Self-tuned vs shipped-defaults from a mis-matched cold start
    (ISSUE 20, ROADMAP item 4: ddl_tpu.tune).

    Both legs run the SAME two-phase workload on a deliberately
    constrained simulated fabric: (A) ``rounds`` real
    ``ThreadExchangeShuffler`` exchange rounds over a
    :class:`_ThrottledRendezvous` link, then (B) ``batches`` prefetched
    device transfers (:class:`_AsyncLinkTransfer` — put returns an
    in-flight handle, so depth buys real overlap) each followed by a
    fixed simulated compute step.

    The SEED config is mis-matched to the fabric on purpose:
    ``wire_dtype="raw"`` on a link slow enough that quantization wins
    the break-even economics, and ``prefetch_depth=1`` (no overlap at
    all).  The **defaults** leg runs the seed as shipped.  The
    **tuned** leg starts cold from the same seed and pays for its own
    tuning inside its timed window: a :class:`~ddl_tpu.tune.Calibrator`
    pass (measured ``probe_link_costs`` over the throttled fabric +
    the wire microbenchmark → int8 wire, depth floored to the shipped
    default), then a :class:`~ddl_tpu.tune.KnobController` stepped
    once per consumed batch, growing prefetch depth under hysteresis
    with the never-worse guard live.  The tuned leg runs with the
    flight recorder armed; the block counts its ``tune`` ring events.

    Honesty gates baked into the block (bench_smoke enforces):
    ``never_slower`` re-measured on a fresh confirmation pair (the
    wire-bench pattern, never an argmax identity); ZERO never-worse
    reverts in the winning leg; every decision carries ``cost_source``
    provenance with at least one ``measured`` decision; the int8 leg's
    loss curve passes ``loss_parity``; and the decisions were actually
    flight-recorded.

    Geometry knobs: ``DDL_BENCH_AUTOTUNE_ROWS``/``COLS`` (exchange
    window AND batch shape, default 256x512),
    ``DDL_BENCH_AUTOTUNE_ROUNDS`` (exchange rounds, default 6),
    ``DDL_BENCH_AUTOTUNE_BATCHES`` (prefetch batches, default 24),
    ``DDL_BENCH_AUTOTUNE_REPS`` (default 2),
    ``DDL_BENCH_AUTOTUNE_LINK_MBPS`` (simulated link, default 16),
    ``DDL_BENCH_AUTOTUNE_COMPUTE_MS`` (per-batch compute, default 6).
    """
    import threading

    from ddl_tpu.config import LoaderConfig
    from ddl_tpu.ingest import DeviceIngestor, PrefetchIterator
    from ddl_tpu.obs import recorder as obs_recorder
    from ddl_tpu.observability import Metrics
    from ddl_tpu.parallel.optimizer import loss_parity
    from ddl_tpu.shuffle import Rendezvous, ThreadExchangeShuffler
    from ddl_tpu.tune import (
        Calibrator,
        ControllerPolicy,
        KnobController,
        prefetch_knob,
    )
    from ddl_tpu.types import Topology

    rows = int(os.environ.get("DDL_BENCH_AUTOTUNE_ROWS", "256"))
    cols = int(os.environ.get("DDL_BENCH_AUTOTUNE_COLS", "512"))
    rounds = int(os.environ.get("DDL_BENCH_AUTOTUNE_ROUNDS", "6"))
    batches = int(os.environ.get("DDL_BENCH_AUTOTUNE_BATCHES", "24"))
    reps = int(os.environ.get("DDL_BENCH_AUTOTUNE_REPS", "2"))
    link = (
        float(os.environ.get("DDL_BENCH_AUTOTUNE_LINK_MBPS", "16"))
        * (1 << 20)
    )
    compute_s = (
        float(os.environ.get("DDL_BENCH_AUTOTUNE_COMPUTE_MS", "6")) / 1e3
    )
    num_exchange = rows
    # Token-like compressible float windows (the wire bench's shape).
    base = [
        (np.random.default_rng(100 + i).integers(0, 32, (rows, cols)))
        .astype(np.float32)
        for i in range(2)
    ]
    seed_cfg = LoaderConfig(wire_dtype="raw", prefetch_depth=1)
    total_samples = float(2 * rows * rounds + batches * rows)

    def probe_losses(streams) -> list:
        """Deterministic linear-probe SGD over the exchanged stream —
        the loss-parity gate's curve (one per leg)."""
        w = np.zeros(cols, np.float64)
        y = np.sin(np.arange(rows)).astype(np.float64)
        losses = []
        for win in streams:
            x = win.astype(np.float64)
            pred = x @ w
            losses.append(float(np.mean((pred - y) ** 2)))
            grad = 2.0 * x.T @ (pred - y) / rows
            w -= 1e-5 * grad
        return losses

    def run_exchange(wire_dtype, m):
        """Phase A: both instances exchange over the throttled link;
        returns instance 0's window stream."""
        rdv = _ThrottledRendezvous(Rendezvous(), link)
        streams = [[], []]
        metrics = [m, Metrics()]
        errors = []

        def worker(i):
            try:
                topo = Topology(
                    n_instances=2, instance_idx=i, n_producers=1
                )
                sh = ThreadExchangeShuffler(
                    topo, 1, num_exchange=num_exchange, rendezvous=rdv,
                    seed=7, wire_dtype=wire_dtype,
                    exchange_timeout_s=60.0,
                )
                sh.metrics = metrics[i]
                ary = base[i].copy()
                for _ in range(rounds):
                    sh.global_shuffle(ary)
                    streams[i].append(ary.copy())
            except Exception as e:  # noqa: BLE001 - surfaced below
                errors.append(e)

        ts = [
            threading.Thread(target=worker, args=(i,)) for i in range(2)
        ]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=120.0)
        if any(t.is_alive() for t in ts):
            raise RuntimeError("autotune leg wedged (exchange stall)")
        if errors:
            raise errors[0]
        return streams[0]

    def run_prefetch(depth, m, with_controller):
        """Phase B: consume `batches` in-flight transfers behind a
        PrefetchIterator at `depth`; the tuned leg steps the controller
        once per batch (the telemetry loop at batch cadence)."""
        host = (
            np.zeros((rows, cols), np.float32) for _ in range(batches)
        )
        it = PrefetchIterator(
            host, DeviceIngestor(), depth=depth,
            put=lambda b: _AsyncLinkTransfer(b, link),
        )
        ctrl = None
        if with_controller:
            # The shipped-second constants rescaled to the bench's
            # batch cadence: each step's window is one full batch
            # cycle, so a single above-band reading is already a
            # sustained observation (sustain_s=0); the cooldown still
            # spaces actions and runs the never-worse window.
            ctrl = KnobController(
                [prefetch_knob(it)],
                policy=ControllerPolicy(
                    up_stall_fraction=0.25, down_stall_fraction=0.0,
                    sustain_s=0.0, cooldown_s=0.12,
                ),
                metrics=m,
            )
        for h in it:
            with m.timed("consumer.wait"):
                h.wait()
            time.sleep(compute_s)
            m.incr("consumer.samples", rows)
            if ctrl is not None:
                ctrl.step()
        return it._depth, ctrl

    def run_defaults():
        """The seed as shipped: raw wire, depth 1, nobody watching."""
        m = Metrics()
        t0 = time.perf_counter()
        stream = run_exchange(seed_cfg.wire_dtype, m)
        run_prefetch(seed_cfg.prefetch_depth, m, False)
        dt = time.perf_counter() - t0
        return total_samples / dt, stream

    def run_tuned():
        """Cold start from the same seed; calibration + control INSIDE
        the timed window (self-tuning must pay for itself)."""
        m = Metrics()
        rec = obs_recorder.FlightRecorder(capacity=8192)
        with obs_recorder.armed(rec):
            t0 = time.perf_counter()
            cal = Calibrator(
                deadline_s=2.0,
                hosts=[0, 1],
                transfer=lambda a, b, p: time.sleep(p.nbytes / link),
                sample=base[0],
                metrics=m,
            )
            tuned_cfg = cal.calibrate(seed_cfg)
            cfg = tuned_cfg.apply(seed_cfg)
            stream = run_exchange(cfg.wire_dtype, m)
            final_depth, ctrl = run_prefetch(cfg.prefetch_depth, m, True)
            dt = time.perf_counter() - t0
        flight = sum(1 for e in rec.events() if e[1] == "tune")
        return {
            "rate": total_samples / dt,
            "stream": stream,
            "calibration": tuned_cfg,
            "controller": ctrl,
            "wire_dtype": cfg.wire_dtype,
            "boot_depth": cfg.prefetch_depth,
            "final_depth": final_depth,
            "reverts": int(m.counter("tune.reverts")),
            "cost_sources": {
                src: int(m.counter(f"tune.cost_source.{src}"))
                for src in ("measured", "declared", "default")
            },
            "flight_recorded": flight,
        }

    best_defaults = 0.0
    best_tuned: dict = {}
    defaults_stream: list = []
    for _ in range(reps):  # interleaved: box noise hits both legs alike
        d_rate, d_stream = run_defaults()
        if d_rate > best_defaults:
            best_defaults = d_rate
        defaults_stream = d_stream
        t = run_tuned()
        if not best_tuned or t["rate"] > best_tuned["rate"]:
            best_tuned = t

    ctrl = best_tuned["controller"]
    decisions = [
        d.as_dict() for d in best_tuned["calibration"].decisions
    ] + ([d.as_dict() for d in ctrl.decisions] if ctrl else [])
    block: dict = {
        "link_bytes_per_sec": link,
        "rows": rows, "cols": cols, "rounds": rounds,
        "batches": batches, "reps": reps,
        "compute_ms": round(compute_s * 1e3, 2),
        "seed": {
            "wire_dtype": seed_cfg.wire_dtype,
            "prefetch_depth": seed_cfg.prefetch_depth,
        },
        "legs": {
            "defaults": {"samples_per_sec": round(best_defaults, 1)},
            "tuned": {"samples_per_sec": round(best_tuned["rate"], 1)},
        },
        "tuned_knobs": {
            "wire_dtype": best_tuned["wire_dtype"],
            "boot_prefetch_depth": best_tuned["boot_depth"],
            "final_prefetch_depth": best_tuned["final_depth"],
        },
        "calibration": best_tuned["calibration"].as_report(),
        "controller": ctrl.report() if ctrl else {},
        "decisions": decisions,
        "cost_sources": best_tuned["cost_sources"],
        "deadline_hit": best_tuned["calibration"].deadline_hit,
        "reverts": best_tuned["reverts"],
        "flight_recorded": best_tuned["flight_recorded"],
        "vs_defaults": round(
            best_tuned["rate"] / max(best_defaults, 1e-9), 3
        ),
    }
    # Lossy-wire honesty: the tuned leg's exchanged stream must pass
    # the loss-parity gate against the raw defaults stream.
    parity = loss_parity(
        probe_losses(defaults_stream),
        probe_losses(best_tuned["stream"]),
    )
    block["parity"] = bool(parity["parity"])
    block["parity_drift"] = parity["max_rel_drift"]
    # Never-slower is a MEASUREMENT, not an argmax identity: a fresh
    # confirmation pair, exactly the wire-bench discipline (bench_smoke
    # asserts THIS flag, retried once against box noise).
    c_rate, _ = run_defaults()
    confirm_tuned = run_tuned()
    block["confirm"] = {
        "defaults": round(c_rate, 1),
        "tuned": round(confirm_tuned["rate"], 1),
    }
    block["never_slower"] = bool(confirm_tuned["rate"] >= c_rate)
    block["samples_per_sec"] = round(best_tuned["rate"], 1)
    return block


def _run_cache_ab() -> dict:
    """Cold-vs-warm epoch A/B for the shard cache over a throttled backend.

    Drives a ``FileShardProducer`` refill loop directly (no loader/ring —
    this measures the *storage* path, which is what the cache changes)
    over a ``ThrottledBackend`` simulating a slow source, for two epochs:
    epoch 1 pays fetch+decode per shard (and fills the cache), epoch 2
    serves decoded shards from the warm tier.  The same two-epoch
    sequence also runs with the cache disabled, and every epoch's served
    bytes are CRC'd: ``byte_identical`` asserts the cached stream equals
    the uncached one — the cache must never change data, only speed.

    Geometry knobs: ``DDL_BENCH_CACHE_SHARDS`` (default 8),
    ``DDL_BENCH_CACHE_ROWS`` (rows/shard, default 256),
    ``DDL_BENCH_CACHE_LATENCY_S`` (per-open simulated round-trip,
    default 0.02).
    """
    import shutil
    import tempfile
    import zlib

    from ddl_tpu.cache import CacheStore, ThrottledBackend
    from ddl_tpu.observability import Metrics
    from ddl_tpu.readers import FileShardProducer

    n_shards = int(os.environ.get("DDL_BENCH_CACHE_SHARDS", "8"))
    rows = int(os.environ.get("DDL_BENCH_CACHE_ROWS", "256"))
    latency = float(os.environ.get("DDL_BENCH_CACHE_LATENCY_S", "0.02"))
    n_cols = 64
    tmp = tempfile.mkdtemp(prefix="ddl_cache_bench_")
    try:
        rng = np.random.default_rng(0)
        for i in range(n_shards):
            np.save(
                os.path.join(tmp, f"shard_{i:03d}.npy"),
                rng.standard_normal((rows, n_cols)).astype(np.float32),
            )
        pattern = os.path.join(tmp, "shard_*.npy")

        def run_epochs(cache):
            # warm=False: the A/B measures the refill path itself; a
            # background warmer racing epoch 1 would blur cold cost.
            # cache=False (not None) in the control arm: None defers to
            # the DDL_TPU_CACHE env gate, which would silently cache the
            # "uncached" baseline on a gate-exported host.
            prod = FileShardProducer(
                pattern, seed=0, cache=cache if cache is not None else False,
                backend=ThrottledBackend(latency_s=latency), warm=False,
            )
            ret = prod.on_init(producer_idx=1)
            ary = np.zeros(ret.shape, ret.dtype)
            out = []
            for _ in range(2):  # epochs
                crc = 0
                t0 = time.perf_counter()
                for _ in range(n_shards):
                    prod.execute_function(my_ary=ary)
                    crc = zlib.crc32(ary.tobytes(), crc)
                dt = time.perf_counter() - t0
                out.append((rows * n_shards / dt, crc))
            return out

        m = Metrics()
        store = CacheStore(ram_budget_bytes=256 << 20, metrics=m)
        cached = run_epochs(store)
        uncached = run_epochs(None)
        (cold_rate, cold_crc), (warm_rate, warm_crc) = cached
        block = {
            "shards": n_shards,
            "rows_per_shard": rows,
            "backend_latency_s": latency,
            "cold_samples_per_sec": round(cold_rate, 1),
            "warm_samples_per_sec": round(warm_rate, 1),
            "warm_vs_cold": round(warm_rate / cold_rate, 3),
            "byte_identical": (
                cold_crc == uncached[0][1] and warm_crc == uncached[1][1]
            ),
        }
        stats = m.prefixed("cache.")
        for key in ("hits", "misses", "evictions", "quarantined"):
            block[key] = stats.get(key, 0.0)
        block["resident_bytes_max"] = stats.get("resident_bytes.max", 0.0)
        return block
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _run_placement_ab() -> dict:
    """Topology-aware vs naive producer→consumer placement (ISSUE 10,
    Cloud Collectives arXiv:2105.14088 rank reordering).

    Geometry: 8 mock hosts — 4 loader hosts and 4 trainer hosts — in 4
    two-host islands deliberately PAIRED ACROSS ROLES (each island holds
    one loader + one trainer host), so the naive rank-order round-robin
    pairs every producer with a cross-island consumer while the planner
    can ride 4 intra-island links.  Both assignments are MEASURED over
    the simulated fabric (real memcpys, wire time priced by the declared
    cost matrix — the cache bench's ThrottledBackend pattern): the ratio
    is wall-clock, not model output.  The never-slower invariant holds
    by construction (the naive order is always a candidate plan) and
    bench_smoke gates the measured ratio.

    The chaos half of the block: a seeded ``HOST_LOSS`` at
    ``cluster.heartbeat`` drives one supervisor sweep through a real
    epoch-fenced view change, so the ``view_changes``/``host_losses``
    counters in the JSON chart the membership machinery itself.

    Knobs: ``DDL_BENCH_PLACEMENT_PAYLOAD_MIB`` (default 4),
    ``DDL_BENCH_PLACEMENT_REPS`` (default 3),
    ``DDL_BENCH_PLACEMENT_INTRA_GBPS`` / ``_CROSS_GBPS`` (simulated
    link speeds, default 8 / 1).
    """
    from ddl_tpu import faults
    from ddl_tpu.cluster import (
        ClusterSupervisor,
        ClusterView,
        HostInfo,
        LinkCosts,
        SimulatedFabric,
        placement_report,
    )
    from ddl_tpu.faults import FaultKind, FaultPlan, FaultSpec
    from ddl_tpu.observability import Metrics

    payload = int(
        float(os.environ.get("DDL_BENCH_PLACEMENT_PAYLOAD_MIB", "4"))
        * (1 << 20)
    )
    reps = int(os.environ.get("DDL_BENCH_PLACEMENT_REPS", "3"))
    intra = float(os.environ.get("DDL_BENCH_PLACEMENT_INTRA_GBPS", "8")) * 1e9
    cross = float(os.environ.get("DDL_BENCH_PLACEMENT_CROSS_GBPS", "1")) * 1e9

    loaders, trainers = (0, 1, 2, 3), (4, 5, 6, 7)
    hosts = [
        HostInfo(h, loader_ranks=(h + 1,)) for h in loaders
    ] + [
        HostInfo(h, trainer_ranks=(h - len(loaders),)) for h in trainers
    ]
    view = ClusterView.bootstrap(hosts, n_shards=32)
    # Islands pair loader host h with trainer host 5-h style partners:
    # (0,5) (1,4) (2,7) (3,6) — every naive round-robin pair (0→4, 1→5,
    # 2→6, 3→7) crosses islands; the planner's pairs stay inside them.
    costs = LinkCosts.islands(
        [[0, 5], [1, 4], [2, 7], [3, 6]], intra, cross
    )
    block = placement_report(
        view,
        costs,
        transfer=SimulatedFabric(costs),
        payload_bytes=payload,
        reps=reps,
    )

    # Membership chaos mini-run: one injected host loss through a REAL
    # supervisor sweep — the counters prove the view-change machinery,
    # not a hand-incremented dict.
    m = Metrics()
    sup = ClusterSupervisor(view, lease_s=60.0, metrics=m)
    plan = FaultPlan(
        [FaultSpec("cluster.heartbeat", FaultKind.HOST_LOSS,
                   producer_idx=loaders[-1])]
    )
    with faults.armed(plan):
        sup.sweep()
    assert plan.fired, "HOST_LOSS spec never fired"
    block["view_changes"] = m.counter("cluster.view_changes")
    block["host_losses"] = m.counter("cluster.host_losses")
    block["post_loss_epoch"] = sup.view.epoch
    return block


def _tenancy_pattern_producer(rows: int, vals: int, fill_latency_s: float):
    """Deterministic per-producer window content for the tenancy leg:
    window k from producer p is the constant plane ``p * 1000 + k`` —
    byte-correctness is checkable on any served subsequence regardless
    of pool churn.  ``fill_latency_s`` simulates decode cost (the
    ThrottledBackend pattern) so the producer tier is the measured
    bottleneck and pool size is what moves aggregate throughput.
    THREAD-mode only (deep-copied, never pickled), hence the local
    class."""
    from ddl_tpu import DataProducerOnInitReturn, ProducerFunctionSkeleton

    class PatternProducer(ProducerFunctionSkeleton):
        inplace_fill = True

        def on_init(self, producer_idx=1, **kw):
            self.idx = producer_idx
            self.k = 0
            return DataProducerOnInitReturn(
                nData=rows, nValues=vals, shape=(rows, vals),
                splits=(vals,),
            )

        def post_init(self, my_ary, **kw):
            my_ary[:] = 0.0

        def execute_function(self, my_ary, **kw):
            if fill_latency_s:
                time.sleep(fill_latency_s)
            my_ary[:] = float(self.idx * 1000 + self.k)
            self.k += 1

    return PatternProducer()


def _tenancy_shard_producer(rows: int, vals: int, ranges_by_producer: dict):
    """The chaos leg's producer: serves its host's shard ranges in a
    cycle and re-partitions on ``adopt_shards`` (the test_cluster
    pattern) — so full-shard coverage survives a mid-stream host loss."""
    from ddl_tpu import DataProducerOnInitReturn, ProducerFunctionSkeleton

    def shard_pattern(shard: int):
        return (
            shard * 1000.0
            + np.arange(rows * vals, dtype=np.float32) % 97
        ).reshape(rows, vals)

    class ShardProducer(ProducerFunctionSkeleton):
        inplace_fill = True
        pattern = staticmethod(shard_pattern)

        def _shards(self):
            return [s for a, b in self.ranges for s in range(a, b)]

        def on_init(self, producer_idx=1, **kw):
            self.it = 0
            self.ranges = tuple(ranges_by_producer[producer_idx])
            return DataProducerOnInitReturn(
                nData=rows, nValues=vals, shape=(rows, vals),
                splits=(vals,),
            )

        def post_init(self, my_ary, **kw):
            my_ary[:] = 0.0

        def execute_function(self, my_ary, **kw):
            shards = self._shards()
            my_ary[:] = shard_pattern(shards[self.it % len(shards)])
            self.it += 1

        def adopt_shards(self, ranges, **kw):
            self.ranges = tuple(ranges)

    return ShardProducer()


class _TenantFleet:
    """Autoscaler adapter fanning one resize across every tenant's
    elastic ladder: N independent loader jobs share ONE logical host
    set, so a scale decision must land on each tenant's supervisor (the
    epoch fences keep them mutually consistent — every supervisor
    computes the identical successor view from the same HostInfo)."""

    def __init__(self, elastics):
        self.elastics = list(elastics)

    @property
    def supervisor(self):
        return self.elastics[0].supervisor

    def rejoin_host(self, host):
        view = None
        for e in self.elastics:
            view = e.rejoin_host(host)
        return view

    def drain_host(self, host_id):
        info = None
        for e in self.elastics:
            info = e.drain_host(host_id)
        return info


def _tenancy_leg(
    dynamic: bool,
    demand: "list[int]",
    rows: int,
    vals: int,
    fill_s: float,
    n_hosts_floor: int = 2,
    n_hosts_max: int = 4,
) -> dict:
    """One measured tenancy leg: K tenant loaders (own THREAD envs, one
    ring per mock host, hosts ``floor..max-1`` standing by) drain their
    heavy-tailed demand through one shared fair-share scheduler.
    ``dynamic`` additionally runs the autoscaler on the REAL windowed
    stall signal; the static baseline keeps the floor pool for the whole
    run.  Returns aggregate + per-tenant measurements."""
    import threading

    from ddl_tpu import DistributedDataLoader, Marker, distributed_dataloader
    from ddl_tpu.cluster import ClusterSupervisor, ClusterView, ElasticCluster, HostInfo
    from ddl_tpu.observability import Metrics
    from ddl_tpu.serve import (
        AdmissionController,
        Autoscaler,
        AutoscalerPolicy,
        FairShareScheduler,
        TenantSpec,
    )

    K = len(demand)
    window_bytes = rows * vals * 4
    m = Metrics()
    ctl = AdmissionController(
        scheduler=FairShareScheduler(quantum_bytes=window_bytes, metrics=m),
        metrics=m,
    )
    tenants = [ctl.register(TenantSpec(f"t{i}")) for i in range(K)]

    def bootstrap_view():
        return ClusterView.bootstrap(
            [
                HostInfo(h, loader_ranks=(h + 1,))
                for h in range(n_hosts_floor)
            ],
            n_shards=n_hosts_max * 2,
        )

    pairs = []
    for _ in range(K):
        sup = ClusterSupervisor(bootstrap_view(), lease_s=600.0, metrics=m)
        pairs.append(ElasticCluster(sup, metrics=m))

    per_tenant: dict = {}
    errors: "list[str]" = []
    lock = threading.Lock()

    def run_tenant(i: int) -> None:
        tenant, elastic, n_epochs = tenants[i], pairs[i], demand[i]

        @distributed_dataloader(n_producers=n_hosts_max, mode="thread")
        def tmain(env):
            loader = DistributedDataLoader(
                _tenancy_pattern_producer(rows, vals, fill_s),
                batch_size=rows, connection=env.connection,
                n_epochs=n_epochs, output="numpy", timeout_s=120.0,
                metrics=m, cluster=elastic,
            )
            tenant.bind(loader)
            lats, byte_ok = [], True
            for _ in range(n_epochs):
                t0 = time.perf_counter()
                for (win,) in loader:
                    dt = time.perf_counter() - t0
                    lats.append(dt)
                    # First-class percentiles (ddl_tpu.obs): the same
                    # latencies land in the shared registry's bounded
                    # histogram; the published p50/p99 below read THAT
                    # back, with the raw-list percentile kept as the
                    # independent cross-check.
                    m.observe(
                        f"ingest.{tenant.name}.window_latency", dt
                    )
                    v = win.ravel()[0]
                    if not (win == v).all() or v < 1000.0:
                        byte_ok = False
                    loader.mark(Marker.END_OF_BATCH)
                loader.mark(Marker.END_OF_EPOCH)
            return lats, byte_ok

        try:
            lats, byte_ok = tmain()
            with lock:
                # Primary percentiles come from the Metrics histogram
                # (the values north_star_report surfaces); the raw-list
                # np.percentile rides along as the independent check —
                # bench_smoke asserts they agree within one log bucket.
                per_tenant[tenant.name] = {
                    "windows": n_epochs,
                    "bytes": n_epochs * window_bytes,
                    "p50_window_latency_s": round(
                        m.quantile(
                            f"ingest.{tenant.name}.window_latency", 0.5
                        ), 4
                    ),
                    "p99_window_latency_s": round(
                        m.quantile(
                            f"ingest.{tenant.name}.window_latency", 0.99
                        ), 4
                    ),
                    "p99_window_latency_np_s": round(
                        float(np.percentile(lats, 99)), 4
                    ),
                    "byte_identical": bool(byte_ok),
                }
        except Exception as e:  # noqa: BLE001 - surfaced in the block
            with lock:
                errors.append(f"{tenant.name}: {type(e).__name__}: {e}")

    scaler = None
    if dynamic:
        standby = [
            HostInfo(h, loader_ranks=(h + 1,))
            for h in range(n_hosts_floor, n_hosts_max)
        ]
        scaler = Autoscaler(
            _TenantFleet(pairs),
            standby=standby,
            policy=AutoscalerPolicy(
                up_stall_fraction=0.3, down_stall_fraction=0.02,
                sustain_s=0.1, cooldown_s=0.2,
                min_hosts=n_hosts_floor, max_hosts=n_hosts_max,
            ),
            metrics=m, n_consumers=K, poll_interval_s=0.05,
        ).start()

    threads = [
        threading.Thread(target=run_tenant, args=(i,)) for i in range(K)
    ]
    t_start = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join(300.0)
    wall = time.perf_counter() - t_start
    if scaler is not None:
        scaler.stop()
    hung = [t.name for t in threads if t.is_alive()]
    if hung:
        # A silent join expiry would fabricate samples_per_sec from
        # windows never served AND leak a live pipeline into the next
        # interleaved rep — fail the leg loudly instead.
        raise RuntimeError(f"tenancy leg hung tenants: {hung}")
    if errors:
        raise RuntimeError(f"tenancy leg failed: {errors}")
    total_samples = sum(demand) * rows
    reaction = m.timer("serve.scale_up_reaction")
    # The scheduler/admission report refreshes the per-tenant stall
    # gauges north_star_report surfaces.
    serve_report = ctl.report()
    for name, block in serve_report["tenants"].items():
        if name in per_tenant:
            per_tenant[name]["admission_wait_s"] = round(
                block["admission_wait_s"], 4
            )
            per_tenant[name]["admission_wait_p99_s"] = round(
                block["admission_wait_p99_s"], 6
            )
            per_tenant[name]["stall_fraction"] = round(
                block["stall_fraction"], 4
            )
    # The report-level percentile (the north_star_report key) next to
    # the scheduler's own — one histogram, two readers, must agree.
    from ddl_tpu.ingest import north_star_report as _nsr

    ns = _nsr(m)
    return {
        "samples_per_sec": total_samples / wall,
        "admission_wait_p99_s": round(ns["admission_wait_p99"], 6),
        "wall_s": round(wall, 3),
        "windows": int(sum(demand)),
        "per_tenant": per_tenant,
        "scale_ups": m.counter("serve.scale_ups"),
        "scale_downs": m.counter("serve.scale_downs"),
        "scale_up_reaction_s": round(
            reaction.total_s / reaction.count, 4
        ) if reaction.count else None,
        "pool_hosts_final": m.gauge("serve.pool_hosts"),
        "admissions": serve_report["admissions"],
        "admission_wait_s": round(serve_report["admission_wait_s"], 4),
        "rounds": serve_report["rounds"],
    }


def _tenancy_chaos_leg(K: int, rows: int, vals: int) -> dict:
    """The chaos half of the tenancy block: a TENANT_BURST at
    ``serve.admit`` and a HOST_LOSS at ``cluster.heartbeat`` land
    mid-stream on K concurrent tenants (the burst on tenant 0, the loss
    on mock host 1 of every tenant's fleet view).  Every tenant's
    stream must stay byte-correct with FULL shard coverage — the
    survivors adopt the dead host's ranges — and zero watchdog
    failures."""
    import threading

    from ddl_tpu import DistributedDataLoader, Marker, distributed_dataloader
    from ddl_tpu import faults
    from ddl_tpu.cluster import ClusterSupervisor, ClusterView, ElasticCluster, HostInfo
    from ddl_tpu.faults import FaultKind, FaultPlan, FaultSpec
    from ddl_tpu.observability import Metrics
    from ddl_tpu.serve import AdmissionController, FairShareScheduler, TenantSpec
    from ddl_tpu.watchdog import Watchdog

    n_shards, n_epochs = 4, 12
    m = Metrics()
    ctl = AdmissionController(
        scheduler=FairShareScheduler(
            quantum_bytes=rows * vals * 4, metrics=m
        ),
        metrics=m,
    )
    tenants = [ctl.register(TenantSpec(f"c{i}")) for i in range(K)]
    errors: "list[str]" = []
    coverage: dict = {}
    lock = threading.Lock()

    def run_tenant(i: int) -> None:
        tenant = tenants[i]

        @distributed_dataloader(n_producers=2, mode="thread")
        def tmain(env):
            view = ClusterView.bootstrap(
                [HostInfo(0, loader_ranks=(1,), trainer_ranks=(0,)),
                 HostInfo(1, loader_ranks=(2,))],
                n_shards=n_shards,
            )
            sup = ClusterSupervisor(view, lease_s=60.0, metrics=m)
            elastic = ElasticCluster(sup, workers=env.workers, metrics=m)
            producer = _tenancy_shard_producer(
                rows, vals, {1: ((0, 2),), 2: ((2, 4),)}
            )
            loader = DistributedDataLoader(
                producer, batch_size=rows, connection=env.connection,
                n_epochs=n_epochs, output="numpy", timeout_s=60.0,
                metrics=m, cluster=elastic,
            )
            tenant.bind(loader)
            wd = Watchdog(
                env.workers, poll_interval_s=0.05, stall_budget_s=60.0,
                respawn=True, metrics=m, cluster=sup,
            ).start()
            ref = producer.pattern
            seen, ok = set(), True
            try:
                for _ in range(n_epochs):
                    for (win,) in loader:
                        shard = int(win[0, 0] // 1000)
                        seen.add(shard)
                        if not np.array_equal(win, ref(shard)):
                            ok = False
                        loader.mark(Marker.END_OF_BATCH)
                    loader.mark(Marker.END_OF_EPOCH)
                    # Pace the stream so the watchdog-driven sweeps (and
                    # the armed HOST_LOSS) land mid-run, not after it.
                    time.sleep(0.05)
            finally:
                wd.stop()
            return seen, ok

        try:
            seen, ok = tmain()
            with lock:
                coverage[tenant.name] = {
                    "shards_seen": sorted(seen),
                    "byte_correct": bool(
                        ok and sorted(seen) == list(range(n_shards))
                    ),
                }
        except Exception as e:  # noqa: BLE001 - surfaced in the block
            with lock:
                errors.append(f"{tenant.name}: {type(e).__name__}: {e}")

    plan = FaultPlan([
        # The burst lands on tenant 0's 3rd admission...
        FaultSpec("serve.admit", FaultKind.TENANT_BURST,
                  at=3, producer_idx=0, param=float(8 << 20)),
        # ...while EVERY tenant's supervisor declares mock host 1 dead
        # at its next sweep (count covers all K supervisors' sweeps —
        # repeat declarations of an already-departed host are no-ops).
        FaultSpec("cluster.heartbeat", FaultKind.HOST_LOSS,
                  producer_idx=1, count=10_000),
    ])
    threads = [
        threading.Thread(target=run_tenant, args=(i,)) for i in range(K)
    ]
    with faults.armed(plan):
        for t in threads:
            t.start()
        for t in threads:
            t.join(300.0)
    hung = [t.name for t in threads if t.is_alive()]
    if hung:
        raise RuntimeError(f"tenancy chaos leg hung tenants: {hung}")
    if errors:
        raise RuntimeError(f"tenancy chaos leg failed: {errors}")
    fired = {kind for _site, kind, _idx, _n in plan.fired}
    return {
        "tenants": coverage,
        "byte_correct": all(
            c["byte_correct"] for c in coverage.values()
        ),
        "tenant_bursts": m.counter("serve.tenant_bursts"),
        "host_losses": m.counter("cluster.host_losses"),
        "view_changes": m.counter("cluster.view_changes"),
        # The elastic-side SEND counter: producer-side adoption applies
        # land on the worker threads' default registry, not this leg's.
        "shard_adoptions": m.counter("cluster.shard_adoptions"),
        "watchdog_failures": m.counter("watchdog.failures"),
        "fired_kinds": sorted(fired),
    }


def _run_tenancy_ab() -> dict:
    """The multi-tenant ingest-service A/B (ISSUE 11, ROADMAP item 1).

    K concurrent synthetic tenants on a heavy-tailed demand schedule
    (tenant i demands ``base * K / (i + 1)`` windows — Zipf-1) drain
    throttled producers through ONE shared fair-share scheduler, twice:

    - **static** — the pool is pinned at the floor (2 of 4 mock hosts)
      for the whole run: the provision-for-peak baseline.
    - **dynamic** — the autoscaler watches the real windowed stall
      signal and `rejoin_host`s the standby hosts on sustained demand.

    Both legs are MEASURED (wall-clock aggregate samples/s over real
    THREAD pipelines; the producer throttle makes pool size the
    bottleneck by construction), interleaved best-of-``reps``; the
    winner is the headline under the same never-slower invariant every
    other competition rides, and bench_smoke gates ``vs_static >= 1``.
    Per-tenant p50/p99 window latency, byte-identity flags, admission
    waits, and the scale-up reaction time (sustained-signal-to-rejoin,
    the ``serve.scale_up_reaction`` timer) ride in the block, plus the
    chaos leg (:func:`_tenancy_chaos_leg`).

    Knobs: ``DDL_BENCH_TENANCY_TENANTS`` (K, default 3),
    ``DDL_BENCH_TENANCY_BASE`` (demand base, default 12 — long enough
    that the post-scale-up span dominates the measurement),
    ``DDL_BENCH_TENANCY_FILL_MS`` (producer throttle, default 25),
    ``DDL_BENCH_TENANCY_ROWS`` (window rows, default 256),
    ``DDL_BENCH_TENANCY_REPS`` (default 2).
    """
    K = max(3, int(os.environ.get("DDL_BENCH_TENANCY_TENANTS", "3")))
    base = int(os.environ.get("DDL_BENCH_TENANCY_BASE", "12"))
    fill_s = float(os.environ.get("DDL_BENCH_TENANCY_FILL_MS", "25")) / 1e3
    rows = int(os.environ.get("DDL_BENCH_TENANCY_ROWS", "256"))
    reps = int(os.environ.get("DDL_BENCH_TENANCY_REPS", "2"))
    vals = 8
    # Heavy-tailed (Zipf-1) demand: tenant 0 wants K× tenant K-1's load.
    demand = [max(2, base * K // (i + 1)) for i in range(K)]

    best: dict = {}
    for _ in range(max(1, reps)):
        # Interleaved static/dynamic pairs, best-of per side.
        st = _tenancy_leg(False, demand, rows, vals, fill_s)
        dy = _tenancy_leg(True, demand, rows, vals, fill_s)
        if st["samples_per_sec"] > best.get("static", {}).get(
            "samples_per_sec", 0.0
        ):
            best["static"] = st
        if dy["samples_per_sec"] > best.get("dynamic", {}).get(
            "samples_per_sec", 0.0
        ):
            best["dynamic"] = dy
    st, dy = best["static"], best["dynamic"]
    vs_static = (
        dy["samples_per_sec"] / st["samples_per_sec"]
        if st["samples_per_sec"] > 0
        else 1.0
    )
    winner = "dynamic" if dy["samples_per_sec"] >= st["samples_per_sec"] else "static"
    chaos = _tenancy_chaos_leg(K, rows=32, vals=4)
    return {
        "n_tenants": K,
        "demand_windows": demand,
        "fill_latency_ms": fill_s * 1e3,
        "window_bytes": rows * vals * 4,
        "samples_per_sec": round(
            max(dy["samples_per_sec"], st["samples_per_sec"]), 1
        ),
        "dynamic_samples_per_sec": round(dy["samples_per_sec"], 1),
        "static_samples_per_sec": round(st["samples_per_sec"], 1),
        "vs_static": round(vs_static, 3),
        "winner": winner,
        "scale_ups": dy["scale_ups"],
        "scale_downs": dy["scale_downs"],
        "scale_up_reaction_s": dy["scale_up_reaction_s"],
        "pool_hosts_final": dy["pool_hosts_final"],
        "static_wall_s": st["wall_s"],
        "dynamic_wall_s": dy["wall_s"],
        "per_tenant": dy["per_tenant"],
        "byte_identical": all(
            t["byte_identical"] for t in dy["per_tenant"].values()
        ) and all(
            t["byte_identical"] for t in st["per_tenant"].values()
        ),
        "admission_wait_s": dy["admission_wait_s"],
        "rounds": dy["rounds"],
        "chaos": chaos,
    }


def _ensure_virtual_mesh(n: int) -> None:
    """Ask XLA for an n-device CPU virtual mesh (:func:`bring_up` calls
    this before the backend's first touch).  No-op when the flag is
    already set."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}"
        ).strip()


def _run_ici_ab(platform: str) -> dict:
    """The ICI ingest A/B (ROADMAP item 1): one window, H2D onto the
    anchor device, then distributed to a dp-sharded target two ways —
    ``ici`` (Pallas fan-out ring + redistribution legs,
    ddl_tpu/parallel/ici.py) vs ``xla`` (the pre-existing
    ``device_put`` scatter) — measured INTERLEAVED, best-of both sides.

    Two ratios come out: ``vs_xla`` (end-to-end, the ici-vs-xla
    competition under the never-slower headline invariant) and
    ``bandwidth_utilization`` — the fan-out's measured per-hop wire rate
    over the platform's per-LINK ICI spec (``_PEAK_ICI_LINK``), the
    BASELINE.md ≥0.90 target's denominator.  Off-TPU the kernel runs in
    interpret mode on the virtual mesh (byte-identity + contract-shape
    proof; the utilization denominator is null — there is no ICI).

    Geometry knobs: ``DDL_BENCH_ICI_MIB`` (window size, default 64 on
    TPU / 0.0625 interpreted), ``DDL_BENCH_ICI_REPS`` (default 5).
    """
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from ddl_tpu.observability import Metrics
    from ddl_tpu.parallel.ici import IciDistributor

    devices = jax.devices()
    n_dev = len(devices)
    if n_dev < 2:
        raise RuntimeError(f"ici A/B needs >= 2 devices, found {n_dev}")
    mesh = Mesh(np.array(devices), ("dp",))
    sharding = NamedSharding(mesh, P("dp"))

    interpret = platform != "tpu"
    # Interpreted: 64 KiB — the interpreter deadlocks on larger kernel
    # operands when the ring is as wide as the host has cores
    # (ops/ici_fanout.py, "Interpret mode").
    mib = float(
        os.environ.get("DDL_BENCH_ICI_MIB", "0.0625" if interpret else "64")
    )
    cols = N_VALUES
    rows = max(n_dev, int(mib * (1 << 20)) // (cols * 4) // n_dev * n_dev)
    win = np.random.default_rng(0).random((rows, cols)).astype(np.float32)
    reps = int(os.environ.get("DDL_BENCH_ICI_REPS", "5"))

    m = Metrics()
    dist = IciDistributor(sharding, metrics=m)
    plan = dist.plan(win.shape, win.dtype)  # PlanError -> errors block

    # Warmup both paths (compiles) + the byte-identity check.
    out_i = dist.put(win, jax.device_put)
    out_x = jax.device_put(win, sharding)
    jax.block_until_ready((out_i, out_x))
    byte_identical = bool(
        np.array_equal(np.asarray(out_i), np.asarray(out_x))
    )

    def timed(fn):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        return time.perf_counter() - t0

    # End-to-end (H2D + distribution), interleaved so neither side owns
    # the quiet minutes (the PR 6 vs_baseline discipline).
    ici_s, xla_s = [], []
    for _ in range(reps):
        ici_s.append(timed(lambda: dist.put(win, jax.device_put)))
        xla_s.append(timed(lambda: jax.device_put(win, sharding)))

    # Distribution-only (anchor-resident source): the ICI hop itself,
    # the wire-rate numerator — H2D excluded from the clock.
    anchor_block = jax.device_put(win, plan.anchor)
    jax.block_until_ready(anchor_block)
    dist_s = min(timed(lambda: dist.distribute(anchor_block))
                 for _ in range(reps))

    if dist.faulted:
        # A latched fallback mid-bench means the "ici" timings silently
        # measured the xla path — that is not a result.
        raise RuntimeError(
            "ici distributor latched the xla fallback during the A/B "
            f"(ici.fallbacks={m.counter('ici.fallbacks')})"
        )

    nbytes = win.nbytes
    ici_rate = nbytes / min(ici_s)
    xla_rate = nbytes / min(xla_s)
    winner = "ici" if ici_rate >= xla_rate else "xla"
    wire_rate = plan.wire_bytes / dist_s
    # Wire bytes per device.  The direct scatter's bytes all leave the
    # source, so against one link's spec this is a floor for the
    # busiest link, not its utilization.
    per_hop = wire_rate / n_dev
    link_spec = (
        _peak_ici_link(devices[0].device_kind) if platform == "tpu"
        else None
    )
    util = per_hop / link_spec if link_spec else 0.0
    block = {
        "n_devices": n_dev,
        "window_mib": round(nbytes / 2**20, 2),
        "interpret": interpret,
        "plan_mode": plan.mode,
        "plan_legs": [leg.kind for leg in plan.legs],
        "peak_factor": round(plan.peak_factor, 3),
        "peak_bytes": plan.peak_bytes,
        # The ici-vs-xla competition: the block's headline bytes/s is
        # the WINNER's (never a config this run measured slower).
        "bytes_per_s": round(max(ici_rate, xla_rate), 1),
        "winner": winner,
        "ici_bytes_per_s": round(ici_rate, 1),
        "xla_bytes_per_s": round(xla_rate, 1),
        "vs_xla": round(ici_rate / xla_rate, 3),
        "byte_identical": byte_identical,
        # The ICI hop itself: wire bytes the fan-out+legs moved per
        # window over the distribution-only span, per ring link.
        "wire_bytes": plan.wire_bytes,
        "wire_bytes_per_s": round(wire_rate, 1),
        "per_hop_bytes_per_s": round(per_hop, 1),
        "link_spec_bytes_per_s": link_spec,
        "bandwidth_utilization": round(util, 4),
        "fanout_s": round(m.timer("ici.fanout").total_s, 4),
        "redistribute_s": round(m.timer("ici.redistribute").total_s, 4),
        "fallbacks": m.counter("ici.fallbacks"),
    }
    return _gate_utilization(block, "ici per-hop")


# -- device-shuffle A/B --------------------------------------------------------


def _run_shuffle_ab(platform: str) -> dict:
    """The global-shuffle exchange A/B (ROADMAP item 2 / ISSUE 17): the
    same seeded epoch exchange run two ways — ``host``
    (``ThreadExchangeShuffler`` over the in-process rendezvous, the
    2n-mailbox-hop path) vs ``device`` (``DeviceExchangeShuffler``: one
    collective over the ring per round, ``ddl_tpu/ops/device_shuffle``)
    — measured INTERLEAVED, best-of both sides, byte-identity of the
    post-exchange pools asserted per rep.

    The headline is the WINNER's bytes/s (the never-headline-slower
    invariant every competition rides).  Off-TPU the ring kernel runs
    in interpret mode on the virtual mesh, where the Python-level
    emulation usually LOSES to the host memcpy path — the contract
    (identity, plan accounting, zero fallbacks) must stay green anyway,
    the ici-bench precedent.

    Per-leg wire-byte accounting comes from ``plan_exchange``: the
    device path's ICI bytes vs what the HOST path would put on the
    boards raw and wire-encoded (the PR-13 int8 wire pricing, composed
    via ``DDL_TPU_WIRE_DTYPE``/``DDL_BENCH_SHUFFLE_WIRE``).

    Geometry knobs: ``DDL_BENCH_SHUFFLE_INSTANCES`` (ring width,
    default min(4, devices)), ``DDL_BENCH_SHUFFLE_ROWS`` (pool rows per
    instance, default 512 interpreted / 8192 on TPU),
    ``DDL_BENCH_SHUFFLE_ROUNDS`` (default 4), ``DDL_BENCH_SHUFFLE_REPS``
    (default 3), ``DDL_BENCH_SHUFFLE_IMPL`` (ring | xla).
    """
    import threading

    import jax

    from ddl_tpu.observability import Metrics
    from ddl_tpu.ops.device_shuffle import exchange_wire_bytes, plan_exchange
    from ddl_tpu.shuffle import (
        DeviceExchangeFabric,
        DeviceExchangeShuffler,
        Rendezvous,
        ThreadExchangeShuffler,
    )
    from ddl_tpu.types import Topology

    devices = jax.devices()
    n_dev = len(devices)
    interpret = platform != "tpu"
    n = int(os.environ.get("DDL_BENCH_SHUFFLE_INSTANCES", min(4, n_dev)))
    if n < 2 or n_dev < n:
        raise RuntimeError(
            f"shuffle A/B needs 2 <= instances <= devices, "
            f"got {n} instances / {n_dev} devices"
        )
    rows = int(os.environ.get(
        "DDL_BENCH_SHUFFLE_ROWS", "512" if interpret else "8192"
    ))
    cols = N_VALUES
    rounds = int(os.environ.get("DDL_BENCH_SHUFFLE_ROUNDS", "4"))
    reps = int(os.environ.get("DDL_BENCH_SHUFFLE_REPS", "3"))
    impl = os.environ.get("DDL_BENCH_SHUFFLE_IMPL", "ring")
    wire = os.environ.get("DDL_BENCH_SHUFFLE_WIRE") or None
    num_exchange = rows  # the whole pool travels: the worst-case round
    half = num_exchange // 2
    seed = 17

    def pools():
        rng = np.random.default_rng(3)
        return [
            rng.random((rows, cols)).astype(np.float32) for _ in range(n)
        ]

    def run_rounds(make_shuffler, arys):
        """All n instances exchanging concurrently (the real shape: the
        k-th producer of every instance), clocked end to end."""
        shufs = [make_shuffler(i) for i in range(n)]
        errs = []

        def worker(i):
            try:
                for _ in range(rounds):
                    shufs[i].global_shuffle(arys[i])
            except Exception as e:  # noqa: BLE001 - joined + re-raised below
                errs.append(e)

        ts = [
            threading.Thread(target=worker, args=(i,)) for i in range(n)
        ]
        t0 = time.perf_counter()
        for t in ts:
            t.start()
        for t in ts:
            t.join(600)
        dt = time.perf_counter() - t0
        if errs or any(t.is_alive() for t in ts):
            raise RuntimeError(f"exchange workers failed: {errs}")
        return dt, shufs

    def host_shuffler(rdv):
        return lambda i: ThreadExchangeShuffler(
            Topology(n_instances=n, instance_idx=i, n_producers=1),
            1, num_exchange, rendezvous=rdv, seed=seed,
        )

    fabric = DeviceExchangeFabric(impl=impl)
    metrics_by_i = {}

    def device_shuffler(rdv):
        def make(i):
            sh = DeviceExchangeShuffler(
                Topology(n_instances=n, instance_idx=i, n_producers=1),
                1, num_exchange, rendezvous=rdv,
                fabric=fabric, seed=seed,
            )
            sh.metrics = metrics_by_i.setdefault(i, Metrics())
            return sh

        return make

    # Warmup (ring-program compiles) + THE byte-identity assertion.
    host_pools, dev_pools = pools(), pools()
    run_rounds(host_shuffler(Rendezvous()), host_pools)
    run_rounds(device_shuffler(Rendezvous()), dev_pools)
    byte_identical = all(
        np.array_equal(host_pools[i], dev_pools[i]) for i in range(n)
    )
    if not byte_identical:
        raise RuntimeError(
            "device exchange diverged from the host path — identical "
            "seeds must produce identical post-exchange pools"
        )

    # Interleaved best-of timing: each rep clocks both sides once on
    # fresh pools, so neither side owns the quiet minutes (the PR 6
    # vs_baseline discipline).
    host_s, dev_s = [], []
    for _ in range(reps):
        host_s.append(run_rounds(host_shuffler(Rendezvous()), pools())[0])
        dev_s.append(run_rounds(device_shuffler(Rendezvous()), pools())[0])

    # A latched fallback mid-bench means the "device" timings silently
    # measured the host path — that is not a result (the ici A/B's
    # dist.faulted precedent).
    fallbacks = sum(
        m.counter("shuffle.device_fallbacks") for m in metrics_by_i.values()
    )
    if fallbacks:
        raise RuntimeError(
            "device shuffler latched the host fallback during the A/B "
            f"(shuffle.device_fallbacks={fallbacks})"
        )

    # Exchanged payload per timed run: both lanes, every instance,
    # every round.
    per_round = exchange_wire_bytes(n, half, cols, np.dtype(np.float32))
    nbytes = per_round * rounds
    host_rate = nbytes / min(host_s)
    dev_rate = nbytes / min(dev_s)
    winner = "device" if dev_rate >= host_rate else "host"
    plan = plan_exchange(
        n, num_exchange, cols, np.dtype(np.float32),
        wire_dtype=wire, n_devices=n_dev,
    )
    return {
        "n_instances": n,
        "n_devices": n_dev,
        "impl": impl,
        "interpret": interpret,
        "pool_rows": rows,
        "exchange_rows": num_exchange,
        "rounds": rounds,
        "exchanged_mib_per_run": round(nbytes / 2**20, 2),
        # The host-vs-device competition: the block's headline bytes/s
        # is the WINNER's (never a config this run measured slower).
        "bytes_per_s": round(max(host_rate, dev_rate), 1),
        "winner": winner,
        "device_bytes_per_s": round(dev_rate, 1),
        "host_bytes_per_s": round(host_rate, 1),
        "vs_host": round(dev_rate / host_rate, 3),
        "byte_identical": byte_identical,
        # Per-leg wire-byte accounting (plan_exchange): what the device
        # path puts on ICI vs what the host path's boards carry raw and
        # wire-encoded (the PR-13 pricing composition).
        "plannable": plan["plannable"],
        "wire_dtype": plan["wire_dtype"],
        "legs": plan["legs"],
        "ici_bytes_per_round": plan["ici_bytes"],
        "host_bytes_raw_per_round": plan["host_bytes_raw"],
        "host_bytes_wire_per_round": plan["host_bytes_wire"],
        "device_rounds": int(sum(
            m.counter("shuffle.device_rounds")
            for m in metrics_by_i.values()
        )),
        "fallbacks": int(fallbacks),
    }


# -- distributed-optimizer A/B ------------------------------------------------


def _opt_mesh_axes(n_dev: int) -> dict:
    """The opt A/B mesh shape for ``n_dev`` devices: dp × fsdp=2 when a
    2-way fsdp axis fits (so zero1 is exercised COMPOSED with fsdp, the
    acceptance shape), else all-dp.  Shared with tools/probe_opt.py so
    the probe's printed numbers describe the same layout the A/B
    artifact gates on."""
    fsdp = 2 if n_dev >= 4 and n_dev % 2 == 0 else 1
    return {"dp": n_dev // fsdp, "fsdp": fsdp}


def _opt_config():
    """The opt A/B model geometry: big enough that the optimizer update
    and its collectives are a visible step fraction, small enough for
    the CPU virtual mesh.  DDL_BENCH_OPT_* knobs shrink/grow it.
    Shared with tools/probe_opt.py (same desync rationale as
    :func:`_opt_mesh_axes`)."""
    from ddl_tpu.models.llama import LlamaConfig

    d = int(os.environ.get("DDL_BENCH_OPT_DMODEL", "256"))
    layers = int(os.environ.get("DDL_BENCH_OPT_LAYERS", "4"))
    return (
        LlamaConfig(
            vocab=2048, d_model=d, n_layers=layers, n_heads=8,
            n_kv_heads=4, d_ff=4 * d, max_seq=256,
        ),
        int(os.environ.get("DDL_BENCH_OPT_BATCH", "8")),
        int(os.environ.get("DDL_BENCH_OPT_SEQ", "256")),
        int(os.environ.get("DDL_BENCH_OPT_STEPS", "8")),
    )


def _run_opt_ab(platform: str) -> dict:
    """The distributed-optimizer A/B (ROADMAP item 2 / ISSUE 8): one
    llama multistep trained three ways on a dp×fsdp mesh — replicated
    optimizer state, ZeRO-1 (``parallel.optimizer.ShardedOptimizer``),
    and ZeRO-1 + int8 grad comm — INTERLEAVED best-of timing, with the
    loss-curve-parity gate asserted in the artifact.

    Contract (bench_smoke enforces): ``tokens_per_sec`` is the WINNER of
    the zero1-vs-replicated pair (never-headline-slower invariant);
    ``loss_parity`` must be true (fp32 zero1 is bit-exact vs replicated
    — any drift is a correctness bug, not noise); ``int8_parity`` holds
    the quantized path inside ``parity_rel_tol``;
    ``state_bytes_per_replica`` must shrink vs ``state_bytes_replicated``
    (~dp×); ``grad_comm_bytes_quantized`` < ``grad_comm_bytes_raw``.
    """
    import jax
    import optax
    from jax.sharding import PartitionSpec as P

    from ddl_tpu.models import llama
    from ddl_tpu.observability import Metrics
    from ddl_tpu.parallel.mesh import make_mesh
    from ddl_tpu.parallel.optimizer import (
        PARITY_REL_TOL,
        ShardedOptimizer,
        loss_parity,
        state_bytes_per_replica,
        _tree_bytes,
    )
    from ddl_tpu.parallel.train import make_multistep

    devices = jax.devices()
    n_dev = len(devices)
    axes = _opt_mesh_axes(n_dev)
    dp, fsdp = axes["dp"], axes["fsdp"]
    if dp < 2:
        raise RuntimeError(
            f"opt A/B needs a dp axis >= 2, found {n_dev} device(s)"
        )
    mesh = make_mesh(axes, devices=devices)
    cfg, batch, seq, steps = _opt_config()
    specs = llama.param_specs(cfg)
    loss_fn = lambda p, b: llama.next_token_loss(p, b[0], cfg)  # noqa: E731
    rng = np.random.default_rng(0)
    tokens = (rng.integers(0, cfg.vocab, (batch, seq)).astype(np.int32),)
    params = llama.init_params(cfg, jax.random.key(0))
    reps = int(os.environ.get("DDL_BENCH_OPT_REPS", "3"))

    m = Metrics()
    base = optax.adamw(3e-4)
    zopt = ShardedOptimizer(base, mesh, specs)
    qopt = ShardedOptimizer(base, mesh, specs, grad_comm="int8")

    from ddl_tpu.observability import metrics as default_metrics

    variants = {}
    for name, opt in (
        ("replicated", base), ("zero1", zopt), ("int8", qopt),
    ):
        init_fn, multi_fn = make_multistep(
            loss_fn, opt, mesh, specs, batch_spec=P(("dp",)),
            n_steps=steps,
        )
        state = init_fn(params)
        state_bytes = state_bytes_per_replica(state.opt_state)
        # First call = compile + THE parity curve (same init, same
        # batch, so the three curves are directly comparable).
        state, losses = multi_fn(state, tokens)
        variants[name] = {
            "multi": multi_fn,
            "state": state,
            "losses": [float(x) for x in losses],
            "state_bytes": state_bytes,
        }
        if name == "zero1":
            raw_bytes = default_metrics().gauge("opt.grad_comm_bytes_raw")
        if name == "int8":
            quant_bytes = default_metrics().gauge(
                "opt.grad_comm_bytes_quantized"
            )

    # Interleaved timing: each rep times every variant once, so no
    # variant owns the quiet minutes (the PR 6 vs_baseline discipline).
    # The host read-back of the last loss closes each timed window
    # (async dispatch cannot fake it — the _run_train discipline).
    for _ in range(reps):
        for v in variants.values():
            t0 = time.perf_counter()
            v["state"], losses = v["multi"](v["state"], tokens)
            float(losses[-1])
            dt = (time.perf_counter() - t0) / steps
            v["dt"] = min(v.get("dt", float("inf")), dt)

    tps = {
        name: batch * seq / v["dt"] for name, v in variants.items()
    }
    parity_fp32 = loss_parity(
        variants["replicated"]["losses"], variants["zero1"]["losses"]
    )
    parity_int8 = loss_parity(
        variants["replicated"]["losses"], variants["int8"]["losses"]
    )
    legs = zopt.measure_legs(variants["zero1"]["state"].params, metrics=m)
    if not np.isfinite(variants["zero1"]["losses"][-1]):
        raise RuntimeError(
            f"non-finite zero1 loss {variants['zero1']['losses'][-1]}"
        )
    pair = {"zero1": tps["zero1"], "replicated": tps["replicated"]}
    winner = max(pair, key=pair.get)
    n_params = sum(
        int(np.prod(np.shape(x))) for x in jax.tree.leaves(params)
    )
    return {
        "n_devices": n_dev,
        "dp": dp,
        "fsdp": fsdp,
        "steps": steps,
        "params_millions": round(n_params / 1e6, 2),
        # The zero1-vs-replicated competition: the block's headline is
        # the WINNER's (never a config this run measured slower).
        "tokens_per_sec": round(max(pair.values()), 1),
        "winner": winner,
        "zero1_tokens_per_sec": round(tps["zero1"], 1),
        "replicated_tokens_per_sec": round(tps["replicated"], 1),
        "int8_tokens_per_sec": round(tps["int8"], 1),
        "vs_replicated": round(tps["zero1"] / tps["replicated"], 3),
        # THE parity gate: fp32 zero1 must be BIT-EXACT vs replicated
        # (elementwise update on shards — drift means a correctness
        # bug); int8 must stay inside the gate's tolerance.
        "loss_parity": parity_fp32["parity"],
        "loss_drift": parity_fp32["max_rel_drift"],
        "int8_parity": parity_int8["parity"],
        "int8_loss_drift": round(parity_int8["max_rel_drift"], 5),
        "parity_rel_tol": PARITY_REL_TOL,
        "first_loss": round(variants["zero1"]["losses"][0], 4),
        "final_loss": round(variants["zero1"]["losses"][-1], 4),
        # Measured state HBM per dp replica (from the PLACED state's
        # shardings — shrinks ~dp× under zero1) and the per-step grad
        # communication payload raw vs quantized.
        "state_bytes_replicated": variants["replicated"]["state_bytes"],
        "state_bytes_per_replica": variants["zero1"]["state_bytes"],
        "state_shrink": round(
            variants["replicated"]["state_bytes"]
            / max(variants["zero1"]["state_bytes"], 1),
            2,
        ),
        "state_bytes_total": _tree_bytes(
            variants["zero1"]["state"].opt_state
        ),
        "grad_comm_bytes_raw": int(raw_bytes),
        "grad_comm_bytes_quantized": int(quant_bytes),
        "gather_s": round(legs["gather_s"], 5),
        "scatter_s": round(legs["scatter_s"], 5),
    }


# -- driver -------------------------------------------------------------------


def _emit(result: dict, errors: dict, t_start: float,
          wall_key: str = "elapsed_s") -> int:
    """Print the run's ONE JSON line and return the exit code: a failed
    phase on a TPU run is a failed run (the line is still printed
    first); a CPU contract run keeps exit 0 so ``bench_smoke`` can read
    the ``errors`` block it asserts on."""
    if errors:
        result["errors"] = errors
    result[wall_key] = round(time.perf_counter() - t_start, 1)
    print(json.dumps(result))
    return 1 if errors and result["platform"] == "tpu" else 0


def main() -> int:
    t_start = time.perf_counter()
    mode = os.environ.get("DDL_BENCH_MODE", "all")
    errors: dict = {}

    platform = bring_up(
        cpu_devices=8 if mode in ("ici", "shuffle", "opt") else 1
    )

    result: dict = {
        "metric": "ingest_samples_per_sec",
        "value": None,
        "unit": "samples/s",
        "vs_baseline": None,
        "platform": platform,
        "git_head": _git_head(),
        "recorded": time.strftime("%Y-%m-%dT%H:%M:%S"),
    }

    if mode == "cache":
        # `make cache-bench`: ONLY the shard-cache cold/warm A/B, with
        # its speedup ratio as the headline (docs/CACHING.md).
        result["metric"] = "cache_warm_vs_cold"
        result["unit"] = "x"
        try:
            result["cache"] = _run_cache_ab()
            result["value"] = result["cache"]["warm_vs_cold"]
        except Exception as e:  # noqa: BLE001 - must emit JSON regardless
            errors["cache"] = f"{type(e).__name__}: {e}"
        return _emit(result, errors, t_start)

    if mode == "wire":
        # `make wire-bench`: raw vs quantized vs compressed exchange
        # wire over a simulated constrained link (ISSUE 13), with the
        # measured winner as the headline under the same never-slower
        # invariant as every other competition; lossless byte identity
        # + lossy loss-parity baked into the block (bench_smoke
        # enforces).
        result["metric"] = "wire_samples_per_sec"
        result["unit"] = "samples/s"
        try:
            result["wire"] = _run_wire_ab()
            result["value"] = result["wire"]["samples_per_sec"]
            result["headline_config"] = result["wire"]["winner"]
        except Exception as e:  # noqa: BLE001 - must emit JSON regardless
            errors["wire"] = f"{type(e).__name__}: {e}"
        return _emit(result, errors, t_start)

    if mode == "autotune":
        # `make tune-bench`: self-tuned vs shipped-defaults from a
        # deliberately mis-matched cold start (ISSUE 20) — boot
        # calibration (measured link probe + wire break-even) plus the
        # closed-loop knob controller, both paying for themselves
        # inside the tuned leg's timed window.  Headline is the
        # speedup ratio; bench_smoke gates never-slower (one noise
        # retry), zero never-worse reverts in the winning leg, and
        # measured cost_source provenance on the decisions.
        result["metric"] = "autotune_vs_defaults"
        result["unit"] = "x"
        try:
            result["autotune"] = _run_autotune()
            result["value"] = result["autotune"]["vs_defaults"]
            result["headline_config"] = "self-tuned"
        except Exception as e:  # noqa: BLE001 - must emit JSON regardless
            errors["autotune"] = f"{type(e).__name__}: {e}"
        return _emit(result, errors, t_start)

    if mode == "obs":
        # `make obs-bench`: the tracing layer priced end to end
        # (ISSUE 15) — armed-vs-disarmed span/recorder overhead
        # (interleaved A/B; the headline is the DISARMED production
        # rate, with the armed rate gated <= 2% under it by
        # bench_smoke), byte identity across arming, histogram keys in
        # the armed north-star report, and the seeded-corruption leg's
        # flight-recorder artifact.
        result["metric"] = "obs_samples_per_sec"
        result["unit"] = "samples/s"
        try:
            result["obs"] = _run_obs_ab()
            result["value"] = result["obs"]["disarmed_samples_per_sec"]
            result["headline_config"] = "disarmed"
        except Exception as e:  # noqa: BLE001 - must emit JSON regardless
            errors["obs"] = f"{type(e).__name__}: {e}"
        return _emit(result, errors, t_start)

    if mode == "preempt":
        # `make preempt-bench`: preemption tolerance priced end to end
        # (ISSUE 14) — async-vs-sync per-checkpoint stall (interleaved
        # A/B; the headline is the stall reduction), notice→resumed
        # recovery wall time through the real chaos site + guard, and
        # the hard-kill lost-work bound (steps lost <= checkpoint
        # interval), with the resumed streams byte-identical and loss
        # curves bit-exact (bench_smoke enforces the block).
        result["metric"] = "ckpt_stall_reduction"
        result["unit"] = "x"
        try:
            result["preempt"] = _run_preempt_ab()
            result["value"] = result["preempt"]["stall_reduction"]
            result["headline_config"] = "async"
        except Exception as e:  # noqa: BLE001 - must emit JSON regardless
            errors["preempt"] = f"{type(e).__name__}: {e}"
        return _emit(result, errors, t_start)

    if mode == "failover":
        # `make failover-bench`: control-plane survivability priced end
        # to end (ISSUE 18) — mid-stream supervisor kill with the
        # lease-expiry standby takeover wall time as the headline, the
        # window stream byte-identical to the steady-state reference
        # with zero watchdog failures, adoption sends absorbed under
        # envelope drop/dup chaos (dedup counters in the block), and
        # scheduler fairness carried bit-exact across the handover
        # (bench_smoke enforces every deterministic field).
        result["metric"] = "failover_takeover_s"
        result["unit"] = "s"
        try:
            result["failover"] = _run_failover_ab()
            result["value"] = result["failover"]["takeover_s"]
        except Exception as e:  # noqa: BLE001 - must emit JSON regardless
            errors["failover"] = f"{type(e).__name__}: {e}"
        return _emit(result, errors, t_start)

    if mode == "fabric":
        # `make fabric-bench`: the multi-job ingest fabric soaked end
        # to end (ISSUE 19) — 50 Zipf-weighted jobs probing one
        # supervisor-resident admission authority from 100 simulated
        # host bindings over the acked control plane, with the max
        # per-job weighted-share deviation as the headline (lower is
        # fairer), the scale-reaction / preemption-drain SLOs, per-job
        # accounting on the ONE shared cache, and the supervisor-kill
        # leg's bit-identical admission order (bench_smoke enforces
        # every deterministic field).
        result["metric"] = "fabric_share_deviation"
        result["unit"] = "frac"
        try:
            result["fabric"] = _run_fabric_soak()
            result["value"] = result["fabric"]["share_deviation_max"]
        except Exception as e:  # noqa: BLE001 - must emit JSON regardless
            errors["fabric"] = f"{type(e).__name__}: {e}"
        return _emit(result, errors, t_start)

    if mode == "ici":
        # `make ici-bench`: the device-side distribution A/B (Pallas
        # fan-out + redistribution vs the XLA scatter), with the winner
        # as the headline — the ici-vs-xla competition rides the same
        # never-headline-slower invariant as the ingest configs
        # (bench_smoke enforces).  A CPU run (asked for by name) runs
        # interpret-mode on the 8-device virtual mesh; the line's
        # ``platform`` says its numbers are not device numbers.
        result["metric"] = "ici_bytes_per_sec"
        result["unit"] = "bytes/s"
        try:
            result["ici"] = _run_ici_ab(platform)
            result["value"] = result["ici"]["bytes_per_s"]
            result["headline_config"] = result["ici"]["winner"]
        except Exception as e:  # noqa: BLE001 - must emit JSON regardless
            errors["ici"] = f"{type(e).__name__}: {e}"
        return _emit(result, errors, t_start)

    if mode == "shuffle":
        # `make shuffle-bench`: the global-
        # shuffle exchange A/B (host rendezvous vs the device-tier
        # collective, ISSUE 17) with the winner as the headline — the
        # same never-headline-slower invariant as the ici/opt
        # competitions, byte-identity asserted per rep, per-leg
        # wire-byte accounting in the block (bench_smoke enforces).
        # A CPU run (asked for by name) runs the ring interpret-mode on
        # the 8-device virtual mesh (it usually LOSES there — the
        # contract stays green).
        result["metric"] = "shuffle_bytes_per_sec"
        result["unit"] = "bytes/s"
        try:
            result["shuffle"] = _run_shuffle_ab(platform)
            result["value"] = result["shuffle"]["bytes_per_s"]
            result["headline_config"] = result["shuffle"]["winner"]
        except Exception as e:  # noqa: BLE001 - must emit JSON regardless
            errors["shuffle"] = f"{type(e).__name__}: {e}"
        return _emit(result, errors, t_start)

    if mode == "placement":
        # `make placement-bench`: topology-aware vs naive producer→
        # consumer placement over the simulated fabric (ISSUE 10), with
        # the measured winner as the headline under the same never-
        # headline-slower invariant as every other competition, plus
        # the membership chaos counters (bench_smoke enforces).
        result["metric"] = "placement_bytes_per_sec"
        result["unit"] = "bytes/s"
        try:
            result["placement"] = _run_placement_ab()
            result["value"] = result["placement"]["bytes_per_s"]
            result["headline_config"] = result["placement"]["winner"]
        except Exception as e:  # noqa: BLE001 - must emit JSON regardless
            errors["placement"] = f"{type(e).__name__}: {e}"
        return _emit(result, errors, t_start)

    if mode == "tenancy":
        # `make tenancy-bench`: the multi-tenant ingest-service A/B
        # (ISSUE 11) — K concurrent tenants on a heavy-tailed demand
        # schedule, autoscaled pool vs the static floor, with the
        # measured winner as the headline under the same never-slower
        # invariant as every other competition, plus per-tenant p99
        # latency/byte-identity and the burst+host-loss chaos leg
        # (bench_smoke enforces the block).
        result["metric"] = "tenancy_samples_per_sec"
        result["unit"] = "samples/s"
        try:
            result["tenancy"] = _run_tenancy_ab()
            result["value"] = result["tenancy"]["samples_per_sec"]
            result["headline_config"] = result["tenancy"]["winner"]
        except Exception as e:  # noqa: BLE001 - must emit JSON regardless
            errors["tenancy"] = f"{type(e).__name__}: {e}"
        return _emit(result, errors, t_start)

    if mode == "opt":
        # `make opt-bench`: the distributed-
        # optimizer A/B (zero1 vs replicated state, fp32 vs int8 grad
        # comm) with loss parity asserted in the artifact and the
        # winner as the headline — the same never-headline-slower
        # invariant as the ingest/ici competitions (bench_smoke
        # enforces).  A CPU run (asked for by name) uses the 8-device
        # virtual mesh.
        result["metric"] = "opt_tokens_per_sec"
        result["unit"] = "tokens/s"
        try:
            result["opt"] = _run_opt_ab(platform)
            result["value"] = result["opt"]["tokens_per_sec"]
            result["headline_config"] = result["opt"]["winner"]
        except Exception as e:  # noqa: BLE001 - must emit JSON regardless
            errors["opt"] = f"{type(e).__name__}: {e}"
        return _emit(result, errors, t_start)

    if mode in ("ingest", "all", "stream"):
        # "stream" (the window-size sweep): ONLY the
        # two window-stream configs + the link measure — the batch-path
        # configs don't depend on DDL_BENCH_STREAM_MIB.
        try:
            # One link-capability measurement shared by every ingest config
            # (the denominator for BASELINE.md's utilization target).
            from ddl_tpu.ingest import measure_h2d_bandwidth

            link_bw = measure_h2d_bandwidth()
        except Exception as e:  # noqa: BLE001
            link_bw = 0.0
            errors["h2d_bandwidth"] = f"{type(e).__name__}: {e}"
        def _ingest_best(**kw):
            # Every ingest config uses the same min-under-noise estimator
            # (see best_of) so ablation deltas are not biased by a
            # transient hitting only one side; per-run utilization gates
            # discard artifact runs before selection.
            def run():
                rate, ns = _run_ingest(**kw)
                if kw.get("link_bytes_per_sec"):
                    _gate_utilization(ns, "ingest")
                return rate, ns

            return best_valid(2, run, key=lambda r: -r[0])

        # One kwargs table for every headline contender, shared by the
        # competition below AND the interleaved vs_baseline re-runs — so
        # the ratio's two sides are guaranteed to measure the exact
        # config the headline named.
        headline_kw = {
            # The two staged legs FORCE the engine (staged=True): with the
            # env default, batch_staged routes CPU drains inline, so on
            # the fallback box "prefetch" would silently measure the
            # identical code path as "prefetch_inline" and the
            # staged_vs_inline ablation would compare inline to inline.
            # Forcing keeps each ablation axis one-variable: prefetch vs
            # no_prefetch isolates the lookahead, prefetch vs
            # prefetch_inline isolates the staging engine.  (On
            # accelerators None already stages — forcing changes nothing.)
            "prefetch": dict(
                nslots=2, n_producers=N_PRODUCERS, sync_every_batch=False,
                use_prefetch=True, staged=True, link_bytes_per_sec=link_bw,
            ),
            "no_prefetch": dict(
                nslots=2, n_producers=N_PRODUCERS, sync_every_batch=False,
                use_prefetch=False, staged=True, link_bytes_per_sec=link_bw,
            ),
            "prefetch_inline": dict(
                nslots=2, n_producers=N_PRODUCERS, sync_every_batch=False,
                use_prefetch=True, staged=False, link_bytes_per_sec=link_bw,
            ),
            "process": dict(
                nslots=2, n_producers=N_PRODUCERS, sync_every_batch=False,
                mode="process", use_prefetch=True,
                link_bytes_per_sec=link_bw,
            ),
        }

        if mode != "stream":
            # The headline COMPETES across every batch-path drain the
            # run measures — prefetch/no-prefetch (THREAD, staged),
            # the inline-staging escape hatch, and PROCESS mode — a run
            # must never headline a config it itself measured as slower
            # (VERDICT r5 weak #1; trustworthy-headline refactor).  The
            # winner is recorded as ``headline_config`` and bench_smoke
            # enforces the never-slower invariant against every sibling
            # block in the same JSON line.
            headline_runs: dict = {}
            try:
                headline_runs["prefetch"] = _ingest_best(
                    **headline_kw["prefetch"]
                )
            except Exception as e:  # noqa: BLE001 - must emit JSON regardless
                errors["ingest"] = f"{type(e).__name__}: {e}"
            try:
                # Same pipeline without the prefetch lookahead: the delta
                # IS the prefetch win/loss (VERDICT r2 item 5 asked for
                # before/after).
                headline_runs["no_prefetch"] = _ingest_best(
                    **headline_kw["no_prefetch"]
                )
                no_pf, ns_no_pf = headline_runs["no_prefetch"]
                result["ingest_no_prefetch"] = {
                    "samples_per_sec": round(no_pf, 1),
                    "stall_fraction": round(ns_no_pf["stall_fraction"], 4),
                }
            except Exception as e:  # noqa: BLE001
                errors["ingest_no_prefetch"] = f"{type(e).__name__}: {e}"
            try:
                # The prefetch config over the inline path (DDL_TPU_STAGED=0
                # equivalent): the staged-vs-inline ablation — the delta
                # is the engine's win (pooled buffers + off-thread
                # copy/dispatch + early slot release) — and a headline
                # contender in its own right.
                headline_runs["prefetch_inline"] = _ingest_best(
                    **headline_kw["prefetch_inline"]
                )
                inline, ns_inline = headline_runs["prefetch_inline"]
                result["ingest_inline"] = {
                    "samples_per_sec": round(inline, 1),
                    "stall_fraction": round(ns_inline["stall_fraction"], 4),
                }
                if "prefetch" in headline_runs:
                    result["staged_vs_inline"] = round(
                        headline_runs["prefetch"][0] / inline, 3
                    )
            except Exception as e:  # noqa: BLE001
                errors["ingest_inline"] = f"{type(e).__name__}: {e}"
            try:
                # PROCESS mode: spawned producer processes over the native
                # C++ shm ring — the native transport's throughput number,
                # and the production shape on a multi-core TPU host.
                headline_runs["process"] = _ingest_best(
                    **headline_kw["process"]
                )
                proc, ns_proc = headline_runs["process"]
                result["ingest_process_mode"] = {
                    "samples_per_sec": round(proc, 1),
                    "stall_fraction": round(ns_proc["stall_fraction"], 4),
                    "ingest_bytes_per_sec": round(
                        ns_proc["ingest_bytes_per_sec"], 1
                    ),
                }
            except Exception as e:  # noqa: BLE001
                errors["ingest_process_mode"] = f"{type(e).__name__}: {e}"
            if headline_runs:
                label = max(headline_runs, key=lambda k: headline_runs[k][0])
                best_rate, north_star = headline_runs[label]
                result["value"] = round(best_rate, 1)
                result["headline_config"] = label
                result.update(
                    samples_per_sec=round(north_star["samples_per_sec"], 1),
                    stall_fraction=round(north_star["stall_fraction"], 4),
                    ingest_bytes_per_sec=round(
                        north_star["ingest_bytes_per_sec"], 1
                    ),
                    link_bytes_per_sec=round(
                        north_star.get("link_bytes_per_sec", 0.0), 1
                    ),
                    bandwidth_utilization=round(
                        north_star.get("bandwidth_utilization", 0.0), 4
                    ),
                )
                # Staged-engine observability for the headline run: where
                # the engine spent time and whether the pool recycled
                # (ddl_tpu.staging; zeros when DDL_TPU_STAGED=0).
                result["staging"] = {
                    "stage_copy_s": round(north_star["stage_copy_s"], 4),
                    "transfer_s": round(north_star["transfer_s"], 4),
                    "stall_s": round(north_star["stall_s"], 4),
                    "alias_windows": north_star["alias_windows"],
                    "alias_fallbacks": north_star["alias_fallbacks"],
                    "pool_hits": north_star["pool_hits"],
                    "pool_misses": north_star["pool_misses"],
                    "queue_depth_max": north_star["queue_depth_max"],
                }
                # Robustness observability (docs/ROBUSTNESS.md): all
                # zeros on a healthy run — a nonzero here in a BENCH_*
                # trajectory means the run only "passed" by recovering
                # (replays, respawns, degraded shuffle) and deserves a
                # look even when throughput held.
                result["robustness"] = {
                    "respawns": north_star["respawns"],
                    "watchdog_failures": north_star["watchdog_failures"],
                    "corrupt_windows": north_star["corrupt_windows"],
                    "replays": north_star["replays"],
                    "shuffle_degraded": north_star["shuffle_degraded"],
                    "staging_retries": north_star["staging_retries"],
                    "inline_fallbacks": north_star["inline_fallbacks"],
                }
            try:
                # Shard-cache cold/warm A/B over a throttled backend
                # (ddl_tpu/cache, docs/CACHING.md): the warm tier's win
                # on a slow source, with byte-identity asserted.
                result["cache"] = _run_cache_ab()
            except Exception as e:  # noqa: BLE001
                errors["cache"] = f"{type(e).__name__}: {e}"
        def _stream_result(stream_mode: str) -> dict:
            """One gated best-of stream measurement for ``stream_mode``
            (shared by the thread and process configs so the utilization
            gate cannot be dropped from one of them)."""

            def run():
                rate, ns = _run_ingest_stream(link_bw, mode=stream_mode)
                if link_bw:
                    _gate_utilization(ns, f"stream-{stream_mode}")
                return rate, ns

            rate, ns = best_valid(2, run, key=lambda r: -r[0])
            return {
                "samples_per_sec": round(rate, 1),
                "window_mib": round(N_DATA_STREAM * N_VALUES * 4 / 2**20, 1),
                "bytes_per_sec": round(ns["ingest_bytes_per_sec"], 1),
                "stall_fraction": round(ns["stall_fraction"], 4),
                "bandwidth_utilization": round(
                    ns.get("bandwidth_utilization", 0.0), 4
                ),
                # Captured at leg end: load_avg then reflects THIS leg's
                # contention, so a starved process leg is diagnosable
                # from the committed JSON alone.
                "core_attach": _core_attach(),
            }

        def _headline_util(key: str, label: str) -> None:
            """Let every stream config compete for the headline
            utilization figure, labelled with the winning config."""
            util = result.get(key, {}).get("bandwidth_utilization", 0.0)
            if util > (result.get("bandwidth_utilization") or 0.0):
                result["bandwidth_utilization"] = util
                result["bandwidth_utilization_config"] = label

        try:
            # Zero-copy window streaming (loader.windows + inplace fill):
            # the bandwidth-utilization headline config.
            result["ingest_stream"] = _stream_result("thread")
            _headline_util("ingest_stream", "stream-thread")
        except Exception as e:  # noqa: BLE001
            errors["ingest_stream"] = f"{type(e).__name__}: {e}"
        try:
            # Stream over PROCESS-mode producers: the production shape on
            # a multi-core TPU host (fills on producer cores, consumer
            # core streams slots to HBM).
            result["ingest_stream_process"] = _stream_result("process")
            _headline_util("ingest_stream_process", "stream-process")
        except Exception as e:  # noqa: BLE001
            errors["ingest_stream_process"] = f"{type(e).__name__}: {e}"
        # The PROCESS-vs-THREAD stream ratio + this run's core attach:
        # the write-once producer refactor's north-star number.  A ratio
        # below 0.9 on a starved attach (fewer cores than producers +
        # consumer) is preemption, not transport overhead — the
        # core_attach record makes the two cases distinguishable in the
        # committed JSON, and bench_smoke gates on exactly that.
        ingest_block: dict = {"core_attach": _core_attach()}
        thread_rate = result.get("ingest_stream", {}).get("samples_per_sec")
        proc_rate = result.get("ingest_stream_process", {}).get(
            "samples_per_sec"
        )
        if thread_rate and proc_rate:
            ingest_block["process_vs_thread"] = round(
                proc_rate / thread_rate, 3
            )
        result["ingest"] = ingest_block
        if mode != "stream":
            try:
                # Reference design point: strict alternation, synchronous
                # transfers (its one-window token protocol).  Measured
                # INTERLEAVED with re-runs of the headline winner: the
                # box noise is one-sided and drifts minute-to-minute
                # (measured: identical configs swing 50k-78k samples/s),
                # so a ratio of two distant-in-time measurements is an
                # artifact generator — r05 shipped vs_baseline 0.865
                # from exactly that, while an interleaved best-of pair
                # on the same box reads >1.  Best-of on BOTH sides (the
                # noise only ever slows a run), alternating samples so
                # neither side owns the quiet minutes.
                winner_kw = headline_kw.get(result.get("headline_config"))
                rates_w = (
                    [result["value"]] if result.get("value") else []
                )
                rates_b = []
                for _ in range(2):
                    b_rate, _ns = _run_ingest(
                        nslots=1, n_producers=N_PRODUCERS,
                        sync_every_batch=True,
                    )
                    rates_b.append(b_rate)
                    if winner_kw is not None:
                        w_rate, w_ns = _run_ingest(**winner_kw)
                        if winner_kw.get("link_bytes_per_sec"):
                            # Same artifact filter the original headline
                            # selection ran under (_ingest_best): a re-run
                            # whose utilization reads implausible is the
                            # timing-artifact class the gate exists to
                            # discard — it must not become the published
                            # headline via max(rates_w) either.
                            try:
                                _gate_utilization(w_ns, "ingest-rerun")
                            except RuntimeError:
                                continue  # sample discarded
                        rates_w.append(w_rate)
                baseline = max(rates_b)
                result["baseline_samples_per_sec"] = round(baseline, 1)
                if rates_w:
                    # The re-runs are further samples of the SAME config
                    # under the same estimator: the headline keeps the
                    # best observation (never publishes a number the run
                    # measured slower for its own config).
                    result["value"] = round(max(rates_w), 1)
                    result["vs_baseline"] = round(
                        max(rates_w) / baseline, 3
                    )
            except Exception as e:  # noqa: BLE001
                errors["ingest_baseline"] = f"{type(e).__name__}: {e}"

    if mode in ("train", "all", "big"):
        train: dict = {}
        impls = ("flash", "dense") if platform == "tpu" else ("dense",)
        if mode == "big":
            impls = ()
        for impl in impls:
            try:
                train[impl] = _run_train(platform, impl)
            except Exception as e:  # noqa: BLE001
                errors[f"train_{impl}"] = f"{type(e).__name__}: {e}"
        if platform == "tpu":
            # HBM-filling credibility config (VERDICT r3 item 7): the MFU
            # number README quotes, at a geometry representative of the
            # 8B-class north-star workload.
            try:
                result["train_big"] = _run_train(
                    platform, "flash", size="big"
                )
            except Exception as e:  # noqa: BLE001
                errors["train_big"] = f"{type(e).__name__}: {e}"
        # BOTH impls are reported verbatim (round 2 published only the
        # "best", which was the broken measurement — VERDICT r2 item 1a).
        for impl, r in train.items():
            result[f"train_{impl}"] = r
        if "flash" in train and "dense" in train:
            # Compare STEP-1 losses: same init, same batch, one step — any
            # material gap means one impl computed a different function.
            # (Final losses drift legitimately: bf16 flash vs fp32-softmax
            # dense amplify over the chained optimizer steps.)
            lf, ld = train["flash"]["first_loss"], train["dense"]["first_loss"]
            if abs(lf - ld) > 0.01 * max(abs(ld), 1e-6):
                errors["train_loss_mismatch"] = (
                    f"flash {lf} vs dense {ld} at step 1 from identical init"
                )
            result["flash_speedup_vs_dense"] = round(
                train["flash"]["tokens_per_sec"]
                / train["dense"]["tokens_per_sec"], 3,
            )
        if train:
            best = max(train.values(), key=lambda r: r["tokens_per_sec"])
            result.update(
                train_tokens_per_sec=best["tokens_per_sec"],
                train_step_time_ms=best["step_time_ms"],
                train_mfu=best["mfu"],
                train_model_tflops_per_sec=best["model_tflops_per_sec"],
                train_attn_impl=best["attn_impl"],
                device_kind=best["device_kind"],
            )
        if mode != "big":
            try:
                impl = "flash" if platform == "tpu" else "dense"
                fit = _run_fit(platform, impl)
                if impl in train:
                    # Cross-config reference (the r1-r5 trajectory
                    # metric): end-to-end vs the train_* multistep —
                    # NOT the gated overhead (fit["pipeline_overhead"]
                    # uses the matched in-function ceiling; this one
                    # bundles in scan-length/input-form amortization).
                    fit["overhead_vs_train"] = round(
                        1.0
                        - fit["tokens_per_sec"]
                        / train[impl]["tokens_per_sec"],
                        4,
                    )
                result["fit_stream"] = fit
            except Exception as e:  # noqa: BLE001
                errors["fit_stream"] = f"{type(e).__name__}: {e}"
        if platform == "tpu" and mode != "big":
            try:
                result["attn_sweep"] = _attn_sweep()
            except Exception as e:  # noqa: BLE001
                errors["attn_sweep"] = f"{type(e).__name__}: {e}"

    if mode in ("decode", "all"):
        # Serving-phase numbers (KV-cache prefill + scanned decode):
        # training MFU says nothing about the inference path, and the
        # decode regime is HBM-bound, graded by MBU instead.
        try:
            result["decode"] = _run_decode(platform)
        except Exception as e:  # noqa: BLE001
            errors["decode"] = f"{type(e).__name__}: {e}"
        if platform == "tpu":
            # Serving the HBM-filling 1.4B config: the representative
            # memory-bound decode point (2.8 GB of bf16 weights/step).
            try:
                result["decode_big"] = _run_decode(platform, size="big")
            except Exception as e:  # noqa: BLE001
                errors["decode_big"] = f"{type(e).__name__}: {e}"

    if result["value"] is None:
        # Stream-only mode: a stream config IS the run's headline
        # (either mode may have been gate-rejected; take the survivor).
        for key in ("ingest_stream", "ingest_stream_process"):
            if result.get(key):
                result["metric"] = f"{key}_samples_per_sec"
                result["value"] = result[key]["samples_per_sec"]
                break
    if result["value"] is None and result.get("train_tokens_per_sec"):
        # Ingest failed but training measured: still report a headline.
        result["metric"] = "train_tokens_per_sec"
        result["value"] = result["train_tokens_per_sec"]
        result["unit"] = "tokens/s"
    if result["value"] is None and result.get("train_big"):
        # Big-only mode: the big config IS the run's headline.
        result["metric"] = "train_big_tokens_per_sec"
        result["value"] = result["train_big"]["tokens_per_sec"]
        result["unit"] = "tokens/s"
    if result["value"] is None:
        # Decode-only mode: serving throughput is the headline (either
        # size may have been gate-rejected; take the survivor).
        for key in ("decode", "decode_big"):
            if result.get(key):
                result["metric"] = "decode_tokens_per_sec"
                result["value"] = result[key]["decode_tokens_per_sec"]
                result["unit"] = "tokens/s"
                break
    return _emit(result, errors, t_start, wall_key="bench_wall_s")


if __name__ == "__main__":
    sys.exit(main())
