#!/usr/bin/env python3
"""chip_smoke.py — the main path, once, on the chip.

    python3 chip_smoke.py            # one TPU chip
    python3 chip_smoke.py --chips 4  # only the four-chip path and what
                                     # it is compared with

One process, the public surface, real sizes; every check raises, and the
last line of stdout is the one JSON object the driver reads.  Without a
TPU it exits non-zero before any phase and prints no result.

One chip:

- **ingest leg** — two spawned PROCESS producers fill 64 MiB float32
  windows (65536 x 256) write-once into native shm
  ring slots; ``loader.windows()`` streams them into HBM; every window
  is CRC'd against a host-side regeneration from the seed; every
  fallback counter must read zero and both producers must exit 0
  without ever having initialised a JAX backend.
- **train leg** — ``Trainer.fit(window_stream=True, mode="process")``,
  fused default, on the repo's HBM-filling Llama (20 layers x 2048
  wide, 16/8 heads, vocab 32768: 1.39 B parameters, bf16, selective
  remat, batch 4 x seq 2048, flash attention) fed token windows by
  seeded producers; then one checkpoint generation, and a fresh
  ``Trainer`` on the same directory that resumes and takes the next
  window with ``state.step`` continuous.

Four chips (``dp=2 x fsdp=2``): 64 MiB windows through the Pallas ICI
fan-out (scatter + gather, and broadcast) against the XLA scatter; the
same ``Trainer.fit`` with distribution forced to ``ici`` and to ``xla``
(window CRCs identical, losses bit-equal, zero fallbacks); parameters
and HBM use spread over the four devices; the device-side epoch
exchange against the host exchange.

JAX is touched only under ``main()``: spawn re-imports this script in
every producer, and producers stay off the device.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import json
import math
import os
import sys
import tempfile
import threading
import time
import zlib

import numpy as np

from ddl_tpu import DataProducerOnInitReturn, ProducerFunctionSkeleton


@dataclasses.dataclass(frozen=True)
class Sizes:
    """The run's geometry.  The defaults ARE the run; the only other
    instance is the CPU rehearsal's (tests/smoke_rehearsal.py)."""

    # Stream windows: 65536 x 256 float32, 64 MiB.
    stream_rows: int = 65536
    stream_cols: int = 256
    stream_batch: int = 2048
    stream_windows: int = 8
    lookahead: int = 3
    # The repo's HBM-filling Llama: 1.39 B parameters at these sizes.
    vocab: int = 32768
    d_model: int = 2048
    n_layers: int = 20
    n_heads: int = 16
    n_kv_heads: int = 8
    d_ff: int = 8192
    seq: int = 2048
    batch: int = 4
    steps_per_window: int = 2
    train_windows: int = 3
    # Four chips: widths stay, depth is cut to keep the four-way
    # compile (charged four times) short; >= 4 windows so both landing
    # slots are used twice.
    mc_layers: int = 8
    mc_windows: int = 4
    shuffle_rows: int = 16384  # x stream_cols float32 = 16 MiB a pool
    shuffle_rounds: int = 3


class SmokeFailure(RuntimeError):
    """A check of the smoke did not hold."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def say(line: str) -> None:
    print(line, flush=True)


def on_tpu() -> bool:
    """False only in the CPU rehearsal (``main`` requires a TPU): the
    two checks nothing but a chip can satisfy are skipped there."""
    import jax

    return jax.devices()[0].platform == "tpu"


@contextlib.contextmanager
def phase(name: str):
    t0 = time.perf_counter()
    say(f"phase {name}: start")
    yield
    say(f"phase {name}: {time.perf_counter() - t0:.1f} s wall")


# -- seeded data (host-side regeneration is the reference) --------------------


def stream_window(seed: int, producer_idx: int, iteration: int,
                  out: np.ndarray) -> None:
    """Window ``iteration`` of producer ``producer_idx``, written in
    place: a function of (seed, producer, iteration) and nothing else."""
    rng = np.random.default_rng([seed, producer_idx, iteration])
    rng.random(out.shape, dtype=np.float32, out=out)


def token_window(seed: int, producer_idx: int, iteration: int, vocab: int,
                 out: np.ndarray) -> None:
    rng = np.random.default_rng([seed, producer_idx, iteration])
    out[...] = rng.integers(0, vocab, out.shape, dtype=np.int32)


class _SeededProducer(ProducerFunctionSkeleton):
    """Module-level (picklable) write-once producer: every fill fully
    rewrites the ring slot it is handed.  After each fill it records, in
    ``status_dir``, whether this process has imported JAX or initialised
    a backend — the consumer holds the chip and a producer must not."""

    inplace_fill = True

    def __init__(self, seed: int, status_dir: str):
        self.seed = seed
        self.status_dir = status_dir

    def on_init(self, producer_idx=0, **kw):
        self._idx = producer_idx
        return self.geometry()

    def execute_function(self, my_ary, iteration=0, **kw):
        self.fill(my_ary, iteration)
        bridge = sys.modules.get("jax._src.xla_bridge")
        status = {
            "pid": os.getpid(),
            "windows": iteration + 1,
            "jax_imported": "jax" in sys.modules,
            "backend_initialised": bool(
                bridge is not None and getattr(bridge, "_backends", None)
            ),
        }
        path = os.path.join(self.status_dir, f"producer_{self._idx}.json")
        with open(path + ".tmp", "w") as f:
            json.dump(status, f)
        os.replace(path + ".tmp", path)


class StreamProducer(_SeededProducer):
    def __init__(self, seed, status_dir, rows, cols):
        super().__init__(seed, status_dir)
        self.rows, self.cols = rows, cols

    def geometry(self):
        return DataProducerOnInitReturn(
            nData=self.rows, nValues=self.cols,
            shape=(self.rows, self.cols), splits=(self.cols - 1, 1),
        )

    def fill(self, my_ary, iteration):
        stream_window(self.seed, self._idx, iteration, my_ary)


class TokenProducer(_SeededProducer):
    def __init__(self, seed, status_dir, rows, seq, vocab):
        super().__init__(seed, status_dir)
        self.rows, self.seq, self.vocab = rows, seq, vocab

    def geometry(self):
        return DataProducerOnInitReturn(
            nData=self.rows, nValues=self.seq, shape=(self.rows, self.seq),
            splits=(self.seq,), dtype=np.int32,
        )

    def fill(self, my_ary, iteration):
        token_window(self.seed, self._idx, iteration, self.vocab, my_ary)


def check_producers_stayed_off_jax(status_dir: str, n_producers: int) -> None:
    for idx in range(1, n_producers + 1):
        path = os.path.join(status_dir, f"producer_{idx}.json")
        check(os.path.exists(path), f"producer {idx} left no status file")
        with open(path) as f:
            status = json.load(f)
        check(
            status["pid"] != os.getpid(),
            f"producer {idx} ran inside the consumer process",
        )
        check(
            not status["backend_initialised"],
            f"producer {idx} (pid {status['pid']}) initialised a JAX backend",
        )
        say(
            f"producer {idx}: pid {status['pid']}, {status['windows']} "
            f"windows filled, jax imported={status['jax_imported']}, "
            "backend initialised=False"
        )


# -- compile accounting -------------------------------------------------------


class CompileLog:
    """Backend-compile seconds per program, from JAX's own monitoring
    events (the duration covers the persistent-cache lookup, so a cache
    hit shows as a short compile)."""

    def __init__(self):
        import jax.monitoring

        self.programs: list = []
        self.cache_hits = 0
        self._mark = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **kw):
        if event.endswith("backend_compile_duration"):
            self.programs.append((kw.get("fun_name", "?"), float(secs)))

    def _event(self, event, **kw):
        if event.endswith("compilation_cache/cache_hits"):
            self.cache_hits += 1

    def report(self, label: str) -> None:
        """Print what compiled since the last report."""
        new, self._mark = self.programs[self._mark:], len(self.programs)
        for name, secs in new:
            if secs >= 0.5:
                say(f"compile {label}: {name} {secs:.1f} s")
        say(
            f"compile {label}: {len(new)} programs, "
            f"{sum(s for _, s in new):.1f} s in all"
        )

    def total(self) -> float:
        return sum(s for _, s in self.programs)


# -- the window stream (ingest leg; four-chip fan-out leg) --------------------


def stream_windows(sz: Sizes, seed: int, sharding=None, distribute=None):
    """Drain ``sz.stream_windows`` windows from two PROCESS producers
    through ``loader.windows()`` onto the device (or ``sharding``).
    Returns ([((producer, seq), crc32)], north-star report, exit codes,
    ring class name)."""
    from ddl_tpu import DistributedDataLoader, Marker, distributed_dataloader
    from ddl_tpu.ingest import north_star_report
    from ddl_tpu.observability import Metrics
    from ddl_tpu.watchdog import Watchdog

    metrics = Metrics()
    status_dir = tempfile.mkdtemp(prefix="ddl_smoke_status_")
    procs: list = []
    seen: list = []
    ring_kind: list = []

    @distributed_dataloader(
        n_producers=2, mode="process", nslots=sz.lookahead + 1
    )
    def consume(env):
        procs.extend(env.workers.processes)
        wd = Watchdog(env.workers, metrics=metrics).start()
        try:
            loader = DistributedDataLoader(
                StreamProducer(
                    seed, status_dir, sz.stream_rows, sz.stream_cols
                ),
                batch_size=sz.stream_batch, connection=env.connection,
                n_epochs=sz.stream_windows, output="jax", metrics=metrics,
                sharding=sharding, distribute=distribute,
            )
            ring_kind.append(type(env.connection.rings[0]).__name__)
            for win in loader.windows(lookahead=sz.lookahead):
                if sharding is not None:
                    check(
                        win.sharding.is_equivalent_to(sharding, win.ndim),
                        f"window landed on {win.sharding}, not {sharding}",
                    )
                host = np.asarray(win)  # waits for the transfer: D2H
                seen.append((loader.last_window_key(), zlib.crc32(host)))
                loader.mark(Marker.END_OF_EPOCH)
        finally:
            wd.stop()
        return north_star_report(metrics)

    report = consume()
    check_producers_stayed_off_jax(status_dir, 2)
    return seen, report, [p.exitcode for p in procs], ring_kind[0]


def expected_stream_crcs(sz: Sizes, seed: int, keys) -> list:
    """Host-side regeneration of the served rows of each window."""
    served = sz.stream_rows // sz.stream_batch * sz.stream_batch
    buf = np.empty((sz.stream_rows, sz.stream_cols), np.float32)
    out = []
    for producer_idx, seq in keys:
        stream_window(seed, producer_idx, seq, buf)
        out.append(zlib.crc32(buf[:served]))
    return out


def check_stream_clean(sz: Sizes, seen, report, exitcodes, ring_kind) -> None:
    check(len(seen) == sz.stream_windows, f"{len(seen)} windows served")
    check(exitcodes == [0, 0], f"producer exit codes {exitcodes}")
    check(ring_kind == "NativeShmRing", f"ring in use is {ring_kind}")
    for name in (
        "corrupt_windows", "replays", "alias_fallbacks", "inline_fallbacks",
        "watchdog_failures", "respawns", "staging_retries", "ici_fallbacks",
    ):
        check(report[name] == 0, f"{name} = {report[name]}")


def ingest_leg(sz: Sizes, seed: int) -> None:
    t0 = time.perf_counter()
    seen, report, exitcodes, ring_kind = stream_windows(sz, seed)
    wall = time.perf_counter() - t0
    check_stream_clean(sz, seen, report, exitcodes, ring_kind)
    keys = [k for k, _ in seen]
    check(
        sorted(keys) == sorted(
            (p, i) for p in (1, 2) for i in range(sz.stream_windows // 2)
        ),
        f"window identities {keys}",
    )
    want = expected_stream_crcs(sz, seed, keys)
    bad = [k for (k, got), w in zip(seen, want) if got != w]
    check(not bad, f"windows {bad} differ from their host regeneration")
    mib = sz.stream_rows * sz.stream_cols * 4 / 2**20
    say(
        f"ingest: {len(seen)} x {mib:.0f} MiB windows CRC-clean from 2 "
        f"process producers over {ring_kind}; alias_windows="
        f"{report['alias_windows']:.0f} alias_fallbacks=0 "
        f"inline_fallbacks=0 corrupt_windows=0 watchdog_failures=0; "
        f"producers exited {exitcodes}; {wall:.1f} s incl. spawn, CRC "
        "and read-back (not a rate)"
    )


# -- the model ----------------------------------------------------------------


def llama_config(sz: Sizes, n_layers: int):
    import jax.numpy as jnp

    from ddl_tpu.config import TrainConfig
    from ddl_tpu.models import llama

    return TrainConfig(remat="selective").model_config(llama.LlamaConfig(
        vocab=sz.vocab, d_model=sz.d_model, n_layers=n_layers,
        n_heads=sz.n_heads, n_kv_heads=sz.n_kv_heads, d_ff=sz.d_ff,
        max_seq=sz.seq, param_dtype=jnp.bfloat16, attn_impl="flash",
    ))


def host_init_params(cfg, seed: int):
    """Seeded random weights, initialised on the device and brought to
    the HOST: the Trainer keeps its ``init_params`` for its whole life,
    and a device-resident copy would cost an HBM-filling model a second
    set of weights."""
    import jax

    from ddl_tpu.models import llama

    params = jax.device_get(llama.init_params(cfg, jax.random.key(seed)))
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    return params, n


def loss_for(cfg, mesh):
    """The train loss over the loader's batch tuple.  One chip: plain
    attention (no shard_map); a mesh: batch/head-sharded local
    attention over it."""
    from ddl_tpu.models import llama

    attn_mesh = mesh if mesh.devices.size > 1 else None
    return lambda p, b: llama.next_token_loss(p, b[0], cfg, mesh=attn_mesh)


def make_trainer(cfg, mesh, params, metrics, **kw):
    import optax

    from ddl_tpu.models import llama
    from ddl_tpu.trainer import Trainer

    return Trainer(
        loss_fn=loss_for(cfg, mesh),
        optimizer=optax.adamw(3e-4),
        mesh=mesh,
        param_specs=llama.param_specs(cfg),
        init_params=params,
        metrics=metrics,
        **kw,
    )


def check_flash_is_mosaic(cfg, mesh, sz: Sizes) -> int:
    """The loss the Trainer differentiates, lowered for this backend:
    on a TPU ``attn_impl="flash"`` must be the Mosaic kernel (a
    ``tpu_custom_call``), never interpret mode or a dense stand-in."""
    import jax

    from ddl_tpu.models import llama

    tokens = jax.ShapeDtypeStruct((sz.batch, sz.seq), np.int32)
    text = jax.jit(jax.grad(loss_for(cfg, mesh))).lower(
        llama.param_shapes(cfg), (tokens,)
    ).as_text()
    n = text.count("tpu_custom_call")
    if on_tpu():  # the CPU rehearsal runs the kernel in interpret mode
        check(n > 0, "no tpu_custom_call in the lowered train step")
    return n


def fit_windows(trainer, sz: Sizes, seed: int, n_windows: int, **kw):
    status_dir = tempfile.mkdtemp(prefix="ddl_smoke_status_")
    res = trainer.fit(
        TokenProducer(
            seed, status_dir, sz.steps_per_window * sz.batch, sz.seq,
            sz.vocab,
        ),
        batch_size=sz.batch, n_epochs=n_windows, n_producers=2,
        mode="process", output="jax", window_stream=True, **kw,
    )
    check_producers_stayed_off_jax(status_dir, 2)
    check(
        all(math.isfinite(v) for v in res.losses),
        f"non-finite losses {res.losses}",
    )
    return res


def hbm_line(label: str) -> None:
    import jax

    for d in jax.devices():
        stats = d.memory_stats() or {}
        say(
            f"hbm {label}: device {d.id} peak "
            f"{_gib(stats.get('peak_bytes_in_use'))} in use "
            f"{_gib(stats.get('bytes_in_use'))} of "
            f"{_gib(stats.get('bytes_limit'))}"
        )


def check_hbm_spread(label: str, stat: str) -> None:
    """Code that has only ever seen one chip may have put everything on
    the first: the chips' ``stat`` must lie within 20% of each other."""
    import jax

    if not on_tpu():  # the rehearsal's CPU client reports no memory stats
        return
    vals = [(d.memory_stats() or {}).get(stat) for d in jax.devices()]
    check(
        None not in vals and min(vals) >= 0.8 * max(vals),
        f"HBM {label} not within 20% across chips: {vals}",
    )
    say(f"hbm {label}: {[_gib(v) for v in vals]} — within 20%")


def _gib(n) -> str:
    return "n/a" if n is None else f"{n / 2**30:.2f} GiB"


def host_line(label: str) -> None:
    """Host memory beside the phase lines: an HBM-filling state is held
    on the host several times over while it is checkpointed."""
    import resource

    avail = "?"
    with open("/proc/meminfo") as f:
        for line in f:
            if line.startswith("MemAvailable:"):
                avail = f"{int(line.split()[1]) / 2**20:.1f} GiB"
    peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2**20
    say(f"host {label}: peak RSS {peak:.1f} GiB, MemAvailable {avail}")


def checkpoint_dir() -> str:
    """A fresh directory for the one generation (gigabytes at the real
    size)."""
    path = tempfile.mkdtemp(prefix="ddl_smoke_ckpt_")
    free = os.statvfs(path)
    say(
        f"checkpoint dir {path}: "
        f"{free.f_bavail * free.f_frsize / 2**30:.0f} GiB free"
    )
    return path


def train_leg(sz: Sizes, seed: int, compiles: CompileLog) -> None:
    import shutil

    import jax

    from ddl_tpu.ingest import north_star_report
    from ddl_tpu.observability import Metrics
    from ddl_tpu.parallel.mesh import make_mesh
    from ddl_tpu.resilience import list_generations

    cfg = llama_config(sz, sz.n_layers)
    mesh = make_mesh({"dp": 1}, devices=jax.devices()[:1])
    params, n_params = host_init_params(cfg, seed)
    say(
        f"train: Llama {n_params / 1e9:.3f} B params (vocab {sz.vocab}, "
        f"d_model {sz.d_model}, {sz.n_layers} layers, {sz.n_heads}/"
        f"{sz.n_kv_heads} heads x {cfg.head_dim}, d_ff {sz.d_ff}, bf16, "
        f"remat selective, batch {sz.batch} x seq {sz.seq}, flash)"
    )
    n_kernels = check_flash_is_mosaic(cfg, mesh, sz)
    ckpt_dir = checkpoint_dir()
    spw, n_win = sz.steps_per_window, sz.train_windows
    # One generation, saved after the LAST BUT ONE window: the async
    # writer then works under the last window's steps, and the resumed
    # run repeats a window whose loss is known.
    saved_at = n_win - 1

    def first_run():
        trainer = make_trainer(
            cfg, mesh, params, Metrics(), checkpoint_dir=ckpt_dir,
            checkpoint_every_epochs=saved_at, checkpoint_keep=1,
        )
        t0 = time.perf_counter()
        res = fit_windows(trainer, sz, seed, n_win)
        wall = time.perf_counter() - t0
        report = north_star_report(res.metrics)
        check(len(res.losses) == n_win, f"losses {res.losses}")
        check(res.state.step == n_win * spw, f"state.step {res.state.step}")
        first, uniform = res.losses[0], math.log(sz.vocab)
        check(
            abs(first - uniform) <= 0.10 * uniform,
            f"first window's mean loss {first:.4f} is not within 10% of "
            f"ln({sz.vocab}) = {uniform:.4f}",
        )
        check(
            report["fused_windows"] == n_win,
            f"fused_windows {report['fused_windows']}",
        )
        check("window_wait_s" in report, "report lacks window_wait_s")
        for name in (
            "corrupt_windows", "alias_fallbacks", "inline_fallbacks",
            "watchdog_failures", "respawns",
        ):
            check(report[name] == 0, f"{name} = {report[name]}")
        say(
            f"train: {n_win} windows x {spw} steps = {res.state.step} "
            f"steps, window losses {[round(v, 4) for v in res.losses]} "
            f"(ln vocab {uniform:.4f}); tpu_custom_call x{n_kernels} in "
            f"the lowered loss gradient; fused_windows="
            f"{report['fused_windows']:.0f} window_wait_s="
            f"{report['window_wait_s']:.3f} ingest_overlap_s="
            f"{report['ingest_overlap_s']:.3f} ckpt_submit_s="
            f"{report['resilience_ckpt_submit_s']:.2f}; {wall:.1f} s incl. "
            "compile and spawn (not a rate)"
        )
        return res.losses

    try:
        losses = first_run()
        # The first trainer and its pooled checkpoint staging buffers
        # (one more host copy of the state) go before the resume reads
        # the generation back: its closures keep it in a reference cycle.
        gc.collect()
        compiles.report("train")
        hbm_line("after train")
        host_line("after train")
        gens = list_generations(ckpt_dir)
        check(
            [g[0] for g in gens] == [saved_at * spw],
            f"checkpoint generations on disk: {gens}",
        )
        say(
            f"checkpoint: generation step {gens[0][0]} (after window "
            f"{saved_at}), {os.path.getsize(gens[0][1]) / 2**30:.2f} GiB "
            "on disk"
        )

        trainer = make_trainer(
            cfg, mesh, params, Metrics(), checkpoint_dir=ckpt_dir,
            checkpoint_every_epochs=10**9,
        )
        t0 = time.perf_counter()
        res = fit_windows(trainer, sz, seed, n_win)
        wall = time.perf_counter() - t0
        check(
            res.resumed_from_epoch == saved_at,
            f"resumed from window {res.resumed_from_epoch}",
        )
        check(
            res.state.step == n_win * spw,
            f"state.step after resume {res.state.step}",
        )
        check(
            res.metrics.counter("resilience.ckpt_restores") == 1
            and res.metrics.counter("resilience.ckpt_quarantined") == 0,
            "the resume did not restore the one verified generation",
        )
        check(
            res.losses == losses[saved_at:],
            f"resumed window's loss {res.losses} is not the first run's "
            f"{losses[saved_at:]}",
        )
        say(
            f"resume: fresh Trainer restored step {gens[0][0]}, took window "
            f"{n_win}: state.step {res.state.step}, loss {res.losses[0]:.6f}"
            f" == the first run's {losses[-1]:.6f}; {wall:.1f} s incl. "
            "restore and spawn"
        )
        compiles.report("resume")
        hbm_line("after resume")
        host_line("after resume")
    finally:
        shutil.rmtree(ckpt_dir, ignore_errors=True)


# -- four chips ---------------------------------------------------------------


def fanout_leg(sz: Sizes, seed: int, mesh) -> None:
    """64 MiB windows through the ICI tier against the XLA scatter, for
    a partial split over each mesh axis (scatter kernel + gather leg;
    the fsdp split rides a ring that is NOT in device order) and for
    full replication (broadcast kernel): identical bytes, zero
    fallbacks, every window fused over the two landing slots."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    for label, spec in (
        ("shard dp", P(None, "dp")), ("shard fsdp", P(None, "fsdp")),
        ("replicate", P()),
    ):
        sharding = NamedSharding(mesh, spec)
        runs = {}
        for distribute in ("ici", "xla"):
            seen, report, exitcodes, ring_kind = stream_windows(
                sz, seed, sharding=sharding, distribute=distribute
            )
            check_stream_clean(sz, seen, report, exitcodes, ring_kind)
            runs[distribute] = (seen, report)
        (ici_seen, ici), (xla_seen, xla) = runs["ici"], runs["xla"]
        check(ici_seen == xla_seen, f"{label}: ici and xla windows differ")
        want = expected_stream_crcs(sz, seed, [k for k, _ in ici_seen])
        check(
            [c for _, c in ici_seen] == want,
            f"{label}: windows differ from their host regeneration",
        )
        check(
            ici["ici_windows"] == sz.stream_windows
            and ici["ici_fallbacks"] == 0,
            f"{label}: ici_windows {ici['ici_windows']} "
            f"fallbacks {ici['ici_fallbacks']}",
        )
        check(xla["ici_windows"] == 0, f"{label}: xla run rode the ici tier")
        say(
            f"fanout {label}: {sz.stream_windows} windows ici == xla == "
            f"host regeneration; ici_windows={ici['ici_windows']:.0f} "
            f"ici_fallbacks=0 ici_bytes={ici['ici_bytes']:.0f}"
        )


def train_pair(sz: Sizes, seed: int, mesh, compiles: CompileLog) -> None:
    from ddl_tpu.observability import Metrics

    cfg = llama_config(sz, sz.mc_layers)
    params, n_params = host_init_params(cfg, seed)
    say(
        f"train x4: Llama {n_params / 1e9:.3f} B params, {sz.mc_layers} "
        f"layers (depth cut, widths as on one chip), mesh "
        f"{dict(mesh.shape)}"
    )
    n_kernels = check_flash_is_mosaic(cfg, mesh, sz)
    say(f"train x4: tpu_custom_call x{n_kernels} in the lowered loss gradient")
    fsdp = mesh.shape["fsdp"]
    runs = {}
    for distribute in ("ici", "xla"):
        crcs: list = []

        def hook(win, crcs=crcs):
            crcs.append(zlib.crc32(np.asarray(win)))
            return win

        trainer = make_trainer(cfg, mesh, params, Metrics())
        res = fit_windows(
            trainer, sz, seed, sz.mc_windows, window_hook=hook,
            loader_kwargs={"distribute": distribute},
        )
        m = res.metrics
        check(len(crcs) == sz.mc_windows, f"{len(crcs)} windows hooked")
        check(m.counter("ici.fallbacks") == 0, "ici.fallbacks != 0")
        check(m.counter("watchdog.failures") == 0, "watchdog.failures != 0")
        if distribute == "ici":
            check(
                m.counter("ici.windows") == sz.mc_windows
                and m.counter("ici.fused_windows") == sz.mc_windows,
                f"ici.windows {m.counter('ici.windows')} fused "
                f"{m.counter('ici.fused_windows')} of {sz.mc_windows}",
            )
            # Placement really spread: an fsdp-sharded leaf has a shard
            # on every chip, each 1/fsdp of the leaf.
            leaf = res.state.params["layers"][0]["wq"]
            shards = leaf.addressable_shards
            check(
                len({s.device for s in shards}) == mesh.devices.size,
                f"wq lives on {len({s.device for s in shards})} devices",
            )
            check(
                all(s.data.nbytes * fsdp == leaf.nbytes for s in shards),
                f"wq shard bytes {[s.data.nbytes for s in shards]} of "
                f"{leaf.nbytes} over fsdp={fsdp}",
            )
            say(
                f"placement: wq {leaf.shape} on "
                f"{len({s.device for s in shards})} devices, "
                f"{shards[0].data.nbytes} B each = total/{fsdp}"
            )
            # ...and so does the whole state, while it is alive.
            check_hbm_spread("in use, train state alive", "bytes_in_use")
        else:
            check(m.counter("ici.windows") == 0, "xla run rode the ici tier")
        runs[distribute] = (res.losses, crcs)
        del trainer, res
        compiles.report(f"train x4 {distribute}")
    check(runs["ici"][1] == runs["xla"][1], "ici and xla window CRCs differ")
    check(
        runs["ici"][0] == runs["xla"][0],
        f"losses differ: ici {runs['ici'][0]} xla {runs['xla'][0]}",
    )
    say(
        f"train x4: {sz.mc_windows} windows x {sz.steps_per_window} steps, "
        f"ici == xla: window CRCs identical, losses bit-equal "
        f"{[round(v, 4) for v in runs['ici'][0]]}; ici.fallbacks=0 "
        f"ici.fused_windows={sz.mc_windows}"
    )
    hbm_line("after train x4")


def shuffle_leg(sz: Sizes, seed: int) -> None:
    """The device-side epoch exchange (Pallas ring) against the host
    exchange at one seed: post-exchange pools byte-identical."""
    from ddl_tpu.observability import Metrics
    from ddl_tpu.shuffle import (
        DeviceExchangeFabric,
        DeviceExchangeShuffler,
        Rendezvous,
        ThreadExchangeShuffler,
    )
    from ddl_tpu.types import Topology

    n, rows, cols = 4, sz.shuffle_rows, sz.stream_cols

    def run(make):
        pools = [
            np.random.default_rng([seed, 7, i]).random(
                (rows, cols), np.float32
            )
            for i in range(n)
        ]
        shufs = [make(i) for i in range(n)]
        errors: list = []

        def worker(i):
            try:
                for _ in range(sz.shuffle_rounds):
                    shufs[i].global_shuffle(pools[i])
            except BaseException as e:  # re-raised on the main thread
                errors.append(e)

        threads = [
            threading.Thread(target=worker, args=(i,)) for i in range(n)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(300)
        check(not any(t.is_alive() for t in threads), "exchange hung")
        if errors:
            raise errors[0]
        return pools, shufs

    def topo(i):
        return Topology(n_instances=n, instance_idx=i, n_producers=1)

    host_rdv, dev_rdv = Rendezvous(), Rendezvous()
    fabric = DeviceExchangeFabric(impl="ring")
    host_pools, _ = run(lambda i: ThreadExchangeShuffler(
        topo(i), 1, rows, rendezvous=host_rdv, seed=seed,
    ))

    def device_shuffler(i):
        sh = DeviceExchangeShuffler(
            topo(i), 1, rows, rendezvous=dev_rdv, fabric=fabric, seed=seed,
        )
        sh.metrics = Metrics()  # per-shuffler registry (the pusher's seam)
        return sh

    dev_pools, shufs = run(device_shuffler)
    for i in range(n):
        check(
            np.array_equal(host_pools[i], dev_pools[i]),
            f"instance {i}: device exchange differs from the host exchange",
        )
    for sh in shufs:
        snap = sh.metrics.snapshot()
        check(
            snap.get("shuffle.device_rounds", 0) == sz.shuffle_rounds
            and snap.get("shuffle.device_fallbacks", 0) == 0,
            f"device_rounds {snap.get('shuffle.device_rounds')} "
            f"fallbacks {snap.get('shuffle.device_fallbacks')}",
        )
    say(
        f"shuffle: {n} instances x {sz.shuffle_rounds} rounds of "
        f"{rows * cols * 4 / 2**20:.0f} MiB pools, device ring == host "
        f"exchange byte for byte; device_rounds={sz.shuffle_rounds} "
        "device_fallbacks=0"
    )


# -- drivers ------------------------------------------------------------------


def one_chip(sz: Sizes, seed: int, compiles: CompileLog) -> None:
    with phase("ingest"):
        ingest_leg(sz, seed)
        compiles.report("ingest")
    with phase("train"):
        train_leg(sz, seed, compiles)


def four_chips(sz: Sizes, seed: int, compiles: CompileLog) -> None:
    import jax

    from ddl_tpu.parallel.mesh import make_mesh

    check(len(jax.devices()) == 4, f"{len(jax.devices())} devices, not 4")
    mesh = make_mesh({"dp": 2, "fsdp": 2})
    with phase("fanout"):
        fanout_leg(sz, seed, mesh)
        compiles.report("fanout")
    with phase("train x4"):
        train_pair(sz, seed, mesh, compiles)
    with phase("shuffle"):
        shuffle_leg(sz, seed)
        compiles.report("shuffle")
    # Last, so that everything above is on the record either way.
    check_hbm_spread("peak, whole run", "peak_bytes_in_use")


def announce() -> dict:
    """Early lines: what this process runs on.  Returns the device
    block of the result line, as JAX reports it."""
    import importlib.metadata

    import jax
    import jaxlib

    from ddl_tpu.transport.shm_ring import native_available

    dev = jax.devices()[0]
    device = {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
    }
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "not installed"
    native = native_available()
    say(
        f"device: {device['platform']} {device['kind']} x{device['count']}; "
        f"jax {jax.__version__} jaxlib {jaxlib.__version__} libtpu {libtpu}; "
        f"python {sys.version.split()[0]}; native ring available={native}; "
        f"compile cache {jax.config.jax_compilation_cache_dir}"
    )
    check(native, "the native shm ring did not build or load")
    return device


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from ddl_tpu.bringup import bring_up

    bring_up()  # a TPU, or exit non-zero with the reason: nothing ran
    t0 = time.perf_counter()
    compiles = CompileLog()
    device = announce()
    check(
        device["count"] == args.chips,
        f"--chips {args.chips} on a machine with {device['count']}",
    )
    (one_chip if args.chips == 1 else four_chips)(Sizes(), args.seed, compiles)
    say(
        f"total: {time.perf_counter() - t0:.1f} s wall, "
        f"{compiles.total():.1f} s compiling, "
        f"{compiles.cache_hits} compile-cache hits"
    )
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
