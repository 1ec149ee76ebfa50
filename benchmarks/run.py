#!/usr/bin/env python3
"""The benchmark's runner: one cell, one process, one result line.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Trains the cell's configuration with the loader live — PROCESS producers
-> shm window rings -> staging -> H2D -> (on a mesh) on-mesh distribution
-> ``Trainer.fit(window_stream=True)`` — and prints, as the last line of
its standard output, the JSON object ``BENCHMARK.json``'s contract asks
for.  Without a TPU, or with fewer chips than the cell asks for, it
exits non-zero and prints no result; ``--rehearsal cpu`` asks for the CPU
by name, runs the same control flow at the tiny sizes the cell's files
keep under ``rehearsal``, and prints no metric.

Phases (everything before the first timed window is ``setup_s``):

1. bring-up (``ddl_tpu.bringup``), weights initialised on the device in
   one jitted call from the seed, the link ceiling measured once;
2. the plain reference: two steps of ``jit(value_and_grad)`` on the
   host-regenerated first window, then freed;
3. a warm-up ``Trainer.fit`` of a few windows: compiles every program
   the cell uses and gives the window period;
4. the measured ``Trainer.fit``: ``n_epochs`` is set from the period so
   that the steady part lasts ``--seconds``.  The benchmark's
   ``window_hook`` stamps each window and sums its rows on the device.
   Dispatch may lead the device by the stream's lookahead, so the first
   and last ``lookahead + 1`` windows are outside the steady part.  With
   ``--trace 1`` the last windows of the steady part run under the
   profiler;
5. checks (``correct``), reduction, the result line.

JAX is touched only under ``main()``: spawn re-imports this script in
every producer, and producers stay off the device.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

#: The Trainer's stream defaults, named here because the steady part is
#: cut by them: ``fit(stream_lookahead=1)``, two ring slots a producer.
LOOKAHEAD = 1
EDGE_WINDOWS = LOOKAHEAD + 1
MIN_STEADY_WINDOWS = 3
#: A fit's first windows are not its pace: on one chip they were filled
#: before it asked and meet an empty device queue, so early stamps come
#: fast (0.33 s against 0.346 s in the ViT cell); on the mesh the first six
#: take 0.6 s against 0.207 s (my chip runs, PR 22).  The steady part
#: starts this long after the edge windows.
SETTLE_SECONDS = 1.5

#: Counters that must read zero over the whole run.
ZERO_COUNTERS = (
    "ici.fallbacks", "staging.inline_fallbacks", "staging.alias_fallbacks",
    "staging.retries", "watchdog.failures", "watchdog.respawns",
)
ZERO_PREFIXES = ("integrity.",)


def say(tag: str, **fields) -> None:
    """An earlier line of the output: one JSON object, tagged."""
    from benchmarks.lib import hostproc

    at = round(hostproc.seconds_since_process_start(), 2)
    print(json.dumps({"line": tag, "at_s": at, **fields}), flush=True)


class CompileLog:
    """When each backend compile (or compile-cache load) happened, from
    JAX's own monitoring events."""

    def __init__(self):
        import jax.monitoring

        self.events: list = []  # (monotonic time, seconds, program)
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **kw):
        if event.endswith("backend_compile_duration"):
            self.events.append(
                (time.monotonic(), float(secs), str(kw.get("fun_name", "?")))
            )

    def _event(self, event, **kw):
        if event.endswith("compilation_cache/cache_hits"):
            self.cache_hits += 1

    def between(self, t0: float, t1: float) -> int:
        return sum(1 for t, _, _ in self.events if t0 <= t <= t1)

    def total_s(self) -> float:
        return sum(s for _, s, _ in self.events)

    def slow(self, at_least_s: float = 1.0) -> list:
        return [[name, round(s, 2)] for _, s, name in self.events if s >= at_least_s]


class WindowHook:
    """The benchmark's ``window_hook``: stamps each window on the host's
    monotonic clock, sums its rows on the device (read back after the
    run), and at the planned windows takes the counter snapshots and
    starts and stops the profiler.  Shape-preserving: returns the window
    it was given."""

    def __init__(self, metrics, plan: dict | None = None):
        import jax
        import jax.numpy as jnp

        self.metrics = metrics
        self.plan = plan or {}
        self.stamps: list = []
        self.sums: list = []
        self.marks: dict = {}
        self.children: list = []
        self.trace_dir: str | None = None
        self.tracing = False

        @jax.jit
        def row_checksums(win):
            # benchmarks/lib/producers.py:row_checksums, on the device.
            words = jax.lax.bitcast_convert_type(win, jnp.uint32)
            words = words.reshape(words.shape[0] * words.shape[1], -1)
            weights = (
                jnp.arange(words.shape[1], dtype=jnp.uint32) * jnp.uint32(2)
                + jnp.uint32(1)
            )
            return jnp.sum(words * weights, axis=1, dtype=jnp.uint32)

        self._row_checksums = row_checksums

    def _mark(self, name: str) -> None:
        from benchmarks.lib import hostproc

        self.marks[name] = {
            "t": time.monotonic(),
            "counters": self.metrics.snapshot(),
            "cpu": hostproc.tree_cpu_seconds(),
        }

    def stop_trace(self) -> None:
        if self.tracing:
            import jax

            jax.profiler.stop_trace()
            self.tracing = False

    def __call__(self, win):
        import jax

        with jax.profiler.TraceAnnotation("bench.window_hook"):
            i = len(self.stamps)
            self.stamps.append(time.monotonic())
            if i == 0:
                import multiprocessing

                self.children = multiprocessing.active_children()
            if i == self.plan.get("steady_first"):
                self._mark("start")
            if i == self.plan.get("steady_end"):
                self._mark("end")
                self.stop_trace()
            if self.trace_dir and i == self.plan.get("trace_first"):
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 2
                jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
                self.tracing = True
            self.sums.append(self._row_checksums(win))
            return win


def read_fills(status_dir: str, n_producers: int) -> list:
    """(producer, iteration, start, end) of every fill, monotonic clock."""
    out = []
    for idx in range(1, n_producers + 1):
        path = os.path.join(status_dir, f"fills_{idx}.txt")
        if not os.path.exists(path):
            continue
        with open(path) as f:
            for line in f:
                it, t0, t1 = line.split()
                out.append((idx, int(it), float(t0), float(t1)))
    return out


def producer_problems(status_dir: str, n_producers: int, children) -> list:
    """Producers ran as other processes, exited 0, stayed off JAX."""
    problems = []
    pids = set()
    for idx in range(1, n_producers + 1):
        path = os.path.join(status_dir, f"producer_{idx}.json")
        if not os.path.exists(path):
            problems.append(f"producer {idx} left no status file")
            continue
        with open(path) as f:
            status = json.load(f)
        pids.add(status["pid"])
        if status["pid"] == os.getpid():
            problems.append(f"producer {idx} ran inside the consumer process")
        if status["backend_initialised"]:
            problems.append(f"producer {idx} initialised a JAX backend")
    codes = {p.pid: p.exitcode for p in children if p.pid in pids}
    if set(codes) != pids:
        problems.append(f"producers {sorted(pids - set(codes))} were not seen as children")
    if any(code != 0 for code in codes.values()):
        problems.append(f"producer exit codes {codes}")
    return problems


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument(
        "--rehearsal", choices=("cpu",), default=None,
        help="run the control flow on the CPU at the cell's tiny sizes; "
        "prints no metric",
    )
    ap.add_argument(
        "--fault", choices=("alter-row", "swap-rows"), default=None,
        help="rehearsal only: a deliberate defect that must read correct=false",
    )
    ap.add_argument(
        "--keep-trace", metavar="DIR", default=None,
        help="with --trace 1: copy the profiler's .xplane.pb into DIR, to "
        "look at by hand",
    )
    args = ap.parse_args(argv)
    if args.fault and not args.rehearsal:
        ap.error("--fault is for the rehearsal: measured runs carry no defect")
    return args


def reference_loss(cell, seed, sizes, mesh, batch_spec, loss_fn, optimizer,
                   params_dev):
    """Mean loss of the plain loop over the host-regenerated first window
    (``params_dev`` is consumed), and the seconds its last step took."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from benchmarks.lib import producers, reference

    mix = cell.mix
    splits = producers.geometry(mix, sizes).splits
    first = producers.host_window(mix, sizes, seed, 1, 0).reshape(
        mix["window_rows"] // mix["batch_rows"], mix["batch_rows"], -1
    )
    batch_sh = NamedSharding(mesh, batch_spec)
    batches = []
    for step in first:
        cols, off = [], 0
        for w in splits:
            cols.append(jax.device_put(step[:, off : off + w], batch_sh))
            off += w
        batches.append(tuple(cols))
    replicated = NamedSharding(mesh, P())
    losses, last_step_s = reference.first_window_losses(
        loss_fn, optimizer, jax.device_put(params_dev, replicated), batches,
        replicated,
    )
    return sum(losses) / len(losses), losses, last_step_s


def check_fit(tag, hook, n, status_dir, losses, cell, seed, sizes, n_producers,
              ref_loss):
    """(problems, windows failed) of one fit: checksums in order against
    the host's regeneration, producers, losses."""
    import numpy as np

    from benchmarks.lib import producers

    problems = []
    want = producers.expected_checksums(
        cell.mix, sizes, seed, n_producers, n, status_dir
    )
    got = [np.asarray(s) for s in hook.sums]
    bad = [
        i for i in range(n)
        if i >= len(got) or not np.array_equal(got[i], want[i])
    ]
    if bad:
        problems.append(
            f"windows {bad[:8]} of {n} differ from the host's regeneration"
        )
    problems += producer_problems(status_dir, n_producers, hook.children)
    if len(losses) != n or not all(math.isfinite(v) for v in losses):
        problems.append(f"losses {losses[:4]}... are not {n} finite values")
    tol = cell.config["loss_tolerance"]["relative"]
    if losses and abs(losses[0] - ref_loss) > tol * abs(ref_loss):
        problems.append(
            f"first-window loss {losses[0]!r} is not the reference's "
            f"{ref_loss!r} within {tol}"
        )
    return [f"{tag} fit: {p}" for p in problems], len(bad)


def reduce_trace(hook, plan, samples_per_window, keep_dir):
    """The profiler's file reduced (``None`` without one), and the
    ``trace`` line: the host's stamps against the device's program
    starts, the rate inside and outside the traced part."""
    import numpy as np

    from benchmarks.lib import tracered

    trace_file = tracered.find_trace_file(hook.trace_dir)
    if not trace_file:
        return None
    if keep_dir:
        os.makedirs(keep_dir, exist_ok=True)
        shutil.copy(trace_file, keep_dir)
    reduced = tracered.reduce(tracered.load(trace_file))
    first, end = plan["trace_first"], plan["steady_end"]
    stamps = hook.stamps
    starts = next(iter(reduced["step_program_starts"].values()), [])
    say(
        "trace", file_bytes=os.path.getsize(trace_file),
        traced_windows=end - first,
        host_period_s=float(np.median(np.diff(stamps[first : end + 1]))),
        device_period_s=(
            float(np.median(np.diff(starts))) if len(starts) > 1 else None
        ),
        reduced_window_s=reduced["window_s"],
        ops_own_time_s=reduced["ops_own_time_s"],
        idle_share_by_chip=reduced["idle_share_by_chip"],
        longest_gap_s=reduced["longest_gap_s"],
        rate_untraced_part=(
            (first - plan["steady_first"]) * samples_per_window
            / (stamps[first] - stamps[plan["steady_first"]])
            if first > plan["steady_first"] else None
        ),
        rate_traced_part=(
            (end - first) * samples_per_window / (stamps[end] - stamps[first])
        ),
    )
    return reduced


def main(argv=None) -> int:
    args = parse_args(argv)

    from benchmarks.lib import cells, hostproc, producers

    cell = cells.load_cell(args.workload, rehearsal=bool(args.rehearsal))
    if args.rehearsal:
        # Virtual CPU devices for a mesh; must precede JAX's import.
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={cell.chips}"
        )

    from ddl_tpu.bringup import bring_up

    platform = bring_up(args.rehearsal)  # a TPU, or exit non-zero: nothing ran
    on_tpu = platform == "tpu"
    import jax
    import numpy as np
    import optax
    from jax.sharding import PartitionSpec as P

    if on_tpu:
        # Every program, however quick to compile, goes to the persistent
        # cache: a second run in the same checkout compiles nothing.
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    from benchmarks.lib import peaks
    from ddl_tpu.ingest import measure_h2d_bandwidth
    from ddl_tpu.observability import Metrics
    from ddl_tpu.parallel.mesh import make_mesh
    from ddl_tpu.trainer import Trainer

    compiles = CompileLog()
    all_devices = jax.devices()
    if len(all_devices) < cell.chips:
        raise SystemExit(
            f"cell {cell.name} needs {cell.chips} chips and JAX found "
            f"{len(all_devices)}"
        )
    devices = all_devices[: cell.chips]
    device = {
        "platform": all_devices[0].platform,
        "kind": all_devices[0].device_kind,
        "count": len(all_devices),
    }
    c, mix, family = cell.config, cell.mix, cell.family
    n_producers = mix["n_producers"]
    if (os.cpu_count() or 1) < 6 and "n_producers_under_6_cores" in mix:
        n_producers = mix["n_producers_under_6_cores"]
    steps_per_window = mix["window_rows"] // mix["batch_rows"]
    samples_per_window = mix["window_rows"] * family.samples_per_row(c, mix)
    say(
        "start", cell=cell.name, seed=args.seed, seconds=args.seconds,
        trace=args.trace, rehearsal=bool(args.rehearsal), device=device,
        host_cores=os.cpu_count(), n_producers=n_producers,
        compile_cache=jax.config.jax_compilation_cache_dir,
    )

    # -- 1. the model, on the device, from the seed ---------------------------
    sizes = family.sizes(c, mix)
    cfg = family.model_config(c, mix)
    mesh = make_mesh(dict(mix["mesh"]), devices=devices)
    batch_spec = P(("dp",))
    loss_fn = family.loss_fn(cfg, mesh)
    t = c["training"]
    if t["optimizer"] != "adamw":
        raise SystemExit(f"optimizer {t['optimizer']!r}: only adamw is wired")
    optimizer = optax.adamw(t["learning_rate"])
    params_dev = jax.jit(lambda key: family.init_params(cfg, key))(
        jax.random.key(args.seed)
    )
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params_dev))
    # The Trainer keeps its init_params for life: hand it host arrays, or
    # an HBM-filling model pays for a second set of weights.
    params_host = jax.device_get(params_dev)
    link_bytes_per_s = measure_h2d_bandwidth(device=devices[0])
    say("weights", params=n_params, link_bytes_per_s=link_bytes_per_s)

    # -- 2. the plain reference on the first window ----------------------------
    ref_loss, ref_losses, ref_step_s = reference_loss(
        cell, args.seed, sizes, mesh, batch_spec, loss_fn, optimizer, params_dev
    )
    del params_dev
    say(
        "reference", first_window_losses=ref_losses, last_step_s=ref_step_s,
        compile_s_so_far=compiles.total_s(),
    )

    # -- 3./4. the Trainer: a warm-up fit, then the measured one ---------------
    metrics = Metrics()
    trainer = Trainer(
        loss_fn=loss_fn, optimizer=optimizer, mesh=mesh,
        param_specs=family.param_specs(cfg), init_params=params_host,
        batch_spec=batch_spec, metrics=metrics,
    )
    scratch = tempfile.mkdtemp(prefix="ddl_bench_")

    def fit(n_windows: int, hook: WindowHook, tag: str, fault=None):
        status_dir = os.path.join(scratch, tag)
        os.makedirs(status_dir)
        res = trainer.fit(
            producers.make_producer(mix, sizes, args.seed, status_dir, fault),
            batch_size=mix["batch_rows"], n_epochs=n_windows,
            n_producers=n_producers, mode="process", output="jax",
            window_stream=True, window_hook=hook, stream_lookahead=LOOKAHEAD,
        )
        losses = list(res.losses)
        del res  # the final train state: HBM the next fit needs
        return losses, status_dir

    try:
        warm_hook = WindowHook(metrics)
        warm_n = max(mix["warmup_windows"], EDGE_WINDOWS + 2)
        warm_losses, warm_dir = fit(warm_n, warm_hook, "warm")
        # The last periods: the first windows were filled before the fit
        # asked for them and say nothing of a pace the input sets.
        period = float(np.median(np.diff(warm_hook.stamps)[EDGE_WINDOWS:][-3:]))
        n_steady = max(MIN_STEADY_WINDOWS, math.ceil(args.seconds / period))
        head = EDGE_WINDOWS + math.ceil(SETTLE_SECONDS / period)
        n_main = head + n_steady + EDGE_WINDOWS
        plan = {"steady_first": head, "steady_end": head + n_steady}
        hook = WindowHook(metrics, plan)
        if args.trace:
            traced = min(
                n_steady - 1, math.ceil(mix["trace_seconds"] / period) + 1
            )
            plan["trace_first"] = plan["steady_end"] - max(1, traced)
            hook.trace_dir = os.path.join(scratch, "trace")
        say(
            "warmup", windows=warm_n, period_s=period, steady_windows=n_steady,
            main_windows=n_main, compile_s_so_far=compiles.total_s(),
            compile_cache_hits=compiles.cache_hits,
        )
        try:
            main_losses, main_dir = fit(n_main, hook, "main", args.fault)
        finally:
            hook.stop_trace()
        t_start, t_end = hook.marks["start"]["t"], hook.marks["end"]["t"]
        setup_s = hostproc.seconds_since_process_start() - (
            time.monotonic() - t_start
        )

        # -- 5. checks ---------------------------------------------------------
        problems, failed = [], 0
        for fit_args in (
            ("warm", warm_hook, warm_n, warm_dir, warm_losses),
            ("main", hook, n_main, main_dir, main_losses),
        ):
            found, bad = check_fit(
                *fit_args, cell, args.seed, sizes, n_producers, ref_loss
            )
            problems += found
            failed += bad
        for name, value in metrics.snapshot().items():
            if (name in ZERO_COUNTERS or name.startswith(ZERO_PREFIXES)) and value:
                problems.append(f"{name} = {value}")
        in_window = compiles.between(t_start, t_end)
        if in_window:
            problems.append(f"{in_window} programs compiled inside the timed window")

        # -- the numbers -------------------------------------------------------
        window_s = t_end - t_start
        rate = n_steady * samples_per_window / window_s
        periods = np.diff(hook.stamps[plan["steady_first"] : plan["steady_end"] + 1])
        # The runtime counts a program's temporaries (saved activations)
        # under bytes_reserved, not bytes_in_use: the peak is both.
        stats = [d.memory_stats() or {} for d in devices]
        peak_bytes = max(
            (s.get("peak_bytes_in_use") or 0) + (s.get("peak_bytes_reserved") or 0)
            for s in stats
        )
        flops_per_sample = family.flops_per_sample(c, mix)
        say(
            "steady", windows=n_steady, window_s=window_s,
            period_median_s=float(np.quantile(periods, 0.5)),
            period_p90_s=float(np.quantile(periods, 0.9)),
            period_max_s=float(periods.max()),
            period_min_s=float(periods.min()),
            first_periods_s=[round(float(d), 4) for d in np.diff(hook.stamps[:10])],
            rate=rate if on_tpu else "not measured",
            first_window_loss=main_losses[0], reference_loss=ref_loss,
            loss_rel_diff=abs(main_losses[0] - ref_loss) / abs(ref_loss),
            last_loss=main_losses[-1],
            compile_s=compiles.total_s(), compile_cache_hits=compiles.cache_hits,
            compiles_over_1s=compiles.slow(), memory_stats=stats[0],
            problems=problems,
        )
        device["memory_peak_bytes"] = peak_bytes if on_tpu else None
        result = {
            "correct": not problems,
            "attempted": warm_n + n_main,
            "failed": failed,
            "metrics": {},
            "device": device,
        }
        values: dict = {}
        if not args.trace:
            wanted = cell.end_to_end
            if on_tpu:
                values = {
                    family.RATE_METRIC: rate,
                    "mfu": 100.0 * flops_per_sample * rate
                    / (cell.chips * peaks.peak_flops(device["kind"])),
                    "setup_s": setup_s,
                }
        else:
            wanted = cell.per_layer
            reduced = None
            if on_tpu:  # a CPU trace holds no device plane to reduce
                reduced = reduce_trace(
                    hook, plan, samples_per_window, args.keep_trace
                )
            if reduced:
                device["busy_s"] = reduced["busy_s"]
                device["window_s"] = reduced["window_s"]
                result["breakdown"] = {
                    "device_ops": reduced["device_ops"],
                    "idle_gaps": reduced["idle_gaps"],
                }
            c0, c1 = hook.marks["start"]["counters"], hook.marks["end"]["counters"]
            cpu0, cpu1 = hook.marks["start"]["cpu"], hook.marks["end"]["cpu"]
            measured = {
                "cell": cell.name, "chips": cell.chips, "config": c, "mix": mix,
                "window_s": window_s, "n_windows": n_steady,
                "steps_per_window": steps_per_window,
                "counters": {k: v - c0.get(k, 0.0) for k, v in c1.items()},
                "fills": [
                    f for f in read_fills(main_dir, n_producers)
                    if t_start <= f[2] and f[3] <= t_end
                ],
                "cpu_s": sum(cpu1[p] - cpu0.get(p, 0.0) for p in cpu1),
                "link_bytes_per_s": link_bytes_per_s,
                "flops_per_step": flops_per_sample * samples_per_window
                / steps_per_window,
                "peak_flops": peaks.peak_flops(device["kind"]) if on_tpu else None,
                "memory_peak_bytes": peak_bytes,
                "trace": reduced,
            }
            for entry in wanted:
                value = cells.layer_reader(entry["name"])(measured)
                if value is not None:
                    values[entry["name"]] = value
        if on_tpu:
            result["metrics"] = {
                e["name"]: {"value": values[e["name"]], "unit": e["unit"]}
                for e in wanted if e["name"] in values
            }
        elif args.trace:
            # A CPU run prints no metric; it says which readers found data.
            say("rehearsal", readers_with_data=sorted(values))
    finally:
        shutil.rmtree(scratch, ignore_errors=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
