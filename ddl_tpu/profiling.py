"""Profiling integration: the data plane's stages on the profiler's timeline.

The reference's only introspection was the DEBUG call tracer
(``with_logging``, SURVEY §5.1), kept in ``ddl_tpu.utils``.  This adds the
TPU-native layer: ``jax.profiler`` device traces with named host
annotations, so ingest stalls and collective time show up on the TensorBoard
timeline next to the XLA ops.

Every host stage of the data plane is emitted through ONE call,
:func:`stage`, and named in ONE table, :data:`STAGES`: the
``jax.profiler.TraceAnnotation`` (the only span that shares a clock with
the device trace — ``benchmarks/lib/tracered.py`` attributes device idle
gaps to these names), the always-on ``Metrics`` timer, and the
``SpanLog`` stage of the operators' cross-process Perfetto export
(``ddl_tpu.obs.spans``).  JAX is imported on first use only: producer
processes import this package and must stay off JAX.

The same call feeds the START-UP record (:func:`startup_record`): what a
process did before its first step ran — backend bring-up, the producers'
pool, state placement, loader attach, and every program JAX traced,
lowered, compiled or loaded from its cache, each with the stage that
caused it (``docs/OBSERVABILITY.md``, "Start-up: stages and builds").
"""

from __future__ import annotations

import collections
import contextlib
import itertools
import threading
import time
from typing import Any, Dict, Iterator, List, NamedTuple, Optional, Tuple

from ddl_tpu.concurrency import named_lock
from ddl_tpu.obs import spans as obs_spans


class Stage(NamedTuple):
    """One stage's three names (``None``: the stage has no such emission)."""

    timer: Optional[str]  # Metrics timer
    span: Optional[str]  # SpanLog stage (``obs.STAGES``)
    where: str  # what the stage covers, for docs/OBSERVABILITY.md
    #: The start-up record's share: ``"span"`` — every emission is a row
    #: of it; the name of a fit's stamp — a fit's FIRST emission is a row
    #: and its end that stamp; ``None`` — the record never sees it.
    startup: Optional[str] = None
    #: The stage can cause a program's build: it notes itself on its
    #: thread, and a build inside it carries its name.
    builds: bool = False


#: Profiler annotation name -> the stage's timer and SpanLog stage.  Names
#: are frozen: the benchmark's readers, ``tracered.py`` and
#: ``north_star_report`` read them.  ``ddl.window_wait`` encloses acquire,
#: transfer wait, release wait and (inline path) put + fan-out; a reader
#: that takes the innermost span covering a gap needs nothing more.
STAGES: Dict[str, Stage] = {
    "ddl.window_wait": Stage(
        "trainer.window_wait", None,
        "trainer: the whole of `next(stream)` on the train loop's thread",
        startup="first_window",
    ),
    "ddl.window_acquire": Stage(
        "consumer.wait", "consumer.acquire",
        "loader: admission + ring wait + integrity verify of one window",
    ),
    "ddl.transfer_wait": Stage(
        "ingest.transfer_wait", None,
        "loader `finish()`: wait for the staged copy + dispatch of the "
        "window about to be yielded (a stolen job runs inside it)",
    ),
    "ddl.release_wait": Stage(
        "ingest.release_wait", None,
        "loader: forced slot release — blocks on an inline transfer "
        "because the ring ran out of free slots",
    ),
    "ddl.staging_copy": Stage(
        "ingest.stage_copy", "staging.copy",
        "staging executor: slot -> pinned staging buffer memcpy (+ CRC), "
        "retries included",
    ),
    "ddl.staging_transfer": Stage(
        None, "staging.transfer",
        "staging executor, on whichever thread claimed the job: the "
        "staged window's H2D dispatch, retries included (alias path: "
        "through completion — the ring slot is the live source)",
    ),
    "ddl.ingest_put_window": Stage(
        None, "ingest.transfer",
        "ingestor `put_window`: one whole-window H2D dispatch",
    ),
    "ddl.ingest_put": Stage(
        None, None,
        "ingestor `put` / `put_batch`: per-batch H2D dispatch",
    ),
    "ddl.ici_fanout": Stage(
        "ici.fanout", "ici.fanout",
        "ICI distributor: a window's dispatch toward its chips — lane pack "
        "+ ring-kernel launch on the anchor, or the direct route's one "
        "sharded put",
    ),
    "ddl.step_dispatch": Stage(
        "trainer.step_dispatch", "trainer.consume",
        "trainer: dispatch of the window's scanned steps (a recompile "
        "or a full dispatch queue shows here)",
        startup="first_dispatch_done", builds=True,
    ),
    "ddl.loss_readback": Stage(
        "trainer.loss_readback", None,
        "trainer: the host read-back of a window's mean loss (fused "
        "loop: the PREVIOUS window's, the loop's one host sync)",
    ),
    "ddl.bring_up": Stage(
        "startup.bring_up", None,
        "`bringup.bring_up`: `import jax`, compile-cache placement and "
        "salt, the first `jax.devices()` — to a usable backend (the "
        "recorded span starts at the function's entry; the annotation "
        "opens once JAX is imported)",
        startup="span",
    ),
    "ddl.pool_start": Stage(
        "startup.pool_start", None,
        "`distributed_dataloader`'s wrapper: topology, knob export, "
        "`WorkerSet(...)` (spawn, channels), up to the call of the "
        "user's main",
        startup="span",
    ),
    "ddl.state_init": Stage(
        "startup.state_init", None,
        "trainer `_restore_or_init`: parameters and optimizer state "
        "placed on the mesh (the `init` program), or the restore",
        startup="span", builds=True,
    ),
    "ddl.loader_attach": Stage(
        "startup.loader_attach", None,
        "trainer: `DistributedDataLoader(...)` construction — handshake, "
        "ring attach, staging set-up",
        startup="span",
    ),
    "ddl.pool_stop": Stage(
        "startup.pool_stop", None,
        "trainer: checkpoint flush + watchdog stop at a fit's end; then "
        "the wrapper's `finally`: `workers.abort()`, `workers.join()` "
        "(one row a fit in the start-up record)",
        startup="span",
    ),
}

_TraceAnnotation: Any = None


def _annotation(name: str):
    global _TraceAnnotation
    if _TraceAnnotation is None:
        import jax

        _TraceAnnotation = jax.profiler.TraceAnnotation
    return _TraceAnnotation(name)


@contextlib.contextmanager
def trace(log_dir: str) -> Iterator[None]:
    """Capture a jax.profiler trace for the enclosed block."""
    import jax

    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def annotate(name: str):
    """Named host span, visible on the profiler timeline (user code;
    the data plane's own stages go through :func:`stage`).

    Usage::

        with annotate("my.eval_pass"):
            evaluate(state)
    """
    return _annotation(name)


class stage:
    """Emit one data-plane stage: profiler annotation + ``Metrics`` timer
    + ``SpanLog`` span, over one extent, from one site.

    ::

        with stage("ddl.window_acquire", self.metrics) as st:
            slot = acquire(...)
            st.key = (producer_idx, seq)   # known only now

    ``name`` is a key of :data:`STAGES`.  ``metrics`` receives the
    stage's timer (skipped when ``None`` or the stage has none).
    ``key`` is the window identity ``(producer_idx, seq)`` for the
    SpanLog span; settable inside the block, defaulting to the thread's
    ``obs_spans.current_window()``.  Per-window use only (DDL023).
    With no profiler session the annotation is one atomic load; with no
    armed SpanLog nothing is recorded and no clock is read for it.
    ``started`` (a ``time.monotonic()`` stamp) back-dates the timer and
    the start-up record's row to work done before the annotation could
    open (``bring_up``: ``import jax``).  A stage whose row has a
    ``startup`` share also lands in :func:`startup_record`; past a fit's
    first window that costs the hot path two thread-local reads.
    """

    __slots__ = ("key", "_name", "_row", "_metrics", "_ann", "_t0", "_started")

    def __init__(
        self,
        name: str,
        metrics: Any = None,
        key: Optional[Tuple[Optional[int], Optional[int]]] = None,
        started: Optional[float] = None,
    ):
        self._name = name
        self._row = STAGES[name]
        self._metrics = metrics if self._row.timer else None
        self._ann = _annotation(name)
        self.key = key
        self._started = started

    def __enter__(self) -> "stage":
        self._ann.__enter__()
        started = self._started
        if self._row.startup is not None:
            self._started = _RECORD._stage_opened(
                self._name, self._row, self._metrics, started
            )
        if self._metrics is None:
            self._t0 = obs_spans.t0()
        else:
            self._t0 = time.perf_counter()
            if started is not None:
                self._t0 -= time.monotonic() - started
        return self

    def __exit__(self, *exc: object) -> None:
        row = self._row
        t0 = self._t0
        try:
            if self._metrics is not None:
                t1 = time.perf_counter()
                self._metrics.add_time(row.timer, t1 - t0)
            else:
                t1 = None
            # A span is a COMPLETED stage: a block that raised (a failed
            # lookahead probe, a retried copy) records none.  t0 == 0.0:
            # no log was armed when the stage began.
            if (
                row.span is not None and exc[0] is None and t0
                and obs_spans.armed()
            ):
                pidx, seq = self.key or obs_spans.current_window()
                obs_spans.record(row.span, pidx, seq, t0, t1)
            if row.startup is not None:
                _RECORD._stage_closed(self._name, row, self._started)
        finally:
            self._ann.__exit__(*exc)


# -- the start-up record -----------------------------------------------------

#: JAX's monitoring events that bracket a program's build -> the kind of
#: row.  Each is emitted by ``jax._src.dispatch.log_elapsed_time``: a
#: scalar event under the same name when the extent opens, the duration
#: when it closes, on the thread that does the work, with ``fun_name``.
BUILD_EVENTS: Dict[str, str] = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
#: Fired INSIDE a backend-compile extent, on its thread: the persistent
#: cache handed the executable back (the extent was a load), or the
#: compile's result was written to it.  With neither the cache was not
#: asked, or did not keep the result: a compile.
CACHE_EVENTS: Dict[str, str] = {
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}
BUILD_KINDS = ("trace", "lower", "compile", "cache_load")
#: A kind's ``Metrics`` timer, on the registry of the stage that caused
#: the build (frozen: the benchmark's ``steady_build_s`` reads them).
BUILD_TIMERS: Dict[str, str] = {
    "trace": "build.trace",
    "lower": "build.lower",
    "compile": "build.compile",
    "cache_load": "build.cache_load",
}
#: A fit's stamps, in the order they happen (``time.monotonic()``).
FIT_STAMPS = (
    "entered", "first_window", "first_dispatch_done", "last_readback",
    "returned",
)
#: The stamps the summary cuts the running sums at.
CUT_STAMPS = FIT_STAMPS[:3]
#: A compile this long is a program the persistent cache did not hold.
SLOW_COMPILE_S = 1.0
#: A nested extent shorter than this keeps no row (its seconds are its
#: parent's either way): one program's trace holds hundreds of `add`s.
NESTED_ROW_MIN_S = 1e-3


class SpanRow(NamedTuple):
    """One recorded stage: a stage emitted again in the same fit (the
    trainer's and the wrapper's ``ddl.pool_stop``) extends its row."""

    name: str
    fit: Optional[int]  # ordinal of the fit running on the thread
    start: float
    end: float
    seconds: float  # inside the stage (no more than end - start)


class BuildRow(NamedTuple):
    """One of JAX's build events."""

    id: int
    #: The outermost extent open on the thread when this one closed: a
    #: jitted helper traced inside a program's trace, a kernel body
    #: traced inside a lowering.  Its seconds are the parent's.
    parent: Optional[int]
    kind: str  # one of BUILD_KINDS
    program: str
    start: float
    end: float
    cause: Optional[str]  # the build-causing stage open on the thread
    fit: Optional[int]
    cache: Optional[str]  # "hit" | "miss" | None: the cache's verdict


class Totals(NamedTuple):
    """The running sums at one moment (outermost extents only)."""

    by_kind: Dict[str, float]
    by_cause: Dict[Optional[str], float]
    slow_compiles: int  # `compile` rows of SLOW_COMPILE_S or more

    def seconds(self) -> float:
        return sum(self.by_kind.values())


class FitRow:
    """One ``Trainer.fit``: its ordinal in the process, its stamps, and
    the running sums as each of ``CUT_STAMPS`` was taken."""

    __slots__ = ("ordinal", "totals") + FIT_STAMPS

    def __init__(self, ordinal: int):
        self.ordinal = ordinal
        self.totals: Dict[str, Totals] = {}
        for name in FIT_STAMPS:
            setattr(self, name, None)

    def as_dict(self) -> dict:
        return {
            "ordinal": self.ordinal,
            **{name: getattr(self, name) for name in FIT_STAMPS},
        }


class _ThreadState(threading.local):
    fit: Optional[FitRow] = None  # the fit running on this thread
    cause: Optional[Tuple[str, Any]] = None  # open stage that builds
    depth = 0  # build extents open on this thread
    outer: Optional[int] = None  # id of the outermost of them
    cache: Optional[str] = None  # verdict for the compile in progress


class StartupRecord:
    """What the process did before (and between) its steps: stage spans,
    fits, and JAX's builds, on ``time.monotonic()``.

    Bounded: the oldest rows fall off (an eager reference makes hundreds
    of small builds), and the running sums beside them keep every
    second — a dropped row loses its name, never its time.  The
    process's one instance is :func:`startup_record`; it is always kept
    (a few hundred rows), there is no switch.
    """

    def __init__(self, max_rows: int = 4096):
        self._lock = named_lock("obs.startup")
        self._tls = _ThreadState()
        self._ids = itertools.count()
        self.spans: collections.deque = collections.deque(maxlen=max_rows)
        self.fits: collections.deque = collections.deque(maxlen=max_rows)
        self.builds: collections.deque = collections.deque(maxlen=max_rows)
        self._n_fits = 0
        # The running sums: keyed by the tables above, so bounded.
        self._stage_s: Dict[str, float] = dict.fromkeys(STAGES, 0.0)
        self._stage_n: Dict[str, int] = dict.fromkeys(STAGES, 0)
        self._kind_s: Dict[str, float] = dict.fromkeys(BUILD_KINDS, 0.0)
        self._cause_s: Dict[Optional[str], float] = dict.fromkeys(
            [None, *(n for n, row in STAGES.items() if row.builds)], 0.0
        )
        self._slow_compiles = 0

    # -- fits ---------------------------------------------------------------

    def begin_fit(self) -> FitRow:
        """A fit starts on this thread: stamps ``entered``."""
        with self._lock:
            fit = FitRow(self._n_fits)
            self._n_fits += 1
            self.fits.append(fit)
        self._tls.fit = fit
        self._stamp(fit, "entered")
        return fit

    def end_fit(self, fit: FitRow) -> None:
        self._stamp(fit, "returned")
        self._tls.fit = None

    def stamp(self, name: str) -> None:
        """Stamp the fit running on this thread (none: nothing)."""
        fit = self._tls.fit
        if fit is not None:
            self._stamp(fit, name)

    def _stamp(self, fit: FitRow, name: str, at: float = 0.0) -> None:
        if name in CUT_STAMPS:
            fit.totals[name] = self.totals()
        setattr(fit, name, at or time.monotonic())

    def totals(self) -> Totals:
        with self._lock:
            return Totals(
                dict(self._kind_s), dict(self._cause_s), self._slow_compiles
            )

    # -- stages (``stage`` calls these) -------------------------------------

    def _stage_opened(
        self, name: str, row: Stage, metrics: Any, started: Optional[float]
    ) -> float:
        """The start of the row this emission will be (0.0: none)."""
        t = self._tls
        if row.builds:
            t.cause = (name, metrics)
        if row.startup != "span":
            fit = t.fit
            if fit is None or getattr(fit, row.startup) is not None:
                return 0.0
        return started or time.monotonic()

    def _stage_closed(self, name: str, row: Stage, start: float) -> None:
        t = self._tls
        if row.builds:
            t.cause = None
        if not start:
            return
        end = time.monotonic()
        fit = t.fit
        ordinal = None if fit is None else fit.ordinal
        with self._lock:
            self._stage_s[name] += end - start
            last = self.spans[-1] if self.spans else None
            if (
                last is not None and ordinal is not None
                and (last.name, last.fit) == (name, ordinal)
            ):
                self.spans[-1] = last._replace(
                    end=end, seconds=last.seconds + end - start
                )
            else:
                self._stage_n[name] += 1
                self.spans.append(
                    SpanRow(name, ordinal, start, end, end - start)
                )
        if row.startup != "span":
            self._stamp(fit, row.startup, end)

    # -- builds (the listener calls these) ----------------------------------

    def build_opened(self, event: str) -> None:
        if event in BUILD_EVENTS:
            t = self._tls
            if t.depth == 0:
                t.outer = next(self._ids)
            t.depth += 1
            if BUILD_EVENTS[event] == "compile":
                # A verdict an aborted compile left is not this one's.
                t.cache = None

    def cache_event(self, event: str) -> None:
        verdict = CACHE_EVENTS.get(event)
        if verdict is not None:
            self._tls.cache = verdict

    def build_closed(self, event: str, secs: float, program: str) -> None:
        kind = BUILD_EVENTS.get(event)
        if kind is None:
            return
        end = time.monotonic()
        t = self._tls
        verdict = None
        if kind == "compile":
            verdict, t.cache = t.cache, None
            if verdict == "hit":
                kind = "cache_load"
        # An extent opened before the listener was there closes at 0.
        t.depth = max(0, t.depth - 1)
        outermost = t.depth == 0
        if outermost:
            row_id = t.outer if t.outer is not None else next(self._ids)
            parent, t.outer = None, None
        else:
            row_id, parent = next(self._ids), t.outer
        cause, metrics = t.cause or (None, None)
        fit = t.fit
        with self._lock:
            if outermost or secs >= NESTED_ROW_MIN_S:
                self.builds.append(BuildRow(
                    row_id, parent, kind, program, end - secs, end, cause,
                    None if fit is None else fit.ordinal, verdict,
                ))
            if outermost:
                self._kind_s[kind] += secs
                self._cause_s[cause] += secs
                if kind == "compile" and secs >= SLOW_COMPILE_S:
                    self._slow_compiles += 1
        if outermost and metrics is not None:
            metrics.add_time(BUILD_TIMERS[kind], secs)

    # -- reading it ---------------------------------------------------------

    def summary(self, slowest: int = 5) -> dict:
        """Seconds by phase, and the slowest programs.

        SET-UP is everything up to the LAST fit's ``first_dispatch_done``
        (its step program is built and on its way); without a fit, up to
        now.  ``trace`` / ``lower`` / ``compile`` / ``cache_load`` and
        ``slow_compiles`` are cut there, ``late_build`` is what was
        built since.  ``trainer_build`` is the part of the four kinds
        that a build-causing stage (the Trainer's) caused.  ``fit_start``
        is the mean over the fits of ``entered`` -> ``first_window`` less
        the builds inside it, ``fit_stop`` of ``last_readback`` ->
        ``returned``.  ``stages`` holds every recorded stage's count,
        seconds and self seconds (its spans less the builds it caused),
        over the whole process.
        """
        now = self.totals()
        with self._lock:
            fits = list(self.fits)
            stages = {
                name: {
                    "count": count, "seconds": self._stage_s[name],
                    "self_seconds": (
                        self._stage_s[name] - self._cause_s.get(name, 0.0)
                    ),
                }
                for name, count in self._stage_n.items() if count
            }
            rows = sorted(
                (r for r in self.builds if r.parent is None),
                key=lambda r: r.start - r.end,
            )[:slowest]
        cut = (fits[-1].totals.get("first_dispatch_done") if fits else None)
        cut = cut or now
        starts = [
            f.first_window - f.entered - (
                f.totals["first_window"].seconds()
                - f.totals["entered"].seconds()
            )
            for f in fits if f.first_window is not None
        ]
        stops = [
            f.returned - f.last_readback for f in fits
            if f.returned is not None and f.last_readback is not None
        ]
        seconds = {
            "bring_up": stages.get("ddl.bring_up", {}).get("seconds", 0.0),
            **{kind: cut.by_kind[kind] for kind in BUILD_KINDS},
            "trainer_build": sum(
                cut.by_cause.get(name, 0.0)
                for name, row in STAGES.items() if row.builds
            ),
            "fit_start": sum(starts) / len(starts) if starts else 0.0,
            "fit_stop": sum(stops) / len(stops) if stops else 0.0,
            "late_build": now.seconds() - cut.seconds(),
        }
        return {
            "fits": len(fits),
            "seconds": seconds,
            "slow_compiles": cut.slow_compiles,
            "stages": stages,
            "slowest_programs": [
                {
                    "program": r.program, "kind": r.kind, "cache": r.cache,
                    "seconds": r.end - r.start, "cause": r.cause,
                    "fit": r.fit,
                }
                for r in rows
            ],
        }


_RECORD = StartupRecord()
_listening = False


def startup_record() -> StartupRecord:
    """The process's start-up record."""
    return _RECORD


def _on_scalar(event: str, value: float, **kw: Any) -> None:
    _RECORD.build_opened(event)


def _on_event(event: str, **kw: Any) -> None:
    _RECORD.cache_event(event)


def _on_duration(event: str, secs: float, **kw: Any) -> None:
    _RECORD.build_closed(event, float(secs), str(kw.get("fun_name", "?")))


def listen_for_builds() -> None:
    """Register the record's ONE listener with ``jax.monitoring``
    (idempotent; ``bring_up()`` and ``Trainer.__init__`` call it).  Its
    callbacks run only when JAX builds a program."""
    global _listening
    with _RECORD._lock:
        if _listening:
            return
        _listening = True
    import jax.monitoring

    jax.monitoring.register_scalar_listener(_on_scalar)
    jax.monitoring.register_event_listener(_on_event)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
