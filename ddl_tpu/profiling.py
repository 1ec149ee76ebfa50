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
"""

from __future__ import annotations

import contextlib
import time
from typing import Any, Dict, Iterator, NamedTuple, Optional, Tuple

from ddl_tpu.obs import spans as obs_spans


class Stage(NamedTuple):
    """One stage's three names (``None``: the stage has no such emission)."""

    timer: Optional[str]  # Metrics timer
    span: Optional[str]  # SpanLog stage (``obs.STAGES``)
    where: str  # what the stage covers, for docs/OBSERVABILITY.md


#: Profiler annotation name -> the stage's timer and SpanLog stage.  Names
#: are frozen: the benchmark's readers, ``tracered.py`` and
#: ``north_star_report`` read them.  ``ddl.window_wait`` encloses acquire,
#: transfer wait, release wait and (inline path) put + fan-out; a reader
#: that takes the innermost span covering a gap needs nothing more.
STAGES: Dict[str, Stage] = {
    "ddl.window_wait": Stage(
        "trainer.window_wait", None,
        "trainer: the whole of `next(stream)` on the train loop's thread",
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
        "ICI distributor: lane pack + ring-kernel dispatch on the anchor",
    ),
    "ddl.step_dispatch": Stage(
        "trainer.step_dispatch", "trainer.consume",
        "trainer: dispatch of the window's scanned steps (a recompile "
        "or a full dispatch queue shows here)",
    ),
    "ddl.loss_readback": Stage(
        "trainer.loss_readback", None,
        "trainer: the host read-back of a window's mean loss (fused "
        "loop: the PREVIOUS window's, the loop's one host sync)",
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
    """

    __slots__ = ("key", "_row", "_metrics", "_ann", "_t0")

    def __init__(
        self,
        name: str,
        metrics: Any = None,
        key: Optional[Tuple[Optional[int], Optional[int]]] = None,
    ):
        self._row = STAGES[name]
        self._metrics = metrics if self._row.timer else None
        self._ann = _annotation(name)
        self.key = key

    def __enter__(self) -> "stage":
        self._ann.__enter__()
        self._t0 = (
            time.perf_counter() if self._metrics is not None
            else obs_spans.t0()
        )
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
        finally:
            self._ann.__exit__(*exc)
