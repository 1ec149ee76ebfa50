"""High-level Trainer: loader + sharded train step + aux systems in one call.

The reference left the whole consumer side to the user: init
``torch.distributed`` yourself, write the epoch loop yourself, call
``mark()`` yourself, no checkpointing, no failure detection (reference
``tests/run_ddl.py:171-238``, SURVEY §5.3-5.4).  ``Trainer`` composes the
ddl_tpu equivalents so one object owns the whole training run:

- the producer/consumer topology (``@distributed_dataloader`` role split),
- the GSPMD train step (``parallel.train.make_train_step``) on a caller
  mesh,
- the ``mark()`` protocol, driven automatically around the user-visible
  epoch loop,
- checkpoint/resume of BOTH halves (train state via Orbax, the loader's
  logical clock via ``LoaderCheckpoint``) at epoch boundaries,
- the producer watchdog and the metrics registry.

The loss function owns the batch layout: it receives exactly the column
tuple the loader serves (what the reference's user unpacked by hand,
``run_ddl.py:232``).
"""

from __future__ import annotations

import dataclasses
import logging
import os
import time
from typing import Any, Callable, List, Optional, Sequence, Tuple

from jax.sharding import PartitionSpec as P

from ddl_tpu.datasetwrapper import ProducerFunctionSkeleton
from ddl_tpu.observability import Metrics, metrics as default_metrics
from ddl_tpu.profiling import listen_for_builds, stage, startup_record

logger = logging.getLogger("ddl_tpu")


def _stream_splits(loader: Any) -> Tuple[int, ...]:
    """The single column-split tuple a window stream serves, validated:
    heterogeneous per-producer splits cannot ride one scanned program."""
    splits = set(loader.splits_per_producer)
    if len(splits) != 1:
        raise ValueError(
            "window_stream requires homogeneous column splits across "
            f"producers, got {sorted(splits)}"
        )
    (col_splits,) = splits
    return col_splits


def _window_cols(win: Any, col_splits: Sequence[int]) -> Tuple[Any, ...]:
    """Split a (bpw, batch, *features) device window into column arrays
    along the FIRST feature axis — the axis every batch-path split uses
    (``dataloader._split_columns`` slices ``batch[:, off:off+w]``).

    A single full-width column (token windows, ``splits=(seq,)``) passes
    through UNSLICED: the identity slice was a per-window device op
    whose output also lost the window's NamedSharding, forcing the
    multistep's ``_reshard`` into a second device_put — two dispatches
    per window for nothing, squarely on the stream-fit hot path."""
    if len(col_splits) == 1 and col_splits[0] == win.shape[2]:
        return (win,)
    cols, off = [], 0
    for w in col_splits:
        cols.append(win[:, :, off : off + w])
        off += w
    return tuple(cols)


@dataclasses.dataclass
class FitResult:
    state: Any  # final TrainState
    losses: List[float]  # per-epoch mean loss
    epochs_run: int
    resumed_from_epoch: int
    metrics: Metrics
    #: True when the run ended in a graceful preemption drain
    #: (``ddl_tpu.resilience.PreemptionGuard``) rather than completing
    #: its epochs — the caller should exit and let the restart resume.
    preempted: bool = False


class Trainer:
    """Owns one sharded training run fed by the ddl_tpu loader."""

    def __init__(
        self,
        loss_fn: Callable[[Any, Any], Any],
        optimizer: Any,
        mesh: Any,
        param_specs: Any,
        init_params: Any,
        batch_spec: P = P(("dp",)),
        checkpoint_dir: Optional[str] = None,
        checkpoint_every_epochs: int = 1,
        watchdog: bool = True,
        watchdog_respawn: bool = False,
        stall_budget_s: float = 300.0,
        metrics: Optional[Metrics] = None,
        accum_steps: Optional[int] = None,
        train_config: Any = None,
        checkpoint_async: Optional[bool] = None,
        checkpoint_keep: int = 3,
        preemption_guard: Any = None,
    ):
        """``loss_fn(params, batch) -> scalar`` over the loader's batch
        tuple; ``init_params`` is the initial params pytree (ignored when a
        checkpoint exists in ``checkpoint_dir``).

        ``train_config`` (a :class:`ddl_tpu.config.TrainConfig`)
        supplies the training hot-path defaults — ``accum_steps`` (an
        explicit argument wins; the default is the ``None`` sentinel
        precisely so an explicit ``accum_steps=1`` can DISABLE
        accumulation against a config that asks for it) and the
        distributed-optimizer knobs (``optimizer_sharding="zero1"``
        shards optimizer state + weight update over dp, ``grad_comm=
        "int8"`` opts into the quantized wire format — both flow into
        every step factory this Trainer builds); its remat policy and
        pipeline schedule apply where the model is BUILT
        (``train_config.model_config(cfg)`` /
        ``train_config.pipeline_kwargs()``), since the Trainer only
        ever sees the closed-over ``loss_fn``.

        ``checkpoint_async`` (default: the ``DDL_TPU_CKPT_ASYNC`` env
        gate, on) routes checkpoints through
        :class:`~ddl_tpu.resilience.AsyncCheckpointer` — the step
        loop's stall is the D2H snapshot alone, generations carry
        integrity trailers, and the loader cursor is fenced into the
        same atomic blob; ``False`` keeps the legacy synchronous Orbax
        path (now atomic temp+rename + manifest-verified on read).
        ``checkpoint_keep`` is the async tier's keep-K retention.
        ``preemption_guard`` (a :class:`~ddl_tpu.resilience.
        PreemptionGuard`) is polled at every window/epoch boundary:
        on a notice the run drains gracefully — forced final
        checkpoint, tenant-window revocation, graceful host drain,
        clean producer shutdown — and ``fit`` returns with
        ``FitResult.preempted`` set."""
        from ddl_tpu.parallel.train import make_train_step

        # Every program this Trainer builds lands in the start-up record
        # with the stage that caused it (idempotent).
        listen_for_builds()
        if accum_steps is None:
            accum_steps = (
                train_config.accum_steps if train_config is not None else 1
            )
        self.train_config = train_config
        # Distributed-optimizer knobs (TrainConfig.optimizer_kwargs):
        # zero1 state sharding / int8 grad comm flow into BOTH step
        # factories (the per-batch step here and every window-stream
        # multistep in _fit_windows) from the same dict, so the two
        # paths cannot train under different optimizer semantics.
        self._opt_kwargs = (
            train_config.optimizer_kwargs()
            if train_config is not None
            else {}
        )

        self.mesh = mesh
        self.checkpoint_dir = checkpoint_dir
        self.checkpoint_every_epochs = max(1, checkpoint_every_epochs)
        if checkpoint_async is None:
            from ddl_tpu.utils import env_flag

            checkpoint_async = env_flag("DDL_TPU_CKPT_ASYNC")
        self.checkpoint_async = bool(checkpoint_async)
        self.checkpoint_keep = max(1, int(checkpoint_keep))
        self._ckptr: Any = None  # lazy AsyncCheckpointer
        self._guard = preemption_guard
        self._restored_loader_ck: Any = None
        self._preempted = False
        self.watchdog_enabled = watchdog
        self.watchdog_respawn = watchdog_respawn
        self.stall_budget_s = stall_budget_s
        self.metrics = metrics or default_metrics()
        self._init_params = init_params
        self._batch_spec = batch_spec
        self._loss_fn = loss_fn
        self._optimizer = optimizer
        self._param_specs = param_specs
        self._accum_steps = accum_steps
        self._init_fn, self._step_fn = make_train_step(
            loss_fn, optimizer, mesh, param_specs, batch_spec=batch_spec,
            accum_steps=accum_steps, **self._opt_kwargs,
        )
        # window_stream multistep programs, keyed by steps-per-window, so
        # repeated fit() calls on one Trainer reuse the compiled scan.
        # LRU-bounded (DDL013): a pathological producer mix emitting a
        # new window depth per rotation would otherwise pin every
        # compiled program it ever built; evicted depths just recompile.
        self._multistep_cache: dict = {}
        self._multistep_cache_cap = 8

    # -- checkpoint plumbing ----------------------------------------------

    def _loader_ckpt_path(self) -> str:
        assert self.checkpoint_dir is not None
        return os.path.join(self.checkpoint_dir, "loader.json")

    def _checkpointer(self) -> Any:
        """The lazily built per-trainer async checkpointer."""
        if self._ckptr is None:
            from ddl_tpu.resilience import AsyncCheckpointer

            assert self.checkpoint_dir is not None
            self._ckptr = AsyncCheckpointer(
                self.checkpoint_dir, keep=self.checkpoint_keep,
                metrics=self.metrics,
            )
        return self._ckptr

    def _restore_or_init(self) -> Tuple[Any, int]:
        """Returns (train state, epoch to start from).

        Restore prefers the VERIFIED source with the newest step:
        resilience generation files (integrity-trailer checked, loader
        cursor fenced inside the blob) vs legacy Orbax ``step_*``
        directories (manifest-verified since ISSUE 14) — so a run that
        switched checkpointing modes still resumes from its true
        frontier.  Unverifiable generations of either format are
        quarantined and the previous verified one restores instead;
        exhaustion is a cold start (loud counter), never a crash.
        """
        from ddl_tpu.checkpoint import (
            LoaderCheckpoint,
            latest_verified_step,
            restore_train_state,
        )
        from ddl_tpu.resilience import (
            latest_verified_generation,
            restore_latest,
        )

        state = self._init_fn(self._init_params)
        self._restored_loader_ck = None
        if self.checkpoint_dir is None:
            return state, 0
        gen = latest_verified_generation(
            self.checkpoint_dir, metrics=self.metrics
        )
        legacy_step = latest_verified_step(self.checkpoint_dir)
        if gen is not None and (
            legacy_step is None or gen[0] >= legacy_step
        ):
            # The cold-start state exists here only to give the restore
            # its structure and shardings: keep those and drop its
            # buffers BEFORE the restore allocates — two full states do
            # not fit beside each other for an HBM-filling model.
            import jax

            from ddl_tpu.parallel.train import TrainState

            def abstract(tree: Any) -> Any:
                return jax.tree.map(
                    lambda x: jax.ShapeDtypeStruct(
                        x.shape, x.dtype, sharding=x.sharding
                    ) if isinstance(x, jax.Array) else x,
                    tree,
                )

            like = TrainState(
                params=abstract(state.params),
                opt_state=abstract(state.opt_state),
            )
            del state
            # found=gen: the scan above already CRC'd every candidate —
            # restore must not re-read the blobs a second time.
            restored = restore_latest(
                self.checkpoint_dir, like=like, metrics=self.metrics,
                found=gen,
            )
            assert restored is not None  # gen verified just above
            self._restored_loader_ck = restored.loader
            start_epoch = (
                restored.loader.epoch if restored.loader is not None else 0
            )
            logger.info(
                "trainer: resumed step %d / epoch %d from generation "
                "checkpoint %s", restored.state.step, start_epoch,
                self.checkpoint_dir,
            )
            return restored.state, start_epoch
        if legacy_step is None:
            return state, 0
        state = restore_train_state(
            self.checkpoint_dir, like=state, step=legacy_step
        )
        start_epoch = 0
        if os.path.exists(self._loader_ckpt_path()):
            ck = LoaderCheckpoint.load(self._loader_ckpt_path())
            self._restored_loader_ck = ck
            start_epoch = ck.epoch
        logger.info(
            "trainer: resumed step %d / epoch %d from %s",
            state.step, start_epoch, self.checkpoint_dir,
        )
        return state, start_epoch

    def _checkpoint(
        self, state: Any, loader: Any, shuffler: Any = None,
        force: bool = False, timeout_s: float = 60.0,
    ) -> None:
        # Producer-side shuffler rounds need no explicit capture: on resume
        # ``fit`` replays the consumed windows (``loader.fast_forward``) and
        # the producers re-execute their deterministic schedule — including
        # every exchange round — so the shuffle continues exactly where it
        # stopped (proven end-to-end by tests/test_resume_shuffle.py).
        # Consumer-owned device shufflers DO carry state; their round rides
        # in ``LoaderCheckpoint.shuffle_round`` via ``capture(loader,
        # shuffler)`` (tests/test_aux.py::TestShuffleRoundResume).
        from ddl_tpu.checkpoint import LoaderCheckpoint, save_train_state

        assert self.checkpoint_dir is not None
        ck = LoaderCheckpoint.capture(loader, shuffler=shuffler)
        if self.checkpoint_async:
            # Async tier: the measured stall is the D2H snapshot; the
            # serialize/fsync/rename hides under training.  ``force``
            # (the preemption drain's final checkpoint) waits for the
            # bytes to be durably on disk before returning.
            cp = self._checkpointer()
            if force:
                cp.checkpoint_now(state, ck, timeout_s=timeout_s)
            else:
                cp.submit(state, ck)
            return
        with self.metrics.timed("resilience.ckpt_sync"):
            save_train_state(state, self.checkpoint_dir)
            ck.save(self._loader_ckpt_path())

    def _finish_checkpoints(self) -> None:
        """Bounded flush of the async writer at the end of a fit: the
        final periodic checkpoint must be durable before the process
        can exit (the writer is a daemon thread — without this flush a
        completed run could silently lose its newest generation and a
        restart would resume one interval early)."""
        if self._ckptr is None:
            return
        from ddl_tpu.exceptions import CheckpointError

        try:
            self._ckptr.flush(timeout_s=60.0)
        except CheckpointError:
            logger.exception(
                "trainer: async checkpoint flush at fit end failed — "
                "the newest generation may be missing on restart"
            )

    def _preempt_drain(
        self, state: Any, loader: Any, shuffler: Any = None
    ) -> None:
        """Run the guard's graceful-drain ladder at a window boundary:
        forced final checkpoint (state + fenced loader cursor), tenant
        revocation / host drain (the guard's attached rungs), then a
        clean producer shutdown — the watchdog sees an orderly close,
        not failures."""
        self._preempted = True

        def final_ckpt():
            if self.checkpoint_dir is not None:
                # Bounded by the REMAINING grace budget: a wedged
                # writer must not eat the whole notice window and
                # starve the revoke/drain/shutdown rungs behind it.
                self._checkpoint(
                    state, loader, shuffler=shuffler, force=True,
                    timeout_s=max(1.0, self._guard.remaining()),
                )

        self._guard.drain(
            final_checkpoint=final_ckpt, shutdown=loader.shutdown
        )

    def _should_drain(self) -> bool:
        return self._guard is not None and self._guard.poll()

    # -- evaluation --------------------------------------------------------

    def evaluate(
        self,
        producer_function: ProducerFunctionSkeleton,
        state: Any,
        metric_fn: Callable[[Any, Any], Any],
        batch_size: int,
        n_producers: Optional[int] = None,
        mode: Optional[str] = None,
        output: str = "numpy",
        window_stream: bool = False,
        n_epochs: int = 1,
    ) -> float:
        """Metric pass over a (held-out) producer's windows.

        Drains ``n_epochs`` epochs (one window per producer rotation —
        the Q7 epoch; pass ``n_epochs=n_producers`` to cover every
        producer once) computing ``metric_fn(params, batch) -> scalar``
        per batch and returns the sample-weighted mean.  Uses the same
        producer/consumer machinery as ``fit`` but runs no optimizer
        step — e.g. pass ``models.vit.accuracy`` for classification
        eval.  ``window_stream=True`` (``output="jax"``): each window
        streams zero-copy and all its batches evaluate in one jitted
        scan.
        """
        from ddl_tpu import DistributedDataLoader, Marker, distributed_dataloader

        if window_stream and output != "jax":
            raise ValueError("window_stream requires output='jax'")
        trainer = self

        @distributed_dataloader(n_producers=n_producers, mode=mode)
        def _run(env):
            lkw: dict = {}
            if output == "jax":
                # Same sharded-landing optimisation as fit: batches land
                # distributed over the mesh, not whole on device 0.
                from ddl_tpu.parallel.train import _named

                spec = (
                    P(*((None,) + tuple(trainer._batch_spec)))
                    if window_stream
                    else trainer._batch_spec
                )
                lkw["sharding"] = _named(trainer.mesh, spec)
            loader = DistributedDataLoader(
                producer_function,
                batch_size=batch_size,
                connection=env.connection,
                n_epochs=n_epochs,
                output=output,
                metrics=trainer.metrics,
                **lkw,
            )
            if window_stream:
                import jax

                col_splits = _stream_splits(loader)

                @jax.jit
                def window_metric(params, win):
                    vals = jax.vmap(
                        lambda *b: metric_fn(params, tuple(b))
                    )(*_window_cols(win, col_splits))
                    return vals.mean()

                vals = []
                for win in loader.windows():
                    # Weight each window's mean by its batch count: with
                    # mixed batches_per_window across producers (served
                    # by weighted rotation), a plain mean-of-means would
                    # overweight small windows.
                    vals.append((window_metric(state.params, win),
                                 win.shape[0]))
                    loader.mark(Marker.END_OF_EPOCH)
                total = sum(w for _, w in vals)
                return (
                    sum(float(v) * w for v, w in vals) / total
                    if total else float("nan")
                )
            vals: List[Any] = []
            for _epoch in range(n_epochs):
                it = loader.prefetch() if output == "jax" else loader
                for batch in it:
                    # Keep metrics as device arrays; a float() here would
                    # serialise loading against compute (see fit).
                    vals.append(metric_fn(state.params, batch))
                    loader.mark(Marker.END_OF_BATCH)
                loader.mark(Marker.END_OF_EPOCH)
            # Batches all hold batch_size samples, so a plain mean over
            # batches IS the sample-weighted mean even with mixed
            # window sizes.
            fvals = [float(v) for v in vals]
            return sum(fvals) / len(fvals) if fvals else float("nan")

        return _run()

    # -- window-stream epoch loop -----------------------------------------

    def _fit_windows(
        self,
        loader: Any,
        state: Any,
        start_epoch: int,
        n_epochs: int,
        epoch_losses: List[float],
        window_hook: Any = None,
        hook_state: Any = None,
        stream_lookahead: int = 1,
        fused: Optional[bool] = None,
    ) -> FitResult:
        """One multistep scan per streamed window (see ``fit`` docstring).

        Two disciplines, selected by ``fused`` (default: the
        ``DDL_TPU_FUSED`` gate, on):

        - **Fused** (:meth:`_fused_stream_loop`): the whole data plane
          hides under the train step.  Window N+1's transfer — and on a
          multi-device mesh its double-buffered ICI fan-out ring
          (``IciDistributor``'s landing slots) — is dispatched before
          scan N, the slot release is gated on the CONSUMING step's
          done-future (``loader.gate_release_on``), and the per-epoch
          loss read-back is deferred by one window so the host sync of
          scan k never blocks the enqueue of scan k+1 or the stream of
          window k+2.
        - **Synchronous** (:meth:`_sync_stream_loop`,
          ``DDL_TPU_FUSED=0``): the window lands
          (``block_until_ready``) before the step is dispatched and the
          losses are read back before the next acquire — measured step
          time is compute + ingest, not max().  This is the bench A/B's
          unfused baseline and the discipline every fallback rung
          degrades toward; it must stay loss-identical to the fused
          loop (same data, same math, different dispatch timing).
        """
        from ddl_tpu.parallel.train import make_multistep

        col_splits = _stream_splits(loader)
        if fused is None:
            from ddl_tpu.parallel.ici import fused_enabled

            fused = fused_enabled()

        # Window-stream scans are UNDONATED on the CPU client: a
        # donated jit call executes SYNCHRONOUSLY there (measured —
        # dispatch blocks for the whole execution), which collapses the
        # async dispatch queue the stream's overlap (and the whole
        # fused step) rides on.  Accelerator runtimes pipeline donated
        # buffers fine, so the chip path keeps donation (undonated
        # params + optimizer state would double peak HBM — DDL017's
        # whole point); on CPU the second buffer is the price of the
        # entire data plane hiding under the step.
        donate = all(
            getattr(d, "platform", "cpu") != "cpu"
            for d in self.mesh.devices.flat
        )

        def multi_for(n_steps: int):
            # Resolved PER WINDOW: with mixed batches_per_window across
            # producers, windows of different depths arrive as the
            # rotation advances, each needing its own scan length
            # (compiled once per distinct depth, cached — ``donate`` is
            # constant per trainer, so depth alone keys the cache).
            fn = self._multistep_cache.pop(n_steps, None)
            if fn is None:
                _, fn = make_multistep(
                    self._loss_fn, self._optimizer, self.mesh,
                    self._param_specs, batch_spec=self._batch_spec,
                    n_steps=n_steps, accum_steps=self._accum_steps,
                    donate=donate, **self._opt_kwargs,
                )
            # Re-insert at the MRU end (dict preserves insertion order);
            # trim the LRU end past the cap.
            self._multistep_cache[n_steps] = fn
            while len(self._multistep_cache) > self._multistep_cache_cap:
                self._multistep_cache.pop(
                    next(iter(self._multistep_cache))
                )
            return fn

        stream = loader.windows(lookahead=stream_lookahead)
        loop = self._fused_stream_loop if fused else self._sync_stream_loop
        state = loop(
            loader, stream, state, multi_for, col_splits, window_hook,
            hook_state, epoch_losses, start_epoch,
        )
        startup_record().stamp("last_readback")
        for i, mean in enumerate(epoch_losses):
            logger.info(
                "trainer: epoch %d/%d mean loss %.6f (windowed)",
                start_epoch + i + 1, n_epochs, mean,
            )
        return FitResult(
            state=state,
            losses=epoch_losses,
            epochs_run=(
                len(epoch_losses)
                if self._preempted
                else n_epochs - start_epoch
            ),
            resumed_from_epoch=start_epoch,
            metrics=self.metrics,
            preempted=self._preempted,
        )

    def _fused_stream_loop(
        self, loader, stream, state, multi_for, col_splits, window_hook,
        hook_state, epoch_losses, start_epoch,
    ):
        """The fused compute/ingest step (DDL020: no host syncs).

        Per window: acquire (the data plane already dispatched it under
        the previous scan), dispatch the scan, hand the scan's
        done-future to the loader (``gate_release_on`` — slot release
        waits for the CONSUMER, not the transfer), then read back the
        PREVIOUS window's losses.  That deferred read-back is the only
        host sync, it blocks on a scan that is already one window old
        (bounding in-flight depth at two — the landing-slot count), and
        the overlap it buys is measured: the acquire span of window k+1
        while scan k is still computing accumulates into
        ``trainer.ingest_overlap`` (a LOWER bound on hidden ingest:
        spans whose scan finished mid-acquire are not counted).
        """
        from ddl_tpu import Marker
        from ddl_tpu.utils import value_ready

        m = self.metrics
        pending = None
        epoch = start_epoch
        _done = object()
        while True:
            # Window-wait accounting: with healthy overlap the next
            # window is already in flight while the previous scan runs,
            # so this wait stays near zero; it flows into
            # north_star_report["window_wait_s"] and the bench JSON.
            # The stage puts the same wait on the jax.profiler timeline,
            # where the device's idle gaps are attributed to it.
            t0 = time.perf_counter()
            with stage("ddl.window_wait", m):
                win = next(stream, _done)
            # Ready-by-default polarity: an unprobeable future must
            # never inflate the overlap measurement.
            if pending is not None and not value_ready(pending, True):
                # The previous scan computed through this whole acquire:
                # the data plane was hidden under the step.
                m.add_time(
                    "trainer.ingest_overlap", time.perf_counter() - t0
                )
            if win is _done:
                break
            if window_hook is not None:
                win = window_hook(win)
            # Consume span = the scan DISPATCH (DDL020: the fused loop
            # never waits on the device, so dispatch is all there is).
            with stage(
                "ddl.step_dispatch", m,
                loader.last_window_key() or (None, None),
            ):
                state, losses = multi_for(win.shape[0])(
                    state, _window_cols(win, col_splits), per_step=True
                )
                # The epoch-loss reduction is dispatched HERE, right
                # behind its own scan: backends that execute in dispatch
                # order (the CPU client) would otherwise queue a
                # read-time ``pending.mean()`` behind the NEXT scan,
                # silently re-serializing the loop the fused step exists
                # to overlap.
                loss_mean = losses.mean()
                loader.gate_release_on(losses)
            m.incr("trainer.fused_windows")
            if pending is not None:
                # Deferred ONE window: blocks on the PREVIOUS scan's
                # already-queued reduction, bounding in-flight depth at
                # the landing-slot count.
                with stage("ddl.loss_readback", m):
                    epoch_losses.append(float(pending))
            pending = loss_mean
            epoch += 1
            loader.mark(Marker.END_OF_EPOCH)
            if (
                self.checkpoint_dir is not None
                and epoch % self.checkpoint_every_epochs == 0
            ):
                self._checkpoint(state, loader, shuffler=hook_state)
            if self._should_drain():
                # Graceful preemption drain at the window boundary: the
                # forced checkpoint inside syncs on the dispatched
                # scans (device_get at the step-future boundary), so
                # ZERO completed windows are lost.
                self._preempt_drain(state, loader, shuffler=hook_state)
                break
        if pending is not None:
            # Stream drained; the final scan must be consumed.
            with stage("ddl.loss_readback", m):
                epoch_losses.append(float(pending))
        return state

    def _sync_stream_loop(
        self, loader, stream, state, multi_for, col_splits, window_hook,
        hook_state, epoch_losses, start_epoch,
    ):
        """The synchronous (unfused) discipline — ``DDL_TPU_FUSED=0``.

        The window lands, THEN compute starts, THEN the losses are read
        back: measured step time is compute + ingest.  Kept as (a) the
        explicit escape hatch, (b) the fused A/B's baseline leg in the
        bench, and (c) the behavior every degradation rung falls back
        toward — bit-identical losses to the fused loop by
        construction (same windows, same compiled scans, different
        dispatch timing only).
        """
        import jax

        from ddl_tpu import Marker

        m = self.metrics
        epoch = start_epoch
        _done = object()
        while True:
            with stage("ddl.window_wait", m):
                win = next(stream, _done)
                if win is not _done:
                    # "The window lands...": expose the whole transfer.
                    jax.block_until_ready(win)
            if win is _done:
                break
            if window_hook is not None:
                win = window_hook(win)
            with stage(
                "ddl.step_dispatch", m,
                loader.last_window_key() or (None, None),
            ):
                state, losses = multi_for(win.shape[0])(
                    state, _window_cols(win, col_splits), per_step=True
                )
            # "...then compute runs to completion": immediate read-back
            # serializes the next acquire behind this scan.
            with stage("ddl.loss_readback", m):
                epoch_losses.append(float(losses.mean()))
            epoch += 1
            loader.mark(Marker.END_OF_EPOCH)
            if (
                self.checkpoint_dir is not None
                and epoch % self.checkpoint_every_epochs == 0
            ):
                self._checkpoint(state, loader, shuffler=hook_state)
            if self._should_drain():
                self._preempt_drain(state, loader, shuffler=hook_state)
                break
        return state

    # -- the run -----------------------------------------------------------

    def fit(
        self,
        producer_function: ProducerFunctionSkeleton,
        batch_size: Optional[int] = None,
        n_epochs: Optional[int] = None,
        n_producers: Optional[int] = None,
        mode: Optional[str] = None,
        nslots: Optional[int] = None,
        output: Optional[str] = None,
        global_shuffle_fraction_exchange: Optional[float] = None,
        shuffler_factory: Any = None,
        loader_kwargs: Optional[dict] = None,
        prefetch_depth: Optional[int] = None,
        window_stream: Optional[bool] = None,
        window_hook: Any = None,
        stream_lookahead: int = 1,
        fused: Optional[bool] = None,
        config: Any = None,
    ) -> FitResult:
        """Run the full producer/consumer training job; returns FitResult.

        ``config`` (a :class:`ddl_tpu.config.LoaderConfig`) supplies
        defaults for the *run-level* knobs — batch_size, n_epochs,
        n_producers, mode, nslots, output,
        global_shuffle_fraction_exchange, exchange_method, ring_timeout_s
        — with explicit arguments winning.  Checkpointing and watchdog
        knobs are `Trainer` constructor arguments, not read from the
        config here.  With no config, ``batch_size`` and ``n_epochs`` are
        required.

        ``window_stream=True`` (``output="jax"`` only) drives the run off
        the zero-copy window stream: each epoch-window crosses into HBM as
        ONE transfer straight out of the ring slot
        (``DistributedDataLoader.windows``) and all its batches run as ONE
        jitted ``lax.scan`` of optimizer steps (``make_multistep``,
        ``per_step=True``) — one dispatch and one transfer per window
        instead of one of each per batch, with the next window streaming
        while the scan computes.  The optimizer-step sequence is exactly
        the per-batch path's, so results match batch-mode ``fit``.

        ``window_hook`` (window-stream mode only): a callable applied to
        each drained device window before its train scan — the trainer-
        side extension point for DEVICE-side transforms, e.g. a
        cross-instance ``DeviceGlobalShuffler`` exchange (which, unlike
        the producer-side host exchange, composes with elastic respawn:
        no producer carries exchange state).  Must be shape-preserving.

        ``stream_lookahead`` (window-stream mode only) deepens the window
        stream's in-flight pipeline (``DistributedDataLoader.windows``'s
        ``lookahead``); with the staged ingest engine early slot release
        lets the same ``nslots`` sustain the deeper pipeline.

        ``fused`` (window-stream mode only; default: the
        ``DDL_TPU_FUSED`` env gate, on) selects the fused
        compute/ingest step — the data plane dispatched under the train
        step, slot release gated on the consuming step's done-future —
        vs the synchronous discipline (window lands, then compute, then
        loss read-back).  Loss-identical either way; only dispatch
        timing differs (see ``_fit_windows``).

        Under PROCESS/MULTIHOST modes call this from under
        ``if __name__ == "__main__":`` (multiprocessing spawn re-imports
        the main module).  Global shuffle needs BOTH knobs: the exchange
        fraction and a ``shuffler_factory`` (e.g.
        ``ThreadExchangeShuffler.factory(...)``) — producers only build a
        shuffler when a factory is given.
        """
        from ddl_tpu import DistributedDataLoader, Marker, distributed_dataloader
        from ddl_tpu.watchdog import Watchdog

        if config is not None:
            batch_size = config.batch_size if batch_size is None else batch_size
            n_epochs = config.n_epochs if n_epochs is None else n_epochs
            n_producers = (
                config.n_producers if n_producers is None else n_producers
            )
            mode = config.mode if mode is None else mode
            nslots = config.nslots if nslots is None else nslots
            output = config.output if output is None else output
            if global_shuffle_fraction_exchange is None:
                global_shuffle_fraction_exchange = (
                    config.global_shuffle_fraction_exchange
                )
            if window_stream is None:
                window_stream = getattr(config, "window_stream", False)
            loader_kwargs = dict(loader_kwargs or {})
            loader_kwargs.setdefault(
                "exchange_method", config.exchange_method
            )
            loader_kwargs.setdefault("timeout_s", config.ring_timeout_s)
        if batch_size is None or n_epochs is None:
            raise ValueError(
                "batch_size and n_epochs are required (directly or via "
                "config=LoaderConfig(...))"
            )
        nslots = 2 if nslots is None else nslots
        output = "jax" if output is None else output
        if prefetch_depth is None:
            # config field → env mirror → default, via the envspec seam
            # (the tunable every ddl_tpu.tune knob change lands on).
            if config is not None and hasattr(config, "prefetch_depth"):
                prefetch_depth = config.prefetch_depth
            else:
                from ddl_tpu import envspec

                prefetch_depth = envspec.get("DDL_TPU_PREFETCH_DEPTH")
        window_stream = bool(window_stream)
        if window_stream and output != "jax":
            raise ValueError("window_stream requires output='jax'")
        if window_hook is not None and not window_stream:
            raise ValueError("window_hook requires window_stream=True")
        if fused is not None and not window_stream:
            raise ValueError("fused requires window_stream=True")
        # A stateful hook provider (DeviceGlobalShuffler or anything with
        # a .window_hook() factory) is passed WHOLE so the trainer can
        # checkpoint/restore its round state with the loader clock.  A
        # bare hook produced by .window_hook() carries its provider as
        # ``.owner`` — both forms checkpoint identically; only a hand-
        # written callable with no owner is the caller's responsibility
        # to resume.
        hook_state = None
        if window_hook is not None:
            if hasattr(window_hook, "window_hook"):
                hook_state = window_hook
                window_hook = hook_state.window_hook()
            else:
                hook_state = getattr(window_hook, "owner", None)
        global_shuffle_fraction_exchange = (
            global_shuffle_fraction_exchange or 0.0
        )
        if global_shuffle_fraction_exchange > 0 and shuffler_factory is None:
            raise ValueError(
                "global_shuffle_fraction_exchange > 0 requires a "
                "shuffler_factory (producers build no shuffler without one)"
            )
        trainer = self

        @distributed_dataloader(
            n_producers=n_producers, mode=mode, nslots=nslots,
            shuffler_factory=shuffler_factory,
        )
        def _main(env):
            trainer._preempted = False
            with stage("ddl.state_init", trainer.metrics):
                state, start_epoch = trainer._restore_or_init()
            lkw = dict(loader_kwargs or {})
            if output == "jax" and "sharding" not in lkw:
                # Batches land directly sharded over the mesh instead of
                # materialising whole on device 0 and resharding.  Window
                # layout is (batches_per_window, batch, ...), so stream
                # mode shards one axis deeper.
                from ddl_tpu.parallel.train import _named

                spec = (
                    P(*((None,) + tuple(trainer._batch_spec)))
                    if window_stream
                    else trainer._batch_spec
                )
                lkw["sharding"] = _named(trainer.mesh, spec)
            with stage("ddl.loader_attach", trainer.metrics):
                loader = DistributedDataLoader(
                    producer_function,
                    batch_size=batch_size,
                    connection=env.connection,
                    n_epochs=n_epochs,
                    output=output,
                    metrics=trainer.metrics,
                    global_shuffle_fraction_exchange=(
                        global_shuffle_fraction_exchange
                    ),
                    **lkw,
                )
            if start_epoch >= n_epochs:
                # Nothing left to run (fit re-invoked with fewer epochs
                # than the checkpoint already completed).
                logger.info(
                    "trainer: checkpoint at epoch %d >= n_epochs %d — "
                    "nothing to do", start_epoch, n_epochs,
                )
                loader.shutdown()
                return FitResult(
                    state=state, losses=[], epochs_run=0,
                    resumed_from_epoch=start_epoch, metrics=trainer.metrics,
                )
            if start_epoch:
                from ddl_tpu.checkpoint import LoaderCheckpoint

                # The cursor FENCED to the restored train state (it
                # rode inside the verified generation blob) wins over
                # the loader.json mirror — a crash between the two
                # writes can never desync data from params.
                ck = trainer._restored_loader_ck
                if ck is None:
                    ck = LoaderCheckpoint.load(trainer._loader_ckpt_path())
                # Discard the windows the pre-checkpoint run consumed (one
                # per epoch): producers regenerate their sequence
                # deterministically, so resumed epochs see the DATA they
                # would have seen, not a replay of epoch 0.
                loader.fast_forward(ck.epoch)
                # shuffler=hook_state also restores a device shuffler's
                # round counter, so post-resume exchange permutations
                # continue the schedule instead of replaying round 0.
                ck.apply(loader, shuffler=hook_state)
            wd = None
            if trainer.watchdog_enabled and env.workers is not None:
                # respawn=True turns failure detection into elastic
                # recovery: dead producer workers are replaced in place
                # and the run continues (tests/test_elastic.py).
                wd = Watchdog(
                    env.workers,
                    stall_budget_s=trainer.stall_budget_s,
                    respawn=trainer.watchdog_respawn,
                    # The trainer's registry, not the process default:
                    # respawns/failures must show in THIS run's
                    # north_star_report robustness block.
                    metrics=trainer.metrics,
                ).start()
            epoch_losses: List[float] = []
            if window_stream:
                try:
                    return trainer._fit_windows(
                        loader, state, start_epoch, n_epochs, epoch_losses,
                        window_hook=window_hook, hook_state=hook_state,
                        stream_lookahead=stream_lookahead, fused=fused,
                    )
                finally:
                    with stage("ddl.pool_stop", trainer.metrics):
                        trainer._finish_checkpoints()
                        if wd is not None:
                            wd.stop()
            try:
                for epoch in range(start_epoch, n_epochs):
                    batch_losses: List[Any] = []
                    # Device output iterates with lookahead: batch k+1 is
                    # crossing into HBM while step k computes (VERDICT r2
                    # item 5 — PrefetchIterator was previously unwired).
                    epoch_iter = (
                        loader.prefetch(prefetch_depth)
                        if output == "jax" and prefetch_depth > 1
                        else loader
                    )
                    for batch in epoch_iter:
                        state_new, loss = trainer._step_fn(state, batch)
                        state = state_new
                        # Keep losses as device arrays: a float() here
                        # would block on the step and serialize loading
                        # against compute, defeating the ring overlap.
                        batch_losses.append(loss)
                        loader.mark(Marker.END_OF_BATCH)
                    loader.mark(Marker.END_OF_EPOCH)
                    vals = [float(x) for x in batch_losses]
                    mean = sum(vals) / len(vals) if vals else float("nan")
                    epoch_losses.append(mean)
                    logger.info(
                        "trainer: epoch %d/%d mean loss %.6f (%d batches)",
                        epoch + 1, n_epochs, mean, len(batch_losses),
                    )
                    if (
                        trainer.checkpoint_dir is not None
                        and (epoch + 1) % trainer.checkpoint_every_epochs == 0
                    ):
                        trainer._checkpoint(state, loader)
                    if trainer._should_drain():
                        # Batch-path drain at the epoch boundary (the
                        # stream path drains per window == per epoch).
                        trainer._preempt_drain(state, loader)
                        break
            finally:
                with stage("ddl.pool_stop", trainer.metrics):
                    trainer._finish_checkpoints()
                    if wd is not None:
                        wd.stop()
            return FitResult(
                state=state,
                losses=epoch_losses,
                epochs_run=(
                    len(epoch_losses)
                    if trainer._preempted
                    else n_epochs - start_epoch
                ),
                resumed_from_epoch=start_epoch,
                metrics=trainer.metrics,
                preempted=trainer._preempted,
            )

        # The start-up record's fit: stamped at entry, at the first
        # window and the first dispatch (by their stages), after the
        # last read-back and here, once the pool is down.
        fit_row = startup_record().begin_fit()
        try:
            return _main()
        finally:
            startup_record().end_fit(fit_row)
