"""User extension surface: the producer-function skeleton.

API-compatible with reference ``ddl/datasetwrapper.py:4-19`` and
``ddl/datapusher.py:14-19``: users subclass :class:`ProducerFunctionSkeleton`,
override ``on_init`` (load the dataset, report geometry), ``post_init``
(write the first window) and ``execute_function`` (refill / in-place shuffle
each iteration).  Instances are constructed on the consumer and shipped to
producer workers by pickle (reference ``ddl/mpi_dataloader.py:130-136``),
so subclasses must be picklable.
"""

from __future__ import annotations

import abc
import dataclasses
from typing import Any

import numpy as np


@dataclasses.dataclass
class DataProducerOnInitReturn:
    """Geometry a producer function reports from ``on_init``.

    Parity: reference ``ddl/datapusher.py:14-19``.

    Attributes:
      nData:   number of samples in one window (rows).
      nValues: flattened feature width per sample (columns).
      shape:   full window shape, normally ``(nData, nValues)``.
      splits:  column widths to re-split a batch into the user's tensor
               tuple, e.g. ``(3, 1, 1)`` for (x, y, weight)
               (reference ``tests/run_ddl.py:156-159``).
      dtype:   window element dtype.  The reference hardwired float32
               (``ddl/connection.py:105-106``, SURVEY Q5); here any numpy
               dtype is honoured end-to-end.
    """

    nData: int
    nValues: int
    shape: tuple[int, ...]
    splits: tuple[int, ...]
    dtype: Any = np.float32


class ProducerFunctionSkeleton(abc.ABC):
    """Abstract producer function (reference ``ddl/datasetwrapper.py:4``).

    Lifecycle inside a producer worker:

    1. ``on_init(producer_idx=..., n_producers=..., instance_idx=...,
       n_instances=...)`` → :class:`DataProducerOnInitReturn`.  Load/open the
       dataset shard for this worker here (lazily — this runs in the worker,
       not on the consumer).
    2. ``post_init(my_ary=...)`` → write the initial window contents into
       the provided array view (reference ``tests/run_ddl.py:152-161``).
    3. ``execute_function(my_ary=..., epoch=...)`` → called once per window
       refill; typically an in-place shuffle or the next chunk of a stream
       (reference ``tests/run_ddl.py:163-167``).

    All hooks accept ``**kwargs`` so the framework can grow the context it
    passes without breaking user subclasses.

    ``inplace_fill``: when True, ``my_ary`` is a direct view of the next
    free ring slot rather than a private array, and the commit copy is
    skipped — the zero-copy fill path (the reference's ``my_ary`` *was*
    the shared window, reference ``tests/run_ddl.py:152-161``; here that
    is opt-in because slots rotate).  Contract: ``execute_function`` must
    fully write ``my_ary`` every call — its prior content is the window
    from ``nslots`` iterations ago, not the previous one.

    ``supports_inplace_fill``: the soft variant — "every fill fully
    rewrites the window, hand me a slot view when you can".  The pusher
    then fills in place by default but silently keeps the private-array
    fill when a cross-instance global shuffle needs ``my_ary`` to
    persist, or when ``DDL_TPU_INPLACE=0`` opts out.  Every built-in
    reader advertises it (write-once producers).
    """

    inplace_fill: bool = False
    supports_inplace_fill: bool = False

    #: Wire-format capability (``ddl_tpu.wire``, opt-in per reader):
    #: ``"raw"`` (default) commits windows at their storage dtype;
    #: ``"bf16"`` / ``"int8"`` license the pusher to commit the
    #: blockwise-encoded wire payload instead (scales in the integrity
    #: trailer extension, decoded at the consumer edge) — valid only
    #: for float windows, and a LOSSY statement: set it on readers
    #: whose data tolerates the quantization (the loss-parity gate,
    #: ``ddl_tpu.parallel.optimizer.loss_parity``, is the license).  The
    #: ``DDL_TPU_WIRE_DTYPE`` env overrides either way.
    wire_dtype: str = "raw"

    @abc.abstractmethod
    def on_init(self, **kwargs: Any) -> DataProducerOnInitReturn:
        raise NotImplementedError

    def post_init(self, **kwargs: Any) -> None:
        """Fill the first window. Default: no-op (stream-style producers)."""

    def execute_function(self, **kwargs: Any) -> None:
        """Refill/refresh the window before each handoff. Default: no-op."""

    def adopt_shards(self, ranges: Any, **kwargs: Any) -> None:
        """Adopt shard ``ranges`` mid-run (cross-host elastic recovery,
        :mod:`ddl_tpu.cluster`): a view change re-partitioned a dead
        host's shard range onto this producer's host.  ``ranges`` is a
        tuple of half-open ``(start, stop)`` shard-index pairs — the
        receiving host's FULL post-change assignment, not a delta —
        with ``peer_idx``/``n_peers`` kwargs locating this producer
        among its host's loader ranks.  Default: no-op (producers that
        never partition by shard ignore adoption)."""

    def fast_forward(self, n: int, **kwargs: Any) -> None:
        """Advance the producer's data position by ``n`` windows without
        publishing them — elastic recovery replays a respawned worker to
        where its predecessor died.  Default: ``n`` ordinary
        ``execute_function`` calls with the same kwargs the hot loop
        passes (``my_ary`` plus the per-call ``iteration``), which is
        exact for any producer whose state advances only through that
        hook (seeded shuffles, stream cursors).  Producers with cheaper
        position arithmetic (e.g. a file offset) should override."""
        for i in range(n):
            self.execute_function(iteration=i, **kwargs)
