"""End-to-end example: synthetic pointwise dataset through the full pipeline.

The TPU-native analog of reference ``tests/run_ddl.py`` — its only
executable spec (SURVEY §4): a synthetic CFD-flavoured pointwise dataset
(``run_ddl.py:80-104``), min-max normalised (``:57-77``), loaded by a
``ProducerFunctionSkeleton`` subclass (``:107-167``) and drained by a
decorated main with the explicit ``mark()`` contract (``:228-238``).

Runs in any mode:

    python examples/run_ddl.py                # THREAD mode (single process)
    python examples/run_ddl.py process        # spawned producer processes
    DDL_TPU_N_PRODUCERS=3 python examples/run_ddl.py process

Exit code 0 after a deadlock-free drain of every epoch is the pass
criterion, mirroring the reference's CI gate (``tests/test_ddl.py:14-22``).
"""

from __future__ import annotations

import dataclasses
import logging
import os
import sys
from typing import Any

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from _common import configure  # noqa: E402

configure()

from ddl_tpu import (
    DataProducerOnInitReturn,
    DistributedDataLoader,
    Marker,
    ProducerFunctionSkeleton,
    distributed_dataloader,
)


@dataclasses.dataclass
class Params:
    """Workload knobs (reference ``tests/run_ddl.py:243-316``)."""

    nepoch: int = 4
    batch_size: int = 32
    n_data: int = 1024  # samples per producer window
    n_features: int = 10  # columns: 3 pos + 6 field + 1 weight


def make_pointwise_data(n: int, n_features: int, seed: int) -> np.ndarray:
    """Synthetic CFD-style pointwise samples, min-max normalised per column
    (reference ``tests/run_ddl.py:57-104``)."""
    rng = np.random.default_rng(seed)
    raw = rng.random((n, n_features), dtype=np.float32)
    lo, hi = raw.min(axis=0), raw.max(axis=0)
    return (raw - lo) / np.maximum(hi - lo, 1e-12)


class DataProducer(ProducerFunctionSkeleton):
    """Example producer (reference ``tests/run_ddl.py:107-167``): loads its
    shard lazily in the worker, refreshes by in-place shuffle."""

    def __init__(self, params: Params):
        self.params = params
        self._data: np.ndarray | None = None
        self._rng: np.random.Generator | None = None

    def on_init(self, producer_idx: int = 0, n_producers: int = 1,
                instance_idx: int = 0, n_instances: int = 1,
                **kwargs: Any) -> DataProducerOnInitReturn:
        p = self.params
        seed = instance_idx * 1000 + producer_idx
        self._data = make_pointwise_data(p.n_data, p.n_features, seed)
        self._rng = np.random.default_rng(seed + 1)
        return DataProducerOnInitReturn(
            nData=p.n_data,
            nValues=p.n_features,
            shape=(p.n_data, p.n_features),
            splits=(3, p.n_features - 4, 1),  # (pos, target, weight)
            dtype=np.float32,
        )

    def post_init(self, my_ary: np.ndarray, **kwargs: Any) -> None:
        np.copyto(my_ary, self._data)

    def execute_function(self, my_ary: np.ndarray, **kwargs: Any) -> None:
        assert self._rng is not None
        self._rng.shuffle(my_ary)  # in-place local shuffle per window


@distributed_dataloader
def main(params: Params, ddl_env: Any) -> int:
    """Consumer main (reference ``tests/run_ddl.py:171-238``): drain every
    epoch, verifying batch geometry and data integrity."""
    loader = DistributedDataLoader(
        data_producer_function=DataProducer(params),
        batch_size=params.batch_size,
        connection=ddl_env.connection,
        n_epochs=params.nepoch,
        output="numpy",
    )
    total_batches = 0
    for epoch in range(params.nepoch):
        for i, (pos, target, weight) in enumerate(loader):
            assert pos.shape == (params.batch_size, 3)
            assert target.shape == (params.batch_size, params.n_features - 4)
            assert weight.shape == (params.batch_size, 1)
            assert 0.0 <= float(pos[0, 0]) <= 1.0  # normalised
            total_batches += 1
            loader.mark(Marker.END_OF_BATCH)
        loader.mark(Marker.END_OF_EPOCH)
    expected = params.nepoch * (params.n_data // params.batch_size)
    assert total_batches == expected, (total_batches, expected)
    print(f"drained {total_batches} batches over {params.nepoch} epochs: OK")
    return total_batches


if __name__ == "__main__":
    logging.basicConfig(level=logging.INFO)
    if len(sys.argv) > 1:
        os.environ["DDL_TPU_MODE"] = sys.argv[1]
    main(Params())
