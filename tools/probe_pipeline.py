"""Probe: pipeline-only ingest rate (no device), vs device variants.

Separates the host pipeline (producers filling rings, consumer draining)
from the HBM transfer so the bottleneck is identified by measurement.

    python tools/probe_pipeline.py [thread|process]
"""
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import numpy as np  # noqa: E402

import bench  # noqa: E402
from bench import BATCH, EPOCHS_MEASURED, N_DATA, BenchProducer  # noqa: E402


def run(mode, output, compute, use_prefetch, n_producers=2, nslots=2):
    import jax

    from ddl_tpu import DistributedDataLoader, Marker, distributed_dataloader
    from ddl_tpu.observability import Metrics

    f = bench._consumer_compute() if compute else None
    metrics = Metrics()
    n_epochs = EPOCHS_MEASURED + 2

    @distributed_dataloader(n_producers=n_producers, mode=mode, nslots=nslots)
    def main(env):
        loader = DistributedDataLoader(
            BenchProducer(), batch_size=BATCH, connection=env.connection,
            n_epochs=n_epochs, output=output, metrics=metrics,
        )
        t0 = None
        samples = 0
        out = None
        for epoch in range(n_epochs):
            if epoch == 2:
                if out is not None:
                    jax.block_until_ready(out)
                metrics.reset()
                t0 = time.perf_counter()
                samples = 0
            it = loader.prefetch(2) if use_prefetch else loader
            for x, y in it:
                if f is not None:
                    out = f(x, y)
                if t0 is not None:
                    samples += BATCH
                loader.mark(Marker.END_OF_BATCH)
            loader.mark(Marker.END_OF_EPOCH)
        if out is not None:
            jax.block_until_ready(out)
        return samples / (time.perf_counter() - t0)

    rate = main()
    return {
        "samples_per_sec": round(rate, 1),
        "window_ms": round(N_DATA / rate * 1e3, 2),
        "stall_fraction": round(metrics.stall_fraction(), 5),
        "consumer_wait_s": round(metrics.counter("consumer.wait_s") or 0.0, 4),
    }


def main():
    bench.bring_up()
    mode = sys.argv[1] if len(sys.argv) > 1 else "thread"
    out = {"mode": mode}
    out["numpy_nocompute"] = run(mode, "numpy", False, False)
    out["numpy_compute_cpuskip"] = None  # numpy+compute mixes devices; skip
    out["jax_nopf"] = run(mode, "jax", True, False)
    out["jax_pf2"] = run(mode, "jax", True, True)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
