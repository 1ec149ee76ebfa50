"""Stream-path bandwidth diagnosis: where does the link go?

Where a stream run sits well under the measured link, this probe
isolates the candidate sinks, each as achieved bytes/s vs the measured
link:

1. ``link``      — measure_h2d_bandwidth (64 MiB, page-warm numpy): the
                   denominator.
2. ``np-put``    — back-to-back window-size device_put from a regular
                   numpy buffer, host-synced per put: fixed per-transfer
                   cost at this window size.
3. ``np-put-af`` — same with 2 puts in flight (async, sync every other):
                   does transfer pipelining help on this attach?
4. ``shm-put``   — back-to-back puts sourcing a /dev/shm mmap buffer
                   (the ring-slot memory type): any shm-source penalty.
5. ``busy-put``  — np-put with a spinning python thread (a producer
                   refilling): host-CPU contention cost on 1-core hosts.
6. ``pipeline``  — the full bench stream config (producers + ring +
                   windows()): the end-to-end number under diagnosis.

Reading the table: if np-put ≈ link but pipeline ≪ np-put, the gap is
pipeline overhead (acquire/python/release) or producer contention
(compare busy-put); if np-put ≪ link, the gap is per-transfer cost at
this window size — try DDL_BENCH_STREAM_MIB=64/128; if shm-put ≪
np-put, ring-slot memory itself transfers slower (allocation fix).

Usage: python tools/probe_stream.py [window_mib=32] [reps=8]
"""

from __future__ import annotations

import mmap
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def _rate(nbytes: int, fn, reps: int) -> float:
    fn()  # warm
    t0 = time.perf_counter()
    for _ in range(reps):
        fn()
    return nbytes * reps / (time.perf_counter() - t0)


def main(window_mib: int = 32, reps: int = 8) -> None:
    import bench

    bench.bring_up()
    import jax

    from ddl_tpu.ingest import measure_h2d_bandwidth

    dev = jax.local_devices()[0]
    print(f"device: {dev.platform} {getattr(dev, 'device_kind', '?')}")
    link = measure_h2d_bandwidth()
    print(f"link (64 MiB warm numpy): {link / 1e9:.3f} GB/s")

    nbytes = window_mib << 20
    rows = nbytes // 1024
    buf = np.random.default_rng(0).random((rows, 256), np.float32)

    def sync_put(src):
        jax.block_until_ready(jax.device_put(src, dev))

    r = _rate(nbytes, lambda: sync_put(buf), reps)
    print(f"np-put   {window_mib:4d} MiB sync:      {r / 1e9:.3f} GB/s"
          f"  ({r / link:.2%} of link)")

    # Two transfers in flight (the stream's lookahead shape).  The final
    # drain happens INSIDE the timed window — an undrained tail would
    # inflate the rate by up to 1/reps, the transfer-timing artifact
    # class bench's _UTIL_GATE exists to reject.
    def run_2deep() -> float:
        jax.block_until_ready(jax.device_put(buf, dev))  # warm
        pend: list = []
        t0 = time.perf_counter()
        for _ in range(reps):
            pend.append(jax.device_put(buf, dev))
            if len(pend) >= 2:
                jax.block_until_ready(pend.pop(0))
        jax.block_until_ready(pend)
        return nbytes * reps / (time.perf_counter() - t0)

    r = run_2deep()
    print(f"np-put   {window_mib:4d} MiB 2-deep:    {r / 1e9:.3f} GB/s"
          f"  ({r / link:.2%} of link)")

    # /dev/shm mmap source — the ring slot memory type.
    fd = os.open(f"/dev/shm/ddl-probe-{os.getpid()}", os.O_CREAT | os.O_RDWR)
    try:
        os.ftruncate(fd, nbytes)
        mm = mmap.mmap(fd, nbytes)
        shm = np.frombuffer(mm, np.float32).reshape(rows, 256)
        shm[:] = buf
        r = _rate(nbytes, lambda: sync_put(shm), reps)
        print(f"shm-put  {window_mib:4d} MiB sync:      {r / 1e9:.3f} GB/s"
              f"  ({r / link:.2%} of link)")
    finally:
        os.close(fd)
        os.unlink(f"/dev/shm/ddl-probe-{os.getpid()}")

    # Host-CPU contention: a spinning thread standing in for a producer
    # refill happening during the transfer (the 1-core-host effect).
    stop = threading.Event()
    scratch = np.empty_like(buf)

    def burn():
        while not stop.is_set():
            np.copyto(scratch, buf)

    t = threading.Thread(target=burn, daemon=True)
    t.start()
    try:
        r = _rate(nbytes, lambda: sync_put(buf), reps)
    finally:
        stop.set()
        t.join()
    print(f"busy-put {window_mib:4d} MiB sync:      {r / 1e9:.3f} GB/s"
          f"  ({r / link:.2%} of link)")

    # Full pipeline at the same window size.
    os.environ["DDL_BENCH_STREAM_MIB"] = str(window_mib)
    import importlib

    import bench

    importlib.reload(bench)
    rate, ns = bench._run_ingest_stream(link, mode="thread")
    print(
        f"pipeline {window_mib:4d} MiB thread:    "
        f"{ns['ingest_bytes_per_sec'] / 1e9:.3f} GB/s"
        f"  ({ns.get('bandwidth_utilization', 0.0):.2%} of link)"
        f"  stall={ns['stall_fraction']:.4f}"
    )


if __name__ == "__main__":
    main(
        int(sys.argv[1]) if len(sys.argv) > 1 else 32,
        int(sys.argv[2]) if len(sys.argv) > 2 else 8,
    )
