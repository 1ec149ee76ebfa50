"""Isolate the /dev/shm device_put penalty and test the staged-copy cure.

tools/probe_stream.py measured (TPU v5 attach, 2026-07-31): np-put from a
malloc'd numpy buffer reaches ~95% of the measured link while the SAME
bytes sourced from a /dev/shm mmap reached 23-45%.  Two findings shaped
this probe's design (docs/PERF_NOTES.md):

- ``madvise(MADV_HUGEPAGE)`` on the shmem mapping is actively HARMFUL:
  it slowed every later access to that mapping ~4x on the 1-core attach
  (khugepaged churn), which also poisoned the first version of this
  probe's staged-copy measurements.  Not attempted here.
- Sequential one-shot measurements drift on this attach (each successive
  bench measured slower than the last).  This probe interleaves all
  variants round-robin and prints per-round numbers so drift shows up as
  rounds disagreeing, not as a fake treatment effect.

Variants:
  np-put      device_put from a malloc'd (THP-backed) numpy buffer
  shm-put     device_put from the /dev/shm mmap (the ring's native path)
  staged      memcpy shm -> reusable malloc staging buffer, then put
  staged-2d   staged with 2 buffers, put k async while copying k+1

Usage: python tools/probe_shm_put.py [window_mib] [rounds]
"""

from __future__ import annotations

import mmap
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def shm_buffer(nbytes: int):
    """An anonymous /dev/shm-backed mapping, as the ring allocates."""
    f = tempfile.NamedTemporaryFile(dir="/dev/shm", delete=False)
    try:
        f.truncate(nbytes)
        mm = mmap.mmap(f.fileno(), nbytes)
    finally:
        f.close()
        os.unlink(f.name)
    arr = np.frombuffer(mm, dtype=np.uint8)
    arr[:] = 1
    return mm, arr


def main() -> None:
    mib = int(sys.argv[1]) if len(sys.argv) > 1 else 32
    rounds = int(sys.argv[2]) if len(sys.argv) > 2 else 4
    reps = 6
    nbytes = mib << 20

    import bench

    bench.bring_up()
    import jax

    dev = jax.local_devices()[0]
    print(f"device: {dev.platform} {getattr(dev, 'device_kind', '?')}")

    from ddl_tpu.ingest import measure_h2d_bandwidth

    link = measure_h2d_bandwidth(64 << 20, dev)
    print(f"link (64 MiB warm numpy): {link / 1e9:.3f} GB/s")

    np_src = np.ones(nbytes, np.uint8)
    _mm, shm_arr = shm_buffer(nbytes)
    staging = np.empty(nbytes, np.uint8)
    stag2 = [np.empty(nbytes, np.uint8) for _ in range(2)]

    def t_np_put() -> float:
        t0 = time.perf_counter()
        for _ in range(reps):
            jax.block_until_ready(jax.device_put(np_src, dev))
        return time.perf_counter() - t0

    def t_shm_put() -> float:
        t0 = time.perf_counter()
        for _ in range(reps):
            jax.block_until_ready(jax.device_put(shm_arr, dev))
        return time.perf_counter() - t0

    def t_staged() -> float:
        t0 = time.perf_counter()
        for _ in range(reps):
            np.copyto(staging, shm_arr)
            jax.block_until_ready(jax.device_put(staging, dev))
        return time.perf_counter() - t0

    def t_staged_2d() -> float:
        pend = []
        t0 = time.perf_counter()
        for i in range(reps):
            buf = stag2[i % 2]
            np.copyto(buf, shm_arr)
            pend.append(jax.device_put(buf, dev))
            if len(pend) > 1:
                jax.block_until_ready(pend.pop(0))
        for p in pend:
            jax.block_until_ready(p)
        return time.perf_counter() - t0

    def t_memcpy() -> float:
        t0 = time.perf_counter()
        for _ in range(reps):
            np.copyto(staging, shm_arr)
        return time.perf_counter() - t0

    variants = [
        ("np-put", t_np_put),
        ("shm-put", t_shm_put),
        ("staged", t_staged),
        ("staged-2d", t_staged_2d),
        ("memcpy", t_memcpy),
    ]
    for _, fn in variants:
        fn()  # one full warm round (compiles, faults, allocator)

    results: dict = {name: [] for name, _ in variants}
    for r in range(rounds):
        for name, fn in variants:
            gbs = nbytes * reps / fn() / 1e9
            results[name].append(gbs)
        print(
            f"round {r}: "
            + "  ".join(f"{n}={results[n][-1]:.3f}" for n, _ in variants)
            + "  GB/s"
        )

    print("\nbest-of-rounds (GB/s, % of link):")
    for name, _ in variants:
        best = max(results[name])
        print(f"  {name:10s} {best:7.3f}  ({best * 1e9 / link * 100:6.2f}%)")


if __name__ == "__main__":
    main()
