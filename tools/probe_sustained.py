"""Sustained-regime bandwidth A/B: raw device_put vs the full stream path.

A host-to-device link need not be stationary: a burst regime (caches,
credit, a rate limiter somewhere on the path) can give way to a lower
floor that itself drifts.  A measurement shorter than the burst reports
the burst rate; a longer one mixes regimes.  The framework-attributable
number is therefore the BRACKETED ratio

    utilization_sustained = stream_bytes_per_sec
                            / mean(raw_before, raw_after)

with raw sync puts of a malloc'd buffer measured immediately before AND
after the stream run (all in the floor regime, burst pre-drained).
Raw puts are the ceiling — no loader, no ring, no producer — and the
before/after disagreement ratio gauges how much the link drifted across
the measurement: when the brackets disagree by more than 1.25x, the
tool says so and the ratio should not be quoted.  Not measured on the
current machine.

Stages:
  1. drain   - back-to-back puts until a rate collapse is observed
               (adaptive count; at least 2 GiB for small windows);
               prints per-put rates, burst size, floor rate.
  2. raw     - 12 sync puts: the before-bracket ceiling.
  3. stream  - bench's windows() streaming config (16 timed windows of
               window_mib, DDL_BENCH_STREAM_MIB forced to match).
  4. raw     - 12 more sync puts: the after-bracket ceiling.

Usage: python tools/probe_sustained.py [window_mib]
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main() -> None:
    mib = int(sys.argv[1]) if len(sys.argv) > 1 else 32
    # Force the stream config to the probed window size — a leftover
    # exported DDL_BENCH_STREAM_MIB would otherwise make stage 3 an A/B
    # against a different transfer size.
    os.environ["DDL_BENCH_STREAM_MIB"] = str(mib)
    nbytes = mib << 20

    import bench

    bench.bring_up()
    import jax

    dev = jax.local_devices()[0]
    print(f"device: {dev.platform} {getattr(dev, 'device_kind', '?')}")

    buf = np.ones(nbytes, np.uint8)
    jax.block_until_ready(jax.device_put(buf, dev))  # warm/compile

    def timed_put() -> float:
        t0 = time.perf_counter()
        jax.block_until_ready(jax.device_put(buf, dev))
        return nbytes / (time.perf_counter() - t0)

    # Stage 1: drain until the collapse is SUSTAINED (two consecutive
    # puts under 40% of the early-burst median — robust to the single
    # transient dips seen mid-burst), with a floor of 2 GiB total so a
    # small window size cannot under-drain the ~860 MiB bucket, and a
    # hard cap so a limiter-less attach terminates.
    rates: list = []
    collapse_at = None
    max_puts = max((2 << 30) // nbytes, 64)
    while len(rates) < max_puts:
        rates.append(timed_put())
        if len(rates) >= 7 and collapse_at is None:
            burst_rate = float(np.median(rates[:5]))
            if rates[-1] < 0.4 * burst_rate and rates[-2] < 0.4 * burst_rate:
                collapse_at = len(rates) - 2
        if collapse_at is not None and len(rates) >= collapse_at + 10:
            break
    print("per-put GB/s:", " ".join(f"{r / 1e9:.2f}" for r in rates))
    if collapse_at is None:
        print(
            f"no collapse observed over {len(rates) * mib} MiB — "
            "attach looks limiter-free; bracketed ratio below is still valid."
        )
        burst_mib = len(rates) * mib
    else:
        burst_mib = collapse_at * mib
    floor = float(np.mean(rates[-8:]))
    print(f"burst bucket ~{burst_mib} MiB; floor {floor / 1e9:.3f} GB/s")

    def raw_bracket(k: int = 12) -> float:
        t0 = time.perf_counter()
        for _ in range(k):
            jax.block_until_ready(jax.device_put(buf, dev))
        return nbytes * k / (time.perf_counter() - t0)

    raw_before = raw_bracket()
    print(f"raw before: {raw_before / 1e9:.3f} GB/s")

    rate, ns = bench._run_ingest_stream(0.0, mode="thread")
    stream = ns["ingest_bytes_per_sec"]
    print(f"stream: {stream / 1e9:.3f} GB/s  stall={ns['stall_fraction']:.5f}")

    raw_after = raw_bracket()
    print(f"raw after: {raw_after / 1e9:.3f} GB/s")

    ceiling = (raw_before + raw_after) / 2
    drift = max(raw_before, raw_after) / max(min(raw_before, raw_after), 1.0)
    util = stream / ceiling
    print(f"bracket drift {drift:.2f}x; utilization_sustained = {util:.3f}")
    if drift > 1.25:
        print(
            "NOTE: brackets disagree by more than 1.25x — the limiter "
            "drifted across the measurement; do not quote this ratio."
        )
    print(json.dumps({
        "window_mib": mib,
        "burst_bucket_mib": burst_mib,
        "floor_bytes_per_sec": floor,
        "raw_before_bytes_per_sec": raw_before,
        "raw_after_bytes_per_sec": raw_after,
        "bracket_drift": drift,
        "stream_bytes_per_sec": stream,
        "stream_stall_fraction": ns["stall_fraction"],
        "utilization_sustained": util,
        "attributable": drift <= 1.25,
    }))


if __name__ == "__main__":
    main()
