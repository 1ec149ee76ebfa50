"""On-chip attention microbenchmark: flash (Pallas) vs dense (XLA).

It drives the SAME measurement harness the benchmark publishes from
(``bench.attn_measure`` — chained in-jit iterations, host read-back per
timed call), so re-running this tool reproduces the bench's
``attn_sweep`` block directly, plus an optional block-size sweep for
kernel tuning.

Usage:  python tools/probe_attn.py [--seqs 2048,4096,8192] [--blocks]
Writes one JSON line per config to stdout.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench as _bench  # noqa: E402

_bench.bring_up()  # before anything below touches a device
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench import (  # noqa: E402
    ATTN_D,
    ATTN_H,
    ATTN_HKV,
    attn_measure,
    sweep_batch,
)


def dispatch_overhead_ms(steps=5):
    """Round-trip cost of dispatch + scalar read-back for a trivial op.

    Any per-call timing is noise-floored by it, which is why
    ``attn_measure`` amortises real kernel work over chained in-jit
    iterations.
    """
    x = jnp.ones((8, 128), jnp.float32)

    @jax.jit
    def f(x):
        return jnp.sum(x * 1.000001)

    _ = float(f(x))
    times = []
    for _ in range(steps):
        t0 = time.perf_counter()
        _ = float(f(x))
        times.append(time.perf_counter() - t0)
    return sorted(times)[len(times) // 2] * 1e3


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seqs", default="2048,4096,8192")
    ap.add_argument("--blocks", action="store_true",
                    help="sweep flash block sizes at T=2048")
    ap.add_argument("--steps", type=int, default=3,
                    help="timed calls per config (minimum reported)")
    args = ap.parse_args()

    dev = jax.devices()[0]
    print(json.dumps({
        "device_kind": dev.device_kind, "platform": dev.platform,
        "geometry": {"H": ATTN_H, "Hkv": ATTN_HKV, "D": ATTN_D},
        "dispatch_overhead_ms": round(dispatch_overhead_ms(), 2),
    }), flush=True)

    for T in [int(s) for s in args.seqs.split(",")]:
        B = sweep_batch(T)
        for impl in ("dense", "flash"):
            try:
                dt = attn_measure(impl, B, T, steps=args.steps)
                r = {"impl": impl, "B": B, "T": T,
                     "ms": round(dt * 1e3, 3)}
            except Exception as e:  # noqa: BLE001
                r = {"impl": impl, "B": B, "T": T,
                     "error": f"{type(e).__name__}: {e}"[:200]}
            print(json.dumps(r), flush=True)

    if args.blocks:
        T = 2048
        B = sweep_batch(T)
        for bq in (128, 256, 512):
            for bk in (128, 256, 512, 1024):
                try:
                    dt = attn_measure("flash", B, T, block_q=bq,
                                      block_k=bk, steps=args.steps)
                    r = {"impl": "flash", "T": T, "block_q": bq,
                         "block_k": bk, "ms": round(dt * 1e3, 3)}
                except Exception as e:  # noqa: BLE001
                    r = {"impl": "flash", "T": T, "block_q": bq,
                         "block_k": bk,
                         "error": f"{type(e).__name__}: {e}"[:200]}
                print(json.dumps(r), flush=True)


if __name__ == "__main__":
    main()
