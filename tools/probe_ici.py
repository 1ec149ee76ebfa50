"""Probe: what does the ICI fan-out actually move on this attach?

Runs the Pallas ring kernels (ddl_tpu/ops/ici_fanout.py) on whatever
devices exist — real remote DMA on a TPU pod, ``interpret=True`` on the
CPU virtual mesh — and prints per-hop bytes/s for both fan-out modes at
a sweep of window sizes, plus one full redistribution (plan + legs)
through :class:`~ddl_tpu.parallel.ici.IciDistributor`.  The mirror of
``tools/probe_ingest.py`` for the post-H2D hop: the numbers that decide
whether the device-side tier beats the XLA scatter on a given topology.

Run on the bench chip (or `make ici-dryrun` for the CPU virtual mesh):

    python tools/probe_ici.py
"""
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import numpy as np  # noqa: E402


def best(n, fn):
    out = []
    for _ in range(n):
        t0 = time.perf_counter()
        fn()
        out.append(time.perf_counter() - t0)
    return min(out)


def main():
    import bench

    platform = bench.bring_up(cpu_devices=8)
    import jax

    from ddl_tpu.ops import ici_fanout
    from ddl_tpu.parallel.ici import IciDistributor

    devices = tuple(jax.devices())
    n_dev = len(devices)
    r = {
        "platform": platform,
        "n_devices": n_dev,
        "device_kind": getattr(devices[0], "device_kind", "cpu"),
        "interpret": ici_fanout.interpret_default(devices),
    }
    if n_dev < 2:
        r["error"] = "need >= 2 devices for a fan-out ring"
        print(json.dumps(r))
        return
    link = bench._peak_ici_link(r["device_kind"]) if platform == "tpu" else None
    r["link_spec_bytes_per_s"] = link

    cols = 256
    sizes = [("2MiB", 2 << 20), ("8MiB", 8 << 20), ("64MiB", 64 << 20)]
    if r["interpret"]:
        # Interpret mode simulates every DMA in Python, and deadlocks on
        # kernel operands past ~100 KiB when the ring is as wide as the
        # host has cores (ops/ici_fanout.py) — probe small.
        sizes = [("32KiB", 32 << 10), ("64KiB", 64 << 10)]
    for label, nbytes in sizes:
        rows = max(n_dev, nbytes // (cols * 4) // n_dev * n_dev)
        x = np.random.default_rng(0).random((rows, cols)).astype(np.float32)
        blk = jax.device_put(x, devices[0])
        jax.block_until_ready(blk)
        for mode, fn in (
            ("replicate", lambda: ici_fanout.fanout_replicate(blk, devices)),
            ("shard", lambda: ici_fanout.fanout_shard(blk, devices)),
        ):
            jax.block_until_ready(fn())  # compile
            dt = best(5, lambda: jax.block_until_ready(fn()))
            wire = ici_fanout.wire_bytes(mode, x.nbytes, n_dev)
            per_hop = wire / n_dev / dt
            r[f"{mode}_{label}_ms"] = round(dt * 1e3, 3)
            r[f"{mode}_{label}_hop_GBps"] = round(per_hop / 1e9, 3)
            if link:
                r[f"{mode}_{label}_link_util"] = round(per_hop / link, 4)

    # One full redistribution: plan + fan-out + finish legs onto the
    # dp-sharded target (what DeviceIngestor._transfer dispatches).
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    sharding = NamedSharding(Mesh(np.array(devices), ("dp",)), P("dp"))
    dist = IciDistributor(sharding)
    label, nbytes = sizes[-1]
    rows = max(n_dev, nbytes // (cols * 4) // n_dev * n_dev)
    x = np.random.default_rng(1).random((rows, cols)).astype(np.float32)
    blk = jax.device_put(x, dist.anchor(x.shape, x.dtype))
    jax.block_until_ready(blk)
    jax.block_until_ready(dist.distribute(blk))  # compile
    dt = best(5, lambda: jax.block_until_ready(dist.distribute(blk)))
    # A latch at ANY point (warmup or mid-loop) means some timed reps
    # silently ran the xla fallback — plan-derived wire rates would be
    # fabricated (bytes the kernel never moved), so report only the
    # fault flag, mirroring bench.py's refusal to publish them.
    r["redistribute_faulted"] = dist.faulted
    if not dist.faulted:
        plan = dist.plan(x.shape, x.dtype)
        r[f"redistribute_{label}_ms"] = round(dt * 1e3, 3)
        r[f"redistribute_{label}_hop_GBps"] = round(
            plan.wire_bytes / n_dev / dt / 1e9, 3
        )
        r["redistribute_peak_factor"] = round(plan.peak_factor, 3)

    print(json.dumps(r))


if __name__ == "__main__":
    main()
