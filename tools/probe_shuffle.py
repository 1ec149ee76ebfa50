"""Probe: what does the device-side epoch exchange cost and buy here?

Prints, with no chips required (`make shuffle-dryrun`):

- the analytic exchange pricing (``plan_exchange``): for a sweep of
  ring widths and pool geometries, what one exchange round puts on ICI
  via the device tier vs what the HOST path's rendezvous boards carry
  raw and wire-encoded (the PR-13 int8 pricing composed on the host
  legs) — the numbers that decide whether the device tier is worth
  engaging for a deployment's geometry before ever touching a chip;
- a LIVE parity check: one small seeded exchange run through BOTH
  transports on the virtual mesh (the Pallas ring in interpret mode),
  asserting the post-exchange pools are byte-identical and that zero
  host fallbacks latched — the tentpole invariant, witnessed locally.

The mirror of ``tools/probe_ici.py`` / ``probe_wire.py`` for the
shuffle tier.  Throughput on the interpreted ring is NOT meaningful
(Python emulation); for measured bytes/s run ``DDL_BENCH_MODE=shuffle
python bench.py`` on a multi-chip TPU host.

On the CPU virtual mesh:

    DDL_BENCH_PLATFORM=cpu python tools/probe_shuffle.py
"""
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import numpy as np  # noqa: E402


def _pricing_sweep(n_devices: int) -> list:
    from ddl_tpu.ops.device_shuffle import plan_exchange

    rows = int(os.environ.get("DDL_PROBE_SHUFFLE_ROWS", "4096"))
    cols = int(os.environ.get("DDL_PROBE_SHUFFLE_COLS", "1024"))
    sweep = []
    for n in (2, 4, 8):
        for wire in (None, "int8"):
            p = plan_exchange(
                n, rows, cols, np.dtype(np.float32),
                wire_dtype=wire, n_devices=n_devices,
            )
            entry = {
                "n_instances": n,
                "exchange_rows": rows,
                "cols": cols,
                "wire_dtype": p["wire_dtype"],
                "plannable": p["plannable"],
                "ici_bytes": p["ici_bytes"],
                "host_bytes_raw": p["host_bytes_raw"],
                "host_bytes_wire": p["host_bytes_wire"],
            }
            if not p["plannable"]:
                entry["why_not"] = p["why_not"]
            else:
                # What the device tier saves vs the host boards as the
                # deployment would actually run them (wire-encoded).
                entry["ici_vs_host_wire"] = round(
                    p["ici_bytes"] / max(p["host_bytes_wire"], 1), 3
                )
            sweep.append(entry)
    return sweep


def _live_parity(impl: str) -> dict:
    """One seeded 4-ring exchange through both transports: the byte
    -identity witness, interpret-mode on the virtual mesh."""
    import threading

    from ddl_tpu.observability import Metrics
    from ddl_tpu.shuffle import (
        DeviceExchangeFabric,
        DeviceExchangeShuffler,
        Rendezvous,
        ThreadExchangeShuffler,
    )
    from ddl_tpu.types import Topology

    n, rows, cols, rounds, seed = 4, 64, 16, 2, 11

    def pools():
        rng = np.random.default_rng(5)
        return [
            rng.random((rows, cols)).astype(np.float32) for _ in range(n)
        ]

    def run(make):
        shufs = [make(i) for i in range(n)]
        errs = []

        def worker(i):
            try:
                for _ in range(rounds):
                    shufs[i].global_shuffle(arys[i])
            except Exception as e:  # noqa: BLE001 - joined + reported below
                errs.append(e)

        arys = pools()
        ts = [
            threading.Thread(target=worker, args=(i,)) for i in range(n)
        ]
        for t in ts:
            t.start()
        for t in ts:
            t.join(300)
        if errs:
            raise RuntimeError(f"exchange workers failed: {errs}")
        return arys, shufs

    rdv = Rendezvous()
    host_pools, _ = run(lambda i: ThreadExchangeShuffler(
        Topology(n_instances=n, instance_idx=i, n_producers=1),
        1, rows, rendezvous=rdv, seed=seed,
    ))
    fabric = DeviceExchangeFabric(impl=impl)
    metrics = [Metrics() for _ in range(n)]
    rdv2 = Rendezvous()

    def make_dev(i):
        sh = DeviceExchangeShuffler(
            Topology(n_instances=n, instance_idx=i, n_producers=1),
            1, rows, rendezvous=rdv2, fabric=fabric, seed=seed,
        )
        sh.metrics = metrics[i]
        return sh

    dev_pools, shufs = run(make_dev)
    fallbacks = sum(m.counter("shuffle.device_fallbacks") for m in metrics)
    return {
        "impl": impl,
        "n_instances": n,
        "rounds": rounds,
        "byte_identical": all(
            np.array_equal(host_pools[i], dev_pools[i]) for i in range(n)
        ),
        "device_rounds": int(sum(
            m.counter("shuffle.device_rounds") for m in metrics
        )),
        "fallbacks": int(fallbacks),
        "device_exchange_active": all(
            sh.device_exchange_active for sh in shufs
        ),
    }


def main():
    out: dict = {}
    try:
        import bench

        platform = bench.bring_up(cpu_devices=8)
        import jax

        n_dev = len(jax.devices())
        out["platform"] = platform
        out["n_devices"] = n_dev
        out["exchange_pricing"] = _pricing_sweep(n_dev)
        for impl in ("ring", "xla"):
            out[f"parity_{impl}"] = _live_parity(impl)
    except Exception as e:  # noqa: BLE001 - the probe must print regardless
        out["error"] = f"{type(e).__name__}: {e}"
    print(json.dumps(out, indent=1))
    if any(
        isinstance(v, dict) and v.get("byte_identical") is False
        for v in out.values()
    ):
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
