"""Probe: where does ingest time go on this attach?

Times the primitive costs that bound the loader->HBM pipeline so the
ingest design (batch-level vs window-level transfers) is chosen from
measurements, not guesses.  Run on the bench chip:

    python tools/probe_ingest.py
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

    bench.bring_up()
    import jax
    import jax.numpy as jnp

    dev = jax.local_devices()[0]
    r = {"device": str(dev)}

    # 1. device_put sizes: fixed overhead vs bandwidth
    for label, nbytes in [("8KiB", 8 << 10), ("2MiB", 2 << 20),
                          ("8MiB", 8 << 20), ("64MiB", 64 << 20)]:
        buf = np.ones(nbytes, np.uint8)
        jax.block_until_ready(jax.device_put(buf, dev))
        dt = best(5, lambda: jax.block_until_ready(jax.device_put(buf, dev)))
        r[f"put_{label}_ms"] = round(dt * 1e3, 3)
        r[f"put_{label}_GBps"] = round(nbytes / dt / 1e9, 3)

    # 2. async put chain: N 2MiB puts enqueued then one sync (pipelined?)
    bufs = [np.ones(2 << 20, np.uint8) for _ in range(8)]
    def chain():
        outs = [jax.device_put(b, dev) for b in bufs]
        jax.block_until_ready(outs)
    chain()
    dt = best(5, chain)
    r["put_8x2MiB_chain_ms"] = round(dt * 1e3, 3)
    r["put_8x2MiB_chain_GBps"] = round(len(bufs) * (2 << 20) / dt / 1e9, 3)

    # 3. jit dispatch overhead (tiny op, eager call)
    x = jax.device_put(np.ones((8, 8), np.float32), dev)
    f = jax.jit(lambda a: a + 1)
    jax.block_until_ready(f(x))
    dt = best(20, lambda: jax.block_until_ready(f(x)))
    r["jit_tiny_roundtrip_ms"] = round(dt * 1e3, 3)
    # enqueue-only cost (no sync)
    t0 = time.perf_counter()
    for _ in range(100):
        y = f(x)
    r["jit_tiny_enqueue_us"] = round((time.perf_counter() - t0) * 1e4, 1)
    jax.block_until_ready(y)

    # 4. host-side costs at bench geometry
    win = np.random.default_rng(0).random((8192, 256)).astype(np.float32)
    r["copy_8MiB_ms"] = round(best(5, lambda: np.array(win, copy=True)) * 1e3, 3)
    rng = np.random.default_rng(1)
    r["shuffle_8MiB_ms"] = round(best(3, lambda: rng.shuffle(win)) * 1e3, 3)

    # 5. device-side slice-consume: one jit over a whole window
    dwin = jax.device_put(win.reshape(4, 2048, 256), dev)
    @jax.jit
    def consume(w):
        x = w[:, :, :-1]
        y = w[:, :, -1:]
        return (jnp.einsum("bij,bkj->", x, x) + y.sum())
    jax.block_until_ready(consume(dwin))
    dt = best(5, lambda: jax.block_until_ready(consume(dwin)))
    r["consume_window_jit_ms"] = round(dt * 1e3, 3)

    print(json.dumps(r))


if __name__ == "__main__":
    main()
