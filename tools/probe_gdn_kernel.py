#!/usr/bin/env python3
"""The gated delta rule's kernels alone, and the whole scan, timed on the
chip at the Olmo-Hybrid cell's shape (one row of 16,384 positions, 30 heads
of 96 / 192, bfloat16; PERF.md section 6, PR 37 and PR 41).

    chiprun -- python3 tools/probe_gdn_kernel.py
    chiprun -- python3 tools/probe_gdn_kernel.py --whole-only   # any tree

Host clock around ``--reps`` calls of a jitted program that end in one
``block_until_ready``.  ``whole``: ``jax.grad`` of ``gated_delta_rule`` over
all heads, forward and backward in one program (the two head-major
transposes, the decay sums and both kernels); the same line from a parent's
checkout (``--whole-only``: the kernels' signatures differ from tree to
tree) is the comparison.  ``kernels``: a program that is one call of
``ddl_gdn_fwd`` / ``ddl_gdn_bwd`` - since PR 41 a chunk from q, k, v, its
decay sums and beta to o, and back, a tile of two chunks a grid step - on
seeded operands of one group of heads (what a grid step holds) and of all of
them (what a linear layer calls once a pass).  The cell's own trace reads the kernels' device time
well under this line's - 2.3x under for PR 37's kernels, 1.7x for PR 41's
(ROADMAP M14; PERF.md section 7, PR 37 and PR 41): a lead for comparing
builds, not the kernels' time.

Needs a TPU (``--rehearsal cpu``: a tiny shape in interpret mode, timings
that mean nothing).
"""
import argparse
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def timed(fn, args, reps):
    """Milliseconds a call, after one that compiles."""
    import jax

    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t0) / reps


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=3700000037)
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--rehearsal", choices=("cpu",), default=None)
    ap.add_argument("--whole-only", action="store_true")
    args = ap.parse_args()

    from ddl_tpu.bringup import bring_up

    bring_up(args.rehearsal)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ddl_tpu.ops import gated_delta

    B, T, H, dk, dv = (1, 256, 4, 16, 32) if args.rehearsal else (1, 16384, 30, 96, 192)
    dt = jnp.bfloat16
    r = np.random.default_rng(args.seed)
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)
    q = jnp.asarray(unit(r.standard_normal((B, T, H, dk))) / np.sqrt(dk), dt)
    k = jnp.asarray(unit(r.standard_normal((B, T, H, dk))), dt)
    v = jnp.asarray(r.standard_normal((B, T, H, dv)), dt)
    g = jnp.asarray(-np.exp(r.uniform(np.log(1e-3), np.log(0.5), (B, T, H))), jnp.float32)
    beta = jnp.asarray(2.0 / (1.0 + np.exp(-r.standard_normal((B, T, H)))), jnp.float32)
    device = jax.devices()[0]
    where = {"platform": device.platform, "device_kind": device.device_kind}

    grad = jax.jit(jax.grad(
        lambda *a: jnp.sum(gated_delta.gated_delta_rule(*a).astype(jnp.float32)),
        argnums=range(5),
    ))
    t0 = time.perf_counter()
    ms = timed(grad, (q, k, v, g, beta), max(2, args.reps // 4))
    print(json.dumps({"line": "whole", "fwd_bwd_ms": ms, "shape": [B, T, H, dk, dv],
                      "first_call_and_reps_s": time.perf_counter() - t0, **where}),
          flush=True)
    if args.whole_only:
        return 0

    C = gated_delta._chunk_len(T)
    P, interpret = gated_delta._tile_len(T, C), bool(args.rehearsal)
    tiles = T // P
    fwd = jax.jit(lambda *a: gated_delta._forward(*a, C, interpret))
    bwd = jax.jit(lambda *a: gated_delta._chunks_bwd(C, interpret, a[:-1], a[-1]))
    for G in sorted({B * gated_delta._heads_per_pass(B, T, H), B * H}):
        # head-major tiles, as ``gated_delta_rule`` lays its operands out
        cut = lambda x, span=P: jnp.moveaxis(x, 2, 1)[:, : G // B].reshape(
            (G, -1, span) + x.shape[3:])
        gam = jnp.cumsum(cut(g, C), axis=-1).reshape(G, tiles, P)
        ins = (cut(q), cut(k), cut(v), jnp.stack([gam, cut(beta)], axis=2))
        d_o = jnp.asarray(0.1 * r.standard_normal((G, tiles, P, dv)), dt)
        fwd_ms = timed(fwd, ins, args.reps)
        bwd_ms = timed(bwd, ins + (fwd(*ins)[1], d_o), args.reps)
        heads = gated_delta._heads_per_step(G, P, dk, dv, q.dtype.itemsize)
        print(json.dumps({
            "line": "kernels", "rows": G, "grid": [G // heads, tiles],
            "fwd_ms_a_call": fwd_ms, "bwd_ms_a_call": bwd_ms,
            "ms_a_step_3_layers": 3 * (B * H // G) * (fwd_ms + bwd_ms), **where,
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
