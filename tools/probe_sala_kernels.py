#!/usr/bin/env python3
"""The fixed-decay scan and the block-sparse attention kernels alone, held
to plain forms and timed on the chip at the MiniCPM-SALA cell's shape (one
row of 16,384 positions, 32 heads of 128, 2 key-value heads; PERF.md
section 6, PR 39).

    chiprun -- python3 tools/probe_sala_kernels.py
    chiprun -- python3 tools/probe_sala_kernels.py --tiles 64,128,256

``check``: float32 operands, the whole row: ``lightning_attention`` against
a ``lax.scan`` over positions, ``sparse_attention`` against a masked softmax
a block of queries at a time with the same selection - outputs and input
gradients, root mean square of the differences over the reference's.
``time``: bfloat16, host clock around ``--reps`` calls of a jitted program
that end in one ``block_until_ready``: the scan forward and with its
backward; the selection; the sparse kernels forward and with their backward
for each ``--tiles`` (positions a query tile), with the mean length of a
tile's merged list as a share of its causal blocks.  A lead for comparing
builds: the cell's trace has the kernels' device time.

Needs a TPU (``--rehearsal cpu``: a tiny shape in interpret mode).
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


def say(**line):
    print(json.dumps(line), flush=True)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=3900000039)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--tiles", default="128")
    ap.add_argument("--skip-check", action="store_true")
    ap.add_argument("--rehearsal", choices=("cpu",), default=None)
    args = ap.parse_args()

    from ddl_tpu.bringup import bring_up

    bring_up(args.rehearsal)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.lib import minicpm_sala_reference as reference
    from ddl_tpu.ops import sparse_attention as sa
    from ddl_tpu.ops.lightning_attention import lightning_attention, slopes

    if args.rehearsal:
        T, H, G, D = 256, 4, 2, 16
        sc = sa.SparseConfig(block=16, kernel=8, stride=4, topk=4, local_blocks=2)
    else:
        T, H, G, D = 16384, 32, 2, 128
        sc = sa.SparseConfig()
    rng = np.random.default_rng([args.seed, 39])
    normal = lambda *shape: jnp.asarray(rng.standard_normal(shape, dtype=np.float32))
    q, w = normal(1, T, H, D), normal(1, T, H, D)
    k, v = normal(1, T, G, D), normal(1, T, G, D)
    kl, vl = normal(1, T, H, D), normal(1, T, H, D)
    rel = lambda a, b: float(jnp.sqrt(jnp.sum((a - b) ** 2) / jnp.sum(b**2)))

    if not args.skip_check:
        with jax.default_matmul_precision("highest"):
            got = jax.jit(lambda *a: jax.vjp(lightning_attention, *a[:3])[1](a[3]))(
                q, kl, vl, w
            )
            o = jax.jit(lightning_attention)(q, kl, vl)
            want_o, pull = jax.vjp(
                lambda q, k, v: reference.lightning(q, k, v, slopes(H), 128, True), q, kl, vl
            )
            say(line="lightning_check", o=rel(o, want_o),
                **{n: rel(a, b) for n, a, b in zip(("dq", "dk", "dv"), got, pull(w))})
            sel = jax.jit(lambda q, k: sa.select_blocks(q, k, sc))(q, k)
            o, pull_got = jax.vjp(
                jax.jit(lambda q, k, v: sa.sparse_attention(q, k, v, sel)), q, k, v
            )
            seen = jnp.moveaxis(sel.visible[:, :, :T, : -(-T // sc.block)] > 0.5, 1, 2)
            want_o, pull = jax.vjp(
                lambda q, k, v: reference.sparse_attention(
                    q, k, v, seen, sc.block, 256, True
                ),
                q, k, v,
            )
            say(line="sparse_check", o=rel(o, want_o),
                **{n: rel(a, b) for n, a, b in zip(("dq", "dk", "dv"), pull_got(w), pull(w))})

    bf = lambda *xs: tuple(x.astype(jnp.bfloat16) for x in xs)
    q, k, v, kl, vl, w = bf(q, k, v, kl, vl, w)
    loss = lambda f: (lambda *a: jnp.sum(f(*a[:-1]).astype(jnp.float32) * a[-1]))
    say(line="lightning_ms",
        fwd=timed(jax.jit(lightning_attention), (q, kl, vl), args.reps),
        fwd_bwd=timed(
            jax.jit(jax.grad(loss(lightning_attention), argnums=(0, 1, 2))),
            (q, kl, vl, w), args.reps,
        ))
    say(line="select_ms", scores=timed(
        jax.jit(lambda q, k: sa.block_scores(q, k, sc)), (q, k), args.reps
    ), whole=timed(
        jax.jit(lambda q, k: sa.select_blocks(q, k, sc)), (q, k), args.reps
    ))
    for tile in (int(t) for t in args.tiles.split(",")):
        sa._TILE_Q = tile  # the kernels' constant: a sweep is a build apiece
        sel = jax.jit(lambda q, k: sa.select_blocks(q, k, sc))(q, k)
        tiles = T // min(tile, T)
        causal = np.minimum((np.arange(tiles) + 1) * (min(tile, T) // sc.block),
                            -(-T // sc.block))
        attend = lambda q, k, v: sa.sparse_attention(q, k, v, sel)
        say(line="sparse_ms", tile_q=tile,
            listed_share=float(np.sum(np.asarray(sel.counts)) / (G * causal.sum())),
            fwd=timed(jax.jit(attend), (q, k, v), args.reps),
            fwd_bwd=timed(
                jax.jit(jax.grad(loss(attend), argnums=(0, 1, 2))), (q, k, v, w),
                args.reps,
            ))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
