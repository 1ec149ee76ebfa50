#!/usr/bin/env python3
"""How much work a seed gives the Trinity-Mini share (PERF.md section 6,
PR 30): the cell's own model and sizes, ``jit(value_and_grad)`` + adamw
as the Trainer's step has them, from the seed's weights and seeded
uniform ids, ``--steps`` steps a run.  A line a run: the wall time of
every step, and the share of each expert layer's choices that fell on the
held experts at every step.  The learning rate is an argument of the one
compiled program, so several are read in one call:

    chiprun -- python3 tools/probe_afmoe_seed_work.py \
        --runs 3e-4:11,22 3e-5:11,22,33

Needs a TPU (``--rehearsal cpu`` runs the control flow at the cell's tiny
size; its times say nothing).
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "trinity-mini.tokens-8k"


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--runs", nargs="+", required=True,
                    help="<learning rate>:<seed>,<seed>,...")
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--timed", default="8:36", help="steps of the rate")
    ap.add_argument("--rehearsal", choices=("cpu",), default=None)
    args = ap.parse_args()

    from ddl_tpu.bringup import bring_up

    bring_up(args.rehearsal)
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from benchmarks.families import afmoe as family
    from benchmarks.lib import cells
    from ddl_tpu.models.losses import next_token_cross_entropy

    cell = cells.load_cell(CELL, rehearsal=bool(args.rehearsal))
    c, mix = cell.config, cell.mix
    cfg = family.model_config(c, mix)
    first, count = cfg.held
    rows, seq = mix["batch_rows"], mix["seq"]
    lo, hi = (int(v) for v in args.timed.split(":"))

    def loss(p, t):
        logits, picks = family.model.forward_with_choices(p, t, cfg)
        return next_token_cross_entropy(logits, t), picks

    def step(p, o, t, lr):
        (value, picks), g = jax.value_and_grad(loss, has_aux=True)(p, t)
        u, o = optax.adamw(lr).update(g, o, p)
        mine = (picks >= first) & (picks < first + count)
        return optax.apply_updates(p, u), o, value, jnp.mean(
            mine.astype(jnp.float32), axis=(1, 2, 3)
        )

    step = jax.jit(step, donate_argnums=(0, 1))
    init = jax.jit(lambda key: family.init_params(cfg, key))
    for run in args.runs:
        lr, seeds = run.split(":")
        for seed in (int(s) for s in seeds.split(",")):
            p = init(jax.random.key(seed))
            o = optax.adamw(0.0).init(p)
            rng = np.random.default_rng([seed, 31])
            ms, held, losses = [], [], []
            for _ in range(args.steps):
                t = jnp.asarray(
                    rng.integers(0, cfg.vocab, (rows, seq), dtype=np.int32)
                )
                jax.block_until_ready(t)
                t0 = time.perf_counter()
                p, o, value, share = step(p, o, t, jnp.float32(float(lr)))
                losses.append(float(value))  # blocks
                ms.append(1e3 * (time.perf_counter() - t0))
                held.append(np.asarray(share))
            del p, o
            held = 100.0 * np.array(held)  # (steps, expert layers)
            timed = float(np.mean(ms[lo:hi]))
            print(json.dumps({
                "line": "seed_work", "lr": float(lr), "seed": seed,
                "timed_steps": [lo, hi], "step_ms": round(timed, 3),
                "tokens_per_s": round(1e3 * rows * seq / timed, 1),
                "held_share": round(float(held[lo:hi].mean()), 3),
                "held_share_first_step": round(float(held[0].mean()), 3),
                "held_share_by_layer_timed": [
                    round(float(v), 2) for v in held[lo:hi].mean(0)
                ],
                "loss_first_last": [losses[0], losses[-1]],
                "ms_by_step": [round(v, 1) for v in ms],
                "held_share_by_step": [round(float(v), 2) for v in held.mean(1)],
                "held_share_by_layer_last": [round(float(v), 2) for v in held[-1]],
            }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
