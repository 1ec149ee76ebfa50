#!/usr/bin/env python3
"""The controls behind the Trinity-Mini cell's limits, read on the chip
through the check as it is committed (PERF.md section 6, PR 30):

1. both stand-ins for the system through ``families/afmoe.py``'s
   ``compare_with_reference`` + ``problems_of`` - the reference computed in
   float8_e4m3fn, and the system with the window ignored: each has to come
   back with problems;
2. the fault ``loss_tolerance`` is there to catch: a Trainer that throws
   its updates away.  The plain loop's two first-window losses against the
   same two batches at the INITIAL weights, as a relative difference of
   the window's mean - the number the runner would compare.  It scales
   with the learning rate: 1.72e-4 was read at ``--learning-rate 3e-4``;
   the default is the configuration's own (3e-5 since the third session).

    chiprun -- python3 tools/probe_afmoe_controls.py --seed 2654435769

Needs a TPU (``--rehearsal cpu`` runs the control flow at the cell's tiny
size and proves nothing about the limits).
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
    ap.add_argument("--seed", type=int, default=2654435769)
    ap.add_argument("--learning-rate", type=float, default=None)
    ap.add_argument("--rehearsal", choices=("cpu",), default=None)
    args = ap.parse_args()

    from ddl_tpu.bringup import bring_up

    bring_up(args.rehearsal)
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from benchmarks.families import afmoe as family
    from benchmarks.lib import cells, producers, reference
    from ddl_tpu.parallel.mesh import make_mesh

    cell = cells.load_cell(CELL, rehearsal=bool(args.rehearsal))
    c, mix = cell.config, cell.mix
    cfg = family.model_config(c, mix)
    rehearsal = bool(args.rehearsal)

    for name, kw in (
        ("float8_e4m3fn", {"compute_dtype": jnp.float8_e4m3fn}),
        ("window_ignored", {"window_ignored": True}),
    ):
        t0 = time.monotonic()
        found = family.compare_with_reference(cfg, args.seed, **kw)
        print(json.dumps({
            "line": "stand_in", "which": name, "seed": args.seed,
            "seconds": round(time.monotonic() - t0, 1),
            "peak_GiB": family._peak_gib(),
            "problems": family.problems_of(found, rehearsal), **found,
        }), flush=True)

    # The runner's own first window and weights (benchmarks/run.py,
    # steps 1 and 2), without the family's hook running its check again.
    sizes = family.sizes(c, mix)
    mesh = make_mesh(dict(mix["mesh"]), devices=jax.devices()[: cell.chips])
    loss_fn = lambda p, b: family.model.next_token_loss(p, b[0], cfg)  # noqa: E731
    rate = args.learning_rate or c["training"]["learning_rate"]
    optimizer = optax.adamw(rate)
    params = jax.jit(lambda key: family.init_params(cfg, key))(
        jax.random.key(args.seed)
    )
    first = producers.host_window(mix, sizes, args.seed, 1, 0).reshape(
        mix["window_rows"] // mix["batch_rows"], mix["batch_rows"], -1
    )
    batch_sh = NamedSharding(mesh, P(("dp",)))
    batches = [(jax.device_put(step, batch_sh),) for step in first]
    untrained = [float(jax.jit(loss_fn)(params, b)) for b in batches]
    replicated = NamedSharding(mesh, P())
    trained, _ = reference.first_window_losses(
        loss_fn, optimizer, jax.device_put(params, replicated), batches,
        replicated,
    )
    want = sum(trained) / len(trained)
    got = sum(untrained) / len(untrained)
    print(json.dumps({
        "line": "updates_thrown_away", "seed": args.seed, "learning_rate": rate,
        "plain_loop_losses": trained, "losses_at_initial_weights": untrained,
        "window_loss_rel_diff": abs(got - want) / abs(want),
        "loss_tolerance": c["loss_tolerance"]["relative"],
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
