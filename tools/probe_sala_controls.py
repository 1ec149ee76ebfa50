#!/usr/bin/env python3
"""The controls behind the MiniCPM-SALA cell's limits, read on the chip
through the check as it is committed (PERF.md section 6, PR 39): every
stand-in for the system through ``families/minicpm_sala.py``'s
``compare_with_reference`` + ``problems_of`` - the reference computed in
float8_e4m3fn, and the system with a fault planted (the scan's state
carried in bfloat16; the decay left out, lam = 1; dense attention in place
of the sparse path; a selection a head instead of a group; the residual
scale taken from the cut's depth): each has to come back with problems.
``--which system`` reads the system itself (no problems); ``--which
f32_recurrence`` the system with the reference's float32 recurrence in the
scan kernels' place (a witness, not a fault).  ``--cores-only`` reads only
the two cores over the whole row (no weights, seconds a seed),
``--grads-only`` only the gradient leaves and the optimizer step on the
prefix, for as many ``--seed`` as given.

    chiprun -- python3 tools/probe_sala_controls.py --seed 2654435769
    chiprun -- python3 tools/probe_sala_controls.py --cores-only \
        --which system bf16_state --seed 11 12 13

``--skipped-update`` is the control behind the configuration's
``loss_tolerance``: the cell itself through ``benchmarks/run.py`` with a
Trainer whose optimizer throws every update away (the plain loop keeps its
own): the ``steady`` line's ``loss_rel_diff`` is the reading, and the last
line has to say ``correct: false``.

Needs a TPU (``--rehearsal cpu`` runs the control flow at the cell's tiny
size and proves nothing about the limits).
"""
import argparse
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
CELL = "minicpm-sala.tokens-16k"


def skipped_update(seed: int, rehearsal) -> int:
    """The cell with every update of the Trainer's thrown away."""
    import runpy

    import optax

    from ddl_tpu import trainer

    init = trainer.Trainer.__init__

    def skipping(self, *a, optimizer, **kw):
        init(self, *a, optimizer=optax.chain(optimizer, optax.scale(0.0)), **kw)

    trainer.Trainer.__init__ = skipping
    run = os.path.join(ROOT, "benchmarks", "run.py")
    sys.argv = [run, "--workload", CELL, "--seed", str(seed), "--seconds",
                "0.5" if rehearsal else "5", "--trace", "0"]
    if rehearsal:
        sys.argv += ["--rehearsal", rehearsal]
    runpy.run_path(run, run_name="__main__")
    return 0


def _peaks() -> dict:
    """The two lifetime peaks the runner adds up: arrays, and the largest
    program's temporaries."""
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return {k: stats.get(k) for k in ("peak_bytes_in_use", "peak_bytes_reserved")}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, nargs="+", default=[2654435769])
    ap.add_argument("--rehearsal", choices=("cpu",), default=None)
    ap.add_argument("--which", nargs="*", default=None)
    ap.add_argument("--cores-only", action="store_true")
    ap.add_argument("--grads-only", action="store_true")
    ap.add_argument("--skipped-update", action="store_true")
    args = ap.parse_args()
    if args.skipped_update:
        return skipped_update(args.seed[0], args.rehearsal)

    from ddl_tpu.bringup import bring_up

    bring_up(args.rehearsal)
    import jax.numpy as jnp

    from benchmarks.families import minicpm_sala as family
    from benchmarks.lib import cells

    cell = cells.load_cell(CELL, rehearsal=bool(args.rehearsal))
    cfg = family.model_config(cell.config, cell.mix)
    stand_ins = {"system": {}, "float8_e4m3fn": {"compute_dtype": jnp.float8_e4m3fn}}
    stand_ins.update(
        {fault: {"fault": fault} for fault in family.FAULTS + family.WITNESSES}
    )
    parts = family.PARTS
    if args.cores_only or args.grads_only:
        parts = ("cores",) * args.cores_only + ("gradients",) * args.grads_only
    refused = set(family.FAULTS) | {"float8_e4m3fn"}
    for name in args.which or sorted(refused):
        for seed in args.seed:
            t0 = time.monotonic()
            found = family.compare_with_reference(
                cfg, seed, parts=parts, **stand_ins[name]
            )
            # a part's problems; the whole check's to say that there are none
            problems = family.problems_of(found, bool(args.rehearsal))
            print(json.dumps({
                "line": "stand_in", "which": name, "seed": seed,
                "seconds": round(time.monotonic() - t0, 1),
                "peak_GiB": family._peak_gib(), "peak_bytes": _peaks(),
                "problems": problems, **found,
            }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
