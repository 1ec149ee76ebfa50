#!/usr/bin/env python3
"""The controls behind the Olmo-Hybrid cell's limits, read on the chip
through the check as it is committed (PERF.md section 6, PR 36): every
stand-in for the system through ``families/olmo_hybrid.py``'s
``compare_with_reference`` + ``problems_of`` - the reference computed in
float8_e4m3fn, and the system with a fault of the gated delta rule planted
(the decay left out, alpha = 1; beta not doubled; the chunk-to-chunk state
carried in bfloat16): each has to come back with problems.  ``--which
system`` reads the system itself (no problems).  ``--core-only`` reads only
the linear mixer's core over the whole row (``compare_core``: no weights, a
few seconds a seed), for as many ``--seed`` as given.

    chiprun -- python3 tools/probe_gdn_controls.py --seed 2654435769
    chiprun -- python3 tools/probe_gdn_controls.py --core-only \
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
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "olmo-hybrid-7b.tokens-16k"


def skipped_update(seed: int, rehearsal) -> int:
    """The cell with every update of the Trainer's thrown away."""
    import runpy

    import optax

    from ddl_tpu import trainer

    init = trainer.Trainer.__init__

    def skipping(self, *a, optimizer, **kw):
        init(self, *a, optimizer=optax.chain(optimizer, optax.scale(0.0)), **kw)

    trainer.Trainer.__init__ = skipping
    run = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                       "benchmarks", "run.py")
    sys.argv = [run, "--workload", CELL, "--seed", str(seed), "--seconds",
                "0.5" if rehearsal else "5", "--trace", "0"]
    if rehearsal:
        sys.argv += ["--rehearsal", rehearsal]
    runpy.run_path(run, run_name="__main__")
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, nargs="+", default=[2654435769])
    ap.add_argument("--rehearsal", choices=("cpu",), default=None)
    ap.add_argument("--which", nargs="*", default=None)
    ap.add_argument("--core-only", action="store_true")
    ap.add_argument("--skipped-update", action="store_true")
    args = ap.parse_args()
    if args.skipped_update:
        return skipped_update(args.seed[0], args.rehearsal)

    from ddl_tpu.bringup import bring_up

    bring_up(args.rehearsal)
    import jax.numpy as jnp

    from benchmarks.families import olmo_hybrid as family
    from benchmarks.lib import cells

    cell = cells.load_cell(CELL, rehearsal=bool(args.rehearsal))
    cfg = family.model_config(cell.config, cell.mix)
    stand_ins = {"system": {}, "float8_e4m3fn": {"compute_dtype": jnp.float8_e4m3fn}}
    stand_ins.update({fault: {"fault": fault} for fault in family.FAULTS})
    for name in args.which or [n for n in stand_ins if n != "system"]:
        core = family.check_programs(cfg, **stand_ins[name])["core"]
        for seed in args.seed:
            t0 = time.monotonic()
            if args.core_only:
                found = family.compare_core(cfg, seed, core)
                problems = None  # the whole check's to say
            else:
                found = family.compare_with_reference(cfg, seed, **stand_ins[name])
                problems = family.problems_of(found, bool(args.rehearsal))
            print(json.dumps({
                "line": "stand_in", "which": name, "seed": seed,
                "seconds": round(time.monotonic() - t0, 1),
                "peak_GiB": family._peak_gib(), "problems": problems, **found,
            }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
