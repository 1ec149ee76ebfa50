#!/usr/bin/env python3
"""Read the Xing4.0-29B-A4B cell's reference check and its stand-ins through
the committed adapter (``benchmarks/families/xing4.py``), at the cell's own
sizes on whatever backend JAX finds (the chip through ``chiprun``; the CPU
with ``--rehearsal`` at the cell's tiny sizes).

    python3 tools/probe_xing4_controls.py [--seeds 1,2] [--which system,float8,...] [--rehearsal]

``system``: the model as configured; ``float8`` / ``bfloat16``: the reference
computed in that precision in the system's place; the faults of
``xing4.FAULTS`` (``skipped_update`` and ``no_mtp_term`` read the gradients'
part alone: the forward pass cannot see them).  One JSON line a reading, with
what ``problems_of`` says of it at the chip's limits.
"""

from __future__ import annotations

import argparse
import json
import os
import resource
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CELL = "xing4.0-29b-a4b.tokens-8k-b1"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", default="2654435769")
    ap.add_argument("--which", default="system,float8")
    ap.add_argument("--parts", default=None, help="forward,gradients")
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from benchmarks.families import xing4 as family
    from benchmarks.lib import cells

    cell = cells.load_cell(CELL, rehearsal=args.rehearsal)
    cfg = family.model_config(cell.config, cell.mix)
    dtypes = {"float8": jnp.float8_e4m3fn, "bfloat16": jnp.bfloat16}
    for seed in (int(s) for s in args.seeds.split(",")):
        for which in args.which.split(","):
            kw, parts = {}, family.PARTS
            if which in dtypes:
                kw["compute_dtype"] = dtypes[which]
            elif which != "system":
                kw["fault"] = which
                if which in ("skipped_update", "no_mtp_term"):
                    parts = ("gradients",)
            if args.parts:
                parts = tuple(args.parts.split(","))
            t0 = time.monotonic()
            found = family.compare_with_reference(cfg, seed, parts=parts, **kw)
            print(json.dumps({
                "which": which, "seed": seed, "backend": jax.default_backend(),
                "seconds": round(time.monotonic() - t0, 1),
                "peak_GiB": family._peak_gib(), "host_peak_GiB": round(
                    resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2**20, 2),
                **found,
                "problems": family.problems_of(found, rehearsal=args.rehearsal),
            }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
