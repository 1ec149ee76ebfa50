#!/usr/bin/env python3
"""The controls behind the Kanana-2 cell's limits, read on the chip
through the check as it is committed (PERF.md section 6, PR 32): every
stand-in for the system through ``families/deepseek_v3.py``'s
``compare_with_reference`` + ``problems_of`` - the reference computed in
float8_e4m3fn, and the system with a fault of the latent mechanism
planted (the rotary product left out of the score; the scale
``1/sqrt(128)``): each has to come back with problems.

    chiprun -- python3 tools/probe_mla_controls.py --seed 2654435769

``--aot`` compiles the check's three programs for a DESCRIBED v5e and
prints their memory (no chip, nothing runs).  Needs a TPU otherwise
(``--rehearsal cpu`` runs the control flow at the cell's tiny size and
proves nothing about the limits).
"""
import argparse
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "kanana-2-30b-a3b.tokens-8k"


def aot(family, cfg) -> None:
    """``memory_analysis()`` of the check's programs at the cell's size."""
    from unittest import mock

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])

    def described(tree, dtype=None):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, dtype or x.dtype, sharding=one_chip), tree)

    shapes = jax.eval_shape(lambda: family.init_params(cfg, jax.random.key(0)))
    stored, exact = described(shapes), described(shapes, jnp.float32)
    pair = jax.ShapeDtypeStruct((family.PAIR_ROWS, cfg.max_seq), jnp.int32,
                                sharding=one_chip)
    row = jax.ShapeDtypeStruct((1, family.GRAD_TOKENS), jnp.int32,
                               sharding=one_chip)
    probes = jax.tree.map(
        lambda _: jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip), shapes)
    programs = family.check_programs(cfg)
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        for name, lowered in (
            ("errors", programs["errors"].lower(stored, exact, pair)),
            ("got_norms", programs["got_norms"].program.lower(probes, stored, row)),
            ("want_norms", programs["want_norms"].program.lower(probes, exact, row)),
        ):
            t0 = time.monotonic()
            mem = lowered.compile().memory_analysis()
            print(json.dumps({
                "line": "aot", "program": name,
                "compile_s": round(time.monotonic() - t0, 1),
                "argument_GiB": round(mem.argument_size_in_bytes / 2**30, 3),
                "temp_GiB": round(mem.temp_size_in_bytes / 2**30, 3),
            }), flush=True)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=2654435769)
    ap.add_argument("--rehearsal", choices=("cpu",), default=None)
    ap.add_argument("--aot", action="store_true")
    ap.add_argument("--which", nargs="*", default=None)
    args = ap.parse_args()

    from ddl_tpu.bringup import bring_up

    bring_up("cpu" if args.aot else args.rehearsal)
    import jax.numpy as jnp

    from benchmarks.families import deepseek_v3 as family
    from benchmarks.lib import cells

    cell = cells.load_cell(CELL, rehearsal=bool(args.rehearsal))
    cfg = family.model_config(cell.config, cell.mix)
    if args.aot:
        aot(family, cfg)
        return 0
    stand_ins = {"float8_e4m3fn": {"compute_dtype": jnp.float8_e4m3fn}}
    stand_ins.update({fault: {"fault": fault} for fault in family.FAULTS})
    for name in args.which or stand_ins:
        t0 = time.monotonic()
        found = family.compare_with_reference(cfg, args.seed, **stand_ins[name])
        print(json.dumps({
            "line": "stand_in", "which": name, "seed": args.seed,
            "seconds": round(time.monotonic() - t0, 1),
            "peak_GiB": family._peak_gib(),
            "problems": family.problems_of(found, bool(args.rehearsal)), **found,
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
