#!/usr/bin/env python3
"""Read the LFM2-24B-A2B cell's reference check and its stand-ins through
the committed adapter (``benchmarks/families/lfm2_moe.py``), at the cell's
own sizes on whatever backend JAX finds (the chip through ``chiprun``; the
CPU with ``--rehearsal`` at the cell's tiny sizes).

    python3 tools/probe_lfm2_controls.py [--seeds 1,2] [--which system,float8,...] [--rehearsal]

``system``: the model as configured; ``float8`` / ``bfloat16``: the
reference computed in that precision in the system's place; the faults of
``lfm2_moe.FAULTS`` (``skipped_update`` reads the optimizer step alone);
``layers``: where the system leaves the reference, a layer at a time - the
residual stream behind each layer of the system's own walk against the
reference's (errors carried along), each system layer fed the REFERENCE's
stream (a layer's own error), and the same two readings of the reference
computed in bfloat16.
One JSON line a reading, with what ``problems_of`` says of it at the
chip's limits.  ~1-1.5 min a reading on the chip, the first ~3 min.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CELL = "lfm2-24b-a2b.tokens-8k"


def by_layer(family, cfg, seed: int) -> dict:
    """Relative rms error of the residual stream behind each layer, on a
    step's rows: ``carried`` (the system's own walk), ``own`` (each system
    layer from the reference's stream, rounded to the compute dtype), and
    both again for the reference computed in bfloat16."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.lib import lfm2_moe_reference as reference

    model = family.model
    c = family.reference_config(cfg, reference)
    stored = jax.jit(lambda key: model.init_params(cfg, key))(
        jax.random.fold_in(jax.random.key(seed), 43))
    tokens = jnp.asarray(np.random.default_rng([seed, 43]).integers(
        0, cfg.vocab, (family.PAIR_ROWS, cfg.max_seq), dtype=np.int32))
    positions = jnp.arange(cfg.max_seq)
    kinds = model._kinds(cfg)
    same, low = reference._rounder(None), reference._rounder(jnp.bfloat16)

    @jax.jit
    def rel(got, want):
        got, want = got.astype(jnp.float32), want.astype(jnp.float32)
        return jnp.sqrt(jnp.sum((got - want) ** 2) / jnp.sum(want**2))

    system_layer = jax.jit(
        lambda x, layer, conv, dense: model._layer_apply(
            layer, x, cfg, positions, conv, dense, None)[0],
        static_argnums=(2, 3))
    x_ref = stored["embed"][tokens].astype(jnp.float32)
    x_sys, x_low = x_ref.astype(cfg.dtype), x_ref
    out = {"kinds": ["conv" if k[0] else "attn" for k in kinds],
           "carried": [], "own": [], "bf16_carried": [], "bf16_own": []}
    def plain(x, layer, r, conv, dense):
        # as ``reference.forward`` runs a layer: float32 matmuls at HIGHEST (the
        # chip's default is one bf16 pass)
        with jax.default_matmul_precision("highest"):
            return reference._layer(x, layer, c, r, conv, dense)[0]

    for (conv, dense), layer in zip(kinds, stored["layers"]):
        want = plain(x_ref, layer, same, conv, dense)
        x_sys = system_layer(x_sys, layer, conv, dense)
        x_low = plain(x_low, layer, low, conv, dense)
        own = system_layer(x_ref.astype(cfg.dtype), layer, conv, dense)
        low_own = plain(low(x_ref), layer, low, conv, dense)
        for key, got in (("carried", x_sys), ("own", own),
                         ("bf16_carried", x_low), ("bf16_own", low_own)):
            out[key].append(round(float(rel(got, want)), 6))
        x_ref = want
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", default="2654435769")
    ap.add_argument("--which", default="system,float8")
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from benchmarks.families import lfm2_moe as family
    from benchmarks.lib import cells

    cell = cells.load_cell(CELL, rehearsal=args.rehearsal)
    cfg = family.model_config(cell.config, cell.mix)
    dtypes = {"float8": jnp.float8_e4m3fn, "bfloat16": jnp.bfloat16}
    for seed in (int(s) for s in args.seeds.split(",")):
        for which in args.which.split(","):
            kw, parts = {}, family.PARTS
            if which == "layers":
                print(json.dumps({"which": which, "seed": seed,
                                  **by_layer(family, cfg, seed)}), flush=True)
                continue
            if which in dtypes:
                kw["compute_dtype"] = dtypes[which]
            elif which != "system":
                kw["fault"] = which
                if which == "skipped_update":
                    parts = ("gradients",)
            t0 = time.monotonic()
            found = family.compare_with_reference(cfg, seed, parts=parts, **kw)
            print(json.dumps({
                "which": which, "seed": seed, "backend": jax.default_backend(),
                "seconds": round(time.monotonic() - t0, 1),
                "peak_GiB": family._peak_gib(), **found,
                "problems": family.problems_of(found, rehearsal=args.rehearsal),
            }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
