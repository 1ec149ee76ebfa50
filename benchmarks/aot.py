#!/usr/bin/env python3
"""Compile each cell's step program at its real shapes for a DESCRIBED
``v5e:2x2`` — no chip attached, nothing runs, no chip time spent.

    JAX_PLATFORMS=cpu python3 benchmarks/aot.py [--workload <cell>] [--set key=value ...]

Prints ``memory_analysis()`` of the window program the Trainer runs
(``parallel.train.make_multistep``, donated, per-step batches) and, for a
mesh, of the ICI scatter kernel at the window's per-chip block.  It is
what fixed the Mistral depth and the ViT batch (PERF.md section 4); run
it again before growing either.  ``--set num_hidden_layers=5`` tries
another size without editing a file.

The program decides flash-or-dense and kernel-or-interpreter from
``jax.default_backend()``, which is the CPU here; this script answers
"tpu" for it while it lowers.  A compile that passes is not a chip run.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from unittest import mock

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs under /tmp
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def compile_cell(cell, devices) -> dict:
    import jax
    import numpy as np
    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from benchmarks.lib import producers
    from ddl_tpu.parallel.train import make_multistep

    c, mix, family = cell.config, cell.mix, cell.family
    sizes = family.sizes(c, mix)
    cfg = family.model_config(c, mix)
    mesh = Mesh(
        np.array(devices[: cell.chips]).reshape(tuple(mix["mesh"].values())),
        tuple(mix["mesh"]),
    )
    steps = mix["window_rows"] // mix["batch_rows"]
    loss_fn = family.loss_fn(cfg, mesh)
    optimizer = optax.adamw(c["training"]["learning_rate"])
    _, multi = make_multistep(
        loss_fn, optimizer, mesh, family.param_specs(cfg),
        batch_spec=P(("dp",)), n_steps=steps,
    )
    replicated = NamedSharding(mesh, P())

    def on_mesh(tree):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=replicated),
            tree,
        )

    params = jax.eval_shape(
        lambda: family.init_params(cfg, jax.random.key(0))
    )
    opt_state = jax.eval_shape(optimizer.init, params)
    geom = producers.geometry(mix, sizes)
    window_sh = NamedSharding(mesh, P(None, "dp"))
    cols = tuple(
        jax.ShapeDtypeStruct(
            (steps, mix["batch_rows"], w), geom.dtype, sharding=window_sh
        )
        for w in geom.splits
    )

    # The jitted scan itself (``_run``): ``multi`` wraps it in a host-side
    # reshard that takes arrays, not shapes.
    run = next(
        cell_.cell_contents for cell_ in multi.__closure__
        if hasattr(cell_.cell_contents, "lower")
    )
    t0 = time.perf_counter()
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        compiled = run.lower(
            on_mesh(params), on_mesh(opt_state), cols, True
        ).compile()
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    gib = 2**30
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    out = {
        "cell": cell.name,
        "params": n_params,
        "steps_per_window": steps,
        "compile_s": round(time.perf_counter() - t0, 1),
        "argument_GiB": round(mem.argument_size_in_bytes / gib, 3),
        "output_GiB": round(mem.output_size_in_bytes / gib, 3),
        "alias_GiB": round(mem.alias_size_in_bytes / gib, 3),
        "temp_GiB": round(mem.temp_size_in_bytes / gib, 3),
        "per_chip_total_GiB": round(
            (
                mem.argument_size_in_bytes + mem.output_size_in_bytes
                - mem.alias_size_in_bytes + mem.temp_size_in_bytes
            ) / gib, 3,
        ),
        "tpu_custom_calls": text.count("tpu_custom_call"),
        "all_reduces": text.count(" all-reduce("),
    }
    if cell.chips > 1:
        from ddl_tpu.ops import ici_fanout

        rows = mix["window_rows"] // cell.chips
        krows, kcols = ici_fanout.kernel_view(
            cell.chips, rows, geom.nValues, np.dtype(geom.dtype).name
        )
        scatter = ici_fanout._scatter_call(
            tuple(devices[: cell.chips]), krows, kcols,
            np.dtype(geom.dtype).name, 0, False,
        )
        smem = scatter.memory_analysis()
        out["ici_scatter"] = {
            "tile_aligned": ici_fanout.tile_aligned(
                rows, geom.nValues, np.dtype(geom.dtype).name
            ),
            "kernel_view": [krows, kcols],
            "output_GiB": round(smem.output_size_in_bytes / gib, 3),
            "temp_GiB": round(smem.temp_size_in_bytes / gib, 3),
        }
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", action="append")
    ap.add_argument(
        "--set", action="append", default=[], metavar="KEY=VALUE",
        help="override a number of the configuration or the mix for this compile",
    )
    args = ap.parse_args(argv)

    import jax
    from jax.experimental import topologies

    from benchmarks.lib import cells

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    names = args.workload or [w["name"] for w in cells.benchmark_file()["workloads"]]
    for name in names:
        cell = cells.load_cell(name)
        for item in args.set:
            key, value = item.split("=", 1)
            target = cell.mix if key in cell.mix else cell.config
            target[key] = json.loads(value)
        print(json.dumps(compile_cell(cell, topo.devices)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
