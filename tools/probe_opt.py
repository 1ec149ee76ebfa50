"""Probe: what does the distributed optimizer actually buy on this attach?

Runs the ZeRO-1 sharded optimizer (ddl_tpu/parallel/optimizer.py) on
whatever devices exist — the real mesh on a TPU pod, the 8-device
virtual mesh on CPU — and prints, per config, the optimizer-state
bytes/replica and gradient-communication bytes for the full sweep
{replicated, zero1} × {fp32, int8}, plus the measured gather/scatter
collective-leg times at small scale.  Large configs (llama3-8B, the ≥4B
fits-only-with-zero1 geometry) price ANALYTICALLY via
``hbm_accounting`` over ``param_shapes`` — zero FLOPs, no weights
materialised — so the pod-scale memory claim is checkable from a
laptop.  The mirror of ``tools/probe_ici.py`` for the optimizer tier:
the numbers that decide whether a config fits a chip's HBM.

Run on the bench chip (or `make opt-dryrun` for the CPU virtual mesh):

    python tools/probe_opt.py
"""
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import numpy as np  # noqa: E402


def main():
    import bench

    platform = bench.bring_up(cpu_devices=8)
    import jax
    import optax

    from ddl_tpu.models import llama
    from ddl_tpu.parallel.collectives import QUANT_BLOCK, quantized_bytes
    from ddl_tpu.parallel.mesh import make_mesh
    from ddl_tpu.parallel.optimizer import (
        ShardedOptimizer,
        hbm_accounting,
        state_bytes_per_replica,
    )

    devices = jax.devices()
    n_dev = len(devices)
    r = {
        "platform": platform,
        "n_devices": n_dev,
        "device_kind": getattr(devices[0], "device_kind", "cpu"),
    }
    if n_dev < 2:
        r["error"] = "need >= 2 devices for a dp axis"
        print(json.dumps(r))
        return
    # The SAME mesh shape and model geometry as the DDL_BENCH_MODE=opt
    # A/B (bench._opt_mesh_axes/_opt_config) — the probe's numbers must
    # describe the layout the committed artifact gates on.
    axes = bench._opt_mesh_axes(n_dev)
    mesh = make_mesh(axes, devices=devices)
    r["mesh"] = dict(axes)

    # -- measured: small config, real placed state -----------------------
    cfg, _batch, _seq, _steps = bench._opt_config()
    params = llama.init_params(cfg, jax.random.key(0))
    specs = llama.param_specs(cfg)
    for label, opt in (
        ("replicated", optax.adamw(3e-4)),
        ("zero1", ShardedOptimizer(optax.adamw(3e-4), mesh, specs)),
    ):
        from ddl_tpu.parallel.train import make_train_step

        init_fn, _ = make_train_step(loss_fn=lambda p, b: 0.0,
                                     optimizer=opt, mesh=mesh,
                                     param_spec_tree=specs)
        state = init_fn(params)
        r[f"small_{label}_state_bytes_per_replica"] = (
            state_bytes_per_replica(state.opt_state)
        )
    r["small_state_shrink"] = round(
        r["small_replicated_state_bytes_per_replica"]
        / max(r["small_zero1_state_bytes_per_replica"], 1), 2,
    )
    zopt = ShardedOptimizer(optax.adamw(3e-4), mesh, specs)
    legs = zopt.measure_legs(params)
    r["small_gather_ms"] = round(legs["gather_s"] * 1e3, 3)
    r["small_scatter_ms"] = round(legs["scatter_s"] * 1e3, 3)

    # Per-step grad-communication payload (reduce + gather legs), raw
    # fp32 vs the int8 wire format.
    raw = 2 * sum(
        int(np.prod(l.shape)) * np.dtype(l.dtype).itemsize
        for l in jax.tree.leaves(llama.param_shapes(cfg))
    )
    quant = 2 * sum(
        quantized_bytes(l.shape)
        for l in jax.tree.leaves(llama.param_shapes(cfg))
    )
    r["small_grad_comm_bytes_fp32"] = raw
    r["small_grad_comm_bytes_int8"] = quant
    r["small_grad_comm_cut"] = round(raw / quant, 2)
    r["quant_block"] = QUANT_BLOCK

    # -- analytic: pod-scale configs over eval_shape ----------------------
    # The chip A/B geometry (v5e-32: dp=8 × fsdp=4) priced for the
    # flagship 8B config and the ≥4B fits-only-with-zero1 geometry the
    # accounting test pins (tests/test_optimizer.py).
    pod = {"dp": 8, "fsdp": 4}
    for name, big in (
        ("llama3_8b", llama.LlamaConfig.llama3_8b()),
        ("llama_4b", llama.LlamaConfig.llama_4b()),
    ):
        shapes = llama.param_shapes(big)
        sp = llama.param_specs(big)
        for sharding in ("none", "zero1"):
            acct = hbm_accounting(
                shapes, sp, pod, optimizer_sharding=sharding
            )
            r[f"{name}_{sharding}_resident_gib_per_chip"] = round(
                acct.total_bytes / 2**30, 2
            )
        n_params = sum(
            int(np.prod(l.shape)) for l in jax.tree.leaves(shapes)
        )
        r[f"{name}_params_billions"] = round(n_params / 1e9, 3)
    r["pod_mesh"] = pod
    r["v5e_hbm_gib_per_chip"] = 16.0

    print(json.dumps(r))


if __name__ == "__main__":
    main()
