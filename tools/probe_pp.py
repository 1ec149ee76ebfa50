"""Measure pipeline efficiency vs the GPipe S+M-1 ideal.

Times the pipelined llama fwd+bwd at a sweep of microbatch counts M with
the PER-MICROBATCH size fixed, so total work scales linearly in M and the
schedule model ``t(M) = tick * (S + M - 1) + c`` can be read off directly:
the marginal cost of one more microbatch (the slope between the two
largest M) is the bubble-free per-tick time, and

    measured_efficiency(M) = slope * M / t(M)
    ideal_efficiency(M)    = M / (S + M - 1)   (= 1 - bubble_fraction)

should track each other if the schedule hits the GPipe floor (the
lax.cond tick-skip makes fill/drain ticks ~free, so measured can even
slightly exceed ideal).  Run on a chip attach for real numbers; on the
CPU sim the curve shape is meaningful, absolute times are not.

Usage: python tools/probe_pp.py [n_devices=8] [d_model=128] [M,M,...]
(On the 1-core CPU sim each sweep point costs a full recompile — pass a
short sweep like "2,8" there; the default sweep is sized for a chip.)
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main(n_devices: int = 8, d_model: int = 128, ms=(1, 2, 4, 8, 16)) -> None:
    # A pp probe needs >= 4 devices, which a one-chip machine never has:
    # it runs on the CPU virtual mesh unless DDL_PROBE_TPU=1 says this is
    # a multi-chip host.
    if os.environ.get("DDL_PROBE_TPU") != "1":
        from __graft_entry__ import _ensure_cpu_devices

        _ensure_cpu_devices(n_devices)
    import jax
    import jax.numpy as jnp

    from ddl_tpu.models import llama
    from ddl_tpu.parallel import bubble_fraction
    from ddl_tpu.parallel.mesh import make_mesh

    on_tpu = jax.default_backend() == "tpu"
    S, mb = 4, 4
    # DDL_PROBE_SCHEDULE=1f1b probes the interleaved schedule (V=2
    # chunks/device; M must stay a multiple of S — the sweep below is).
    schedule = os.environ.get("DDL_PROBE_SCHEDULE", "gpipe")
    n_chunks = 2 if schedule == "1f1b" else 1
    # bf16 is EMULATED (slow) on the CPU sim — probe the schedule there
    # in fp32 at a shorter sequence; absolute times only matter on chip.
    T = 128 if on_tpu else 32
    cfg = llama.LlamaConfig(
        vocab=256, d_model=d_model, n_layers=S * 2, n_heads=4,
        n_kv_heads=2, d_ff=d_model * 3,
        dtype=jnp.bfloat16 if on_tpu else jnp.float32,
    )
    pp_params = llama.stage_params(
        llama.init_params(cfg, jax.random.key(0)), S, n_chunks=n_chunks
    )
    devices = jax.devices()[:n_devices]
    mesh = make_mesh({"pp": S, "dp": n_devices // S}, devices)
    rng = np.random.default_rng(0)

    def timed(fn, *args, reps: int = 3) -> float:
        jax.block_until_ready(fn(*args))  # compile + warm
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            best = min(best, time.perf_counter() - t0)
        return best

    print(f"S={S} stages, {cfg.n_layers} layers, d_model={d_model}, "
          f"mb={mb}, seq={T}, {n_devices} devices "
          f"({jax.default_backend()}), schedule={schedule}")
    ms = tuple(sorted(set(ms)))
    if schedule == "1f1b":
        # 1f1b needs M % S == 0; round the sweep up to S multiples.
        ms = tuple(sorted({max(S, (M + S - 1) // S * S) for M in ms}))
    assert len(ms) >= 2, "need >= 2 sweep points for the marginal slope"
    times = {}
    for M in ms:
        tokens = jnp.asarray(
            rng.integers(0, cfg.vocab, (mb * M, T)), jnp.int32
        )
        grad_pp = jax.jit(jax.grad(
            lambda p, t, _M=M: llama.next_token_loss_pp(
                p, t, cfg, mesh, n_microbatches=_M,
                schedule=schedule,
                n_chunks=n_chunks if schedule == "1f1b" else None,
            )
        ))
        times[M] = timed(grad_pp, pp_params, tokens)

    # Bubble-free per-tick cost: marginal microbatch time at the deep end.
    slope = (times[ms[-1]] - times[ms[-2]]) / (ms[-1] - ms[-2])
    print(f"per-tick (marginal microbatch) cost: {slope * 1e3:.2f} ms")
    for M in ms:
        eff = slope * M / times[M] if times[M] > 0 else float("nan")
        bub = bubble_fraction(
            S, M, schedule=schedule,
            n_chunks=n_chunks if schedule == "1f1b" else None,
        )
        print(
            f"M={M:3d}  t={times[M] * 1e3:8.1f} ms"
            f"  measured_eff={eff:6.3f}  ideal={1.0 - bub:.3f}"
            f"  bubble={bub:.3f}"
        )


if __name__ == "__main__":
    main(
        int(sys.argv[1]) if len(sys.argv) > 1 else 8,
        int(sys.argv[2]) if len(sys.argv) > 2 else 128,
        tuple(int(x) for x in sys.argv[3].split(","))
        if len(sys.argv) > 3
        else (1, 2, 4, 8, 16),
    )
