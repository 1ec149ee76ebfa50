#!/usr/bin/env python3
"""What XLA's ``jax.lax.ragged_dot`` does on the chip with the rows past
the last group: how long it takes with every row, an eighth of the rows and
no row in a group, whether the other rows of the result (and of the
transpose with respect to the rows) are zero, stale or NaN, and what the
N*k-row gather beside it costs.  It is what ``models/moe.py:ragged_experts``'
``held=`` masking rests on (PERF.md section 6, PR 30).

    chiprun -- python3 tools/probe_ragged_rows.py

Needs a TPU (``REHEARSE=1`` runs the control flow at a tiny size on the
CPU and proves nothing).
"""
import json, os, sys, time
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from ddl_tpu.bringup import bring_up
REH = bool(os.environ.get("REHEARSE"))
bring_up("cpu" if REH else None)
import jax, jax.numpy as jnp, numpy as np

M, D, F, G = (1024, 64, 32, 16) if REH else (131072, 2048, 1024, 16)
key = jax.random.key(0)
x = jax.random.normal(key, (M, D), jnp.bfloat16)
w = jax.random.normal(jax.random.key(1), (G, D, F), jnp.bfloat16) / 45.0
out = {}
f = jax.jit(lambda x, w, gs: jax.lax.ragged_dot(x, w, gs))
for name, per in (("all_rows", M // G), ("eighth", M // G // 8), ("none", 0)):
    gs = jnp.full((G,), per, jnp.int32)
    y = f(x, w, gs); y.block_until_ready()
    t0 = time.perf_counter()
    for _ in range(10):
        y = f(x, w, gs)
    y.block_until_ready()
    dt = (time.perf_counter() - t0) / 10
    tail = np.asarray(y[per * G:].astype(jnp.float32)) if per * G < M else np.zeros((1, 1))
    out[name] = {"ms": dt * 1e3, "rows_in_groups": per * G,
                 "tflops_on_grouped_rows": 2 * per * G * D * F / dt / 1e12 if per else 0,
                 "tail_all_zero": bool((tail == 0).all()), "tail_has_nan": bool(np.isnan(tail).any()),
                 "tail_abs_max": float(np.nanmax(np.abs(tail)))}
# same with NaN-poisoned fresh output buffers: fill memory with NaN first
big = jnp.full((M, F), jnp.nan, jnp.bfloat16); big.block_until_ready(); del big
gs = jnp.full((G,), M // G // 8, jnp.int32)
y = f(x, w, gs); tail = np.asarray(y[M // 8:].astype(jnp.float32))
out["eighth_after_nan_fill"] = {"tail_all_zero": bool((tail == 0).all()), "tail_has_nan": bool(np.isnan(tail).any())}
# transpose wrt lhs and rhs
g = jax.jit(jax.grad(lambda x, w, gs: jnp.sum(jax.lax.ragged_dot(x, w, gs).astype(jnp.float32)[: M // 8]), argnums=(0, 1)))
dx, dw = g(x, w, gs)
tail = np.asarray(dx[M // 8:].astype(jnp.float32))
out["grad_lhs_tail"] = {"all_zero": bool((tail == 0).all()), "has_nan": bool(np.isnan(tail).any())}
# gather timing: N*k rows
idx = jnp.asarray(np.random.default_rng(0).integers(0, M // 8, M), jnp.int32)
h = jax.jit(lambda x, idx: jnp.take(x, idx, axis=0))
y = h(x[: M // 8], idx); y.block_until_ready()
t0 = time.perf_counter()
for _ in range(10):
    y = h(x[: M // 8], idx)
y.block_until_ready()
out["gather_131072_rows_ms"] = (time.perf_counter() - t0) / 10 * 1e3
print(json.dumps(out, indent=1))
