"""On-chip timing and error of the one-block attention kernels
(``ops/flash_tile.py``) beside the blockwise ones (``ops/flash_attention.py``)
at the same shapes: what places ``flash_tile.MAX_T`` (PERF.md §6, PR 27).

    chiprun -- python3 tools/probe_flash_tile.py [--shapes B,T,H,D[,causal][,f32] ...]

One JSON line a shape: milliseconds a layer of the forward and of forward
+ backward on each path, less the projections around it (chained in one
jit, median of five calls), and the rms and worst error of the output and
of each gradient against the float32 reference under
``Precision.HIGHEST``, both paths on the same seeded inputs (bf16, or
float32 where the shape says ``f32``).  Needs a TPU: a timing from anywhere else is no
timing.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from ddl_tpu.ops import flash_tile  # noqa: E402
from ddl_tpu.parallel.ring_attention import attention_reference  # noqa: E402

# The module: ``ddl_tpu.ops.flash_attention`` the attribute is the function.
blockwise = importlib.import_module("ddl_tpu.ops.flash_attention")

# 132 rows of ViT's geometry beside 128: at B = 128, T = 196 XLA lays this
# little program's tensors out T-major and copies each of them to and from
# the kernels' row-major layout, which ViT's own step does not (its trace
# shows none); at B = 132 it does not here either.  Read times at 132,
# errors at either.
DEFAULT_SHAPES = (
    "132,196,12,64", "128,196,12,64", "64,128,12,64", "16,512,16,128",
    "16,512,16,128,causal", "16,512,32,64", "132,196,12,64,f32",
    "16,512,16,128,f32",
)
CHAIN = 8  # layers per timed program


def paths(causal):
    def block(q, k, v):
        bq, bk = blockwise._default_blocks(q.shape[1], None, None)
        return blockwise._flash_core(
            q, k, v, blockwise._offsets_arr(0, 0), causal, 1, bq, bk, None
        )[0]

    def tile(q, k, v):
        return flash_tile.tile_attention(q, k, v, causal, None)

    return {"block": block, "tile": tile}


def layers(fn, heads, grad):
    """``CHAIN`` attention layers in one program: q, k, v leave a
    projection and the output enters one, as in a model, so each path
    pays the relayouts it asks of its neighbours and no others (a bare
    kernel call is given its operands in the entry layout XLA prefers,
    T-major at T = 196, and pays copies no model pays).  ``fn`` None: the
    projections alone, the time to take off."""
    def layer(x, w):
        q, k, v = (
            (x @ w[i]).reshape(*x.shape[:2], heads, -1) for i in range(3)
        )
        # q * k + v: three distinct cotangents, as attention hands back
        # (q + k + v would let XLA fold the three weight gradients into one).
        a = q * k + v if fn is None else fn(q, k, v)
        return a.reshape(x.shape) @ w[3]

    def once(x, w, g):
        if not grad:
            return layer(x, w)
        dx, dw = jax.grad(
            lambda x, w: jnp.sum(layer(x, w).astype(jnp.float32) * g),
            argnums=(0, 1),
        )(x, w)
        return dx + jnp.sum(dw.astype(jnp.float32)).astype(dx.dtype)

    def run(x, w, g):
        def body(c, _):
            return (x + once(c, w, g) * 1e-3).astype(x.dtype), None
        return jax.lax.scan(body, x, None, length=CHAIN)[0]

    return jax.jit(run)


def time_ms(fn, args):
    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(5):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    return sorted(times)[2] / CHAIN * 1e3


def errors(fn, causal, q, k, v, w):
    def outputs(attn, q, k, v):
        out, vjp = jax.vjp(attn, q, k, v)
        return (out,) + vjp(w.astype(out.dtype))

    with jax.default_matmul_precision("highest"):
        ref = outputs(
            lambda q, k, v: attention_reference(q, k, v, causal=causal),
            *(x.astype(jnp.float32) for x in (q, k, v)),
        )
    got = jax.jit(lambda q, k, v: outputs(fn, q, k, v))(q, k, v)
    report = {}
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, ref):
        err = np.asarray(a, np.float32) - np.asarray(b, np.float32)
        report[name] = {
            "rms": float(np.sqrt(np.mean(err**2))),
            "worst": float(np.max(np.abs(err))),
            "ref_rms": float(np.sqrt(np.mean(np.asarray(b) ** 2))),
        }
    return report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shapes", nargs="*", default=list(DEFAULT_SHAPES))
    ap.add_argument("--seed", type=int, default=2718281828)
    args = ap.parse_args(argv)
    if jax.default_backend() != "tpu":
        print("probe_flash_tile: needs a TPU", file=sys.stderr)
        return 1
    for spec in args.shapes:
        fields = spec.split(",")
        B, T, H, D = (int(x) for x in fields[:4])
        flags = fields[4:]
        causal = "causal" in flags
        dtype = jnp.float32 if "f32" in flags else jnp.bfloat16
        keys = jax.random.split(jax.random.key(args.seed % 2**31), 4)
        q, k, v, w = (
            jax.random.normal(key, (B, T, H, D), dtype) for key in keys
        )
        x = q.reshape(B, T, H * D)
        proj = jax.random.normal(
            keys[3], (4, H * D, H * D), dtype
        ) * (H * D) ** -0.5
        line = {
            "shape": [B, T, H, D], "causal": causal,
            "dtype": jnp.dtype(dtype).name,
            "device": jax.devices()[0].device_kind,
            "fits": flash_tile.fits(q, k, v, 1, 512, 1024, None),
        }
        g = w.reshape(x.shape)
        base = [time_ms(layers(None, H, grad), (x, proj, g))
                for grad in (False, True)]
        line["projections_ms"] = base
        for name, fn in paths(causal).items():
            line[name] = {
                "fwd_ms": time_ms(layers(fn, H, False), (x, proj, g)) - base[0],
                "fwd_bwd_ms": time_ms(layers(fn, H, True), (x, proj, g)) - base[1],
                "error": errors(fn, causal, q, k, v, w),
            }
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
