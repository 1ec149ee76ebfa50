"""On-chip timing of a hyper-connected wrap's passes over the stream
(``models/hyper_connections.py``), each alone at the Xing4.0 cell's shape (1 x
4 x 8,192 x 3,584, bfloat16), XLA's passes beside the ``ddl_hc_*`` kernels
(``ops/hyper_connections.py``): the reading that decides which passes keep
their kernel (PERF.md section 6, PR 50).

    chiprun -- python3 tools/probe_hc.py [--tiles 128 256] [--passes post_fwd ...]

One JSON line a pass and side: device milliseconds a call (own time of every
device op in a profiler trace of ten back-to-back calls, reduced by
``benchmarks/lib/tracered.py``, and of the ``ddl_hc_*`` family alone), the
bytes a token the pass has to move once (the stream's rows read or written
once and the small float32 arrays) and the GB/s that is against the chip's
peak (``benchmarks/lib/peaks.py``), and on the kernels' lines the worst
difference of every output from XLA's pass's.  ``pre_bwd`` carries the
rounds' backward on both sides (XLA's, on the small arrays) and the stream's
second cotangent, which the pass adds while it writes; ``pre_fwd`` on XLA's
side is ``_hc_project`` + the gate + ``_hc_read``; ``wrap`` is one wrap's
forward and backward around ``y = tanh(h)`` - on the kernels' side as a layer
runs it, the stream handed on by ``hc_pre``; on XLA's side as PR 49's tree
ran it, ``hc_post`` taking the stream itself, so that autodiff merges its two
cotangents in an ``add_any``, a pass of its own.  ``--tiles`` reads the kernels at other token tiles.
Needs a TPU: a timing from anywhere else is no timing (``--rehearsal`` runs
the control flow at a tiny size anywhere and prints no time).
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import tempfile
from unittest import mock

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from ddl_tpu.bringup import bring_up  # noqa: E402
from ddl_tpu.models import hyper_connections as hc  # noqa: E402
from ddl_tpu.ops import hyper_connections as kernels  # noqa: E402
from benchmarks.lib import peaks, tracered  # noqa: E402

B, N, T, C = 1, 4, 8192, 3584
CALLS = 10
PASSES = ("pre_fwd", "post_fwd", "pre_bwd", "post_bwd", "wrap")
JITTED = ("_hc_pre_bwd", "_hc_post_fwd", "_hc_post_bwd")


@contextlib.contextmanager
def xla_passes():
    """XLA's passes at a shape that takes the kernels: the rule answered no,
    and the passes that are jitted by name traced through the bare functions
    (JAX keeps a jitted function's traces)."""
    with contextlib.ExitStack() as stack:
        stack.enter_context(mock.patch.object(hc, "_takes_kernels", lambda X: False))
        for name in JITTED:
            stack.enter_context(
                mock.patch.object(hc, name, getattr(hc, name).__wrapped__))
        yield


def least_bytes_per_token(which: str) -> float:
    """What one read a pass has to move: the stream's rows and the rows
    beside it once, the small float32 arrays once."""
    m, row = 2 * N + N * N, 2 * C
    return {
        "pre_fwd": (N + 1) * row + 4 * (m + 1 + N),
        "post_fwd": (2 * N + 1) * row + 4 * (N * N + N),
        "pre_bwd": (3 * N + 1) * row + 4 * (3 * m + 2 + N),
        "post_bwd": (3 * N + 2) * row + 8 * (N * N + N),
    }[which]


def device_ms(fn, args, trace_dir) -> dict:
    """(``all``: device ms a call of every op of ``fn``; ``ddl_hc``: of the
    kernel families alone), from a trace of CALLS back-to-back calls."""
    jax.block_until_ready(fn(*args))  # compile + warm
    if trace_dir is None:  # rehearsal: no device to time
        return {}
    with jax.profiler.trace(trace_dir):
        for _ in range(CALLS):
            out = fn(*args)
        jax.block_until_ready(out)
    trace = tracered.load(tracered.find_trace_file(trace_dir))
    ms = {"all": 0.0, "ddl_hc": 0.0}
    for name, own in tracered.self_times(trace.ops[0]):
        ms["all"] += own * 1e3 / CALLS
        if tracered.op_family(tracered.op_name(name)).startswith("ddl_hc_"):
            ms["ddl_hc"] += own * 1e3 / CALLS
    return {k: round(v, 4) for k, v in ms.items()}


def programs(settings):
    """{pass: (function of the operands, operands' names)}; traced under the
    caller's side of the rule."""

    def pre_fwd(X, wrap):
        (h, _, _, _), (_, _, pre, p, ss) = hc._pre(X, wrap, settings, lambda v: v)
        return h, p, ss, pre

    def pre_bwd(X, wrap, pre, p, ss, dh, dpost, dres, dXn):
        return hc._hc_pre_bwd(X, wrap, pre, p, ss, dh, dpost, dres, dXn, settings)

    def wrap_step(X, wrap, dXn):
        def loss(X, wrap):
            h, post, res, on = hc.hc_pre(X, wrap, settings)
            out = hc.hc_post(
                on if hc._takes_kernels(X) else X, jnp.tanh(h), post, res)
            return jnp.sum(out.astype(jnp.float32) * dXn.astype(jnp.float32))

        return jax.grad(loss, argnums=(0, 1))(X, wrap)

    return {
        "pre_fwd": (pre_fwd, ("X", "wrap")),
        "post_fwd": (lambda *a: hc._hc_post_fwd(*a), ("X", "y", "post", "res")),
        "pre_bwd": (
            pre_bwd, ("X", "wrap", "pre", "p", "ss", "dh", "dpost", "dres", "dXn")),
        "post_bwd": (lambda *a: hc._hc_post_bwd(*a), ("X", "y", "post", "res", "dXn")),
        "wrap": (wrap_step, ("X", "wrap", "dXn")),
    }


def main() -> None:
    global T, C
    ap = argparse.ArgumentParser()
    ap.add_argument("--passes", nargs="*", default=list(PASSES), choices=PASSES)
    ap.add_argument("--tiles", nargs="*", type=int, default=[kernels.TILE])
    ap.add_argument("--seed", type=int, default=2654435769)
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args()
    bring_up("cpu" if args.rehearsal else None)  # a TPU, or SystemExit
    dev = jax.devices()[0]
    if args.rehearsal:
        T, C = 512, 256
    settings = hc.HyperConnections()
    keys = iter(jax.random.split(jax.random.key(args.seed % (2**31)), 64))
    wrap = {}
    for row in hc.wrap_rows("w", N, C):
        name = row.name.split(".")[1]
        value = (jnp.full(row.shape, row.fill, row.dtype) if row.fill is not None
                 else row.draw(next(keys), row.shape) if row.draw is not None
                 else jax.random.normal(next(keys), row.shape) / np.sqrt(row.shape[0]))
        # off the near-identity start, as the benchmark's check stirs a wrap
        wrap[name] = 30.0 * value if name.startswith("alpha") else (
            value + 0.2 * jax.random.normal(next(keys), value.shape))
    stream = lambda: jax.random.normal(next(keys), (B, N, T, C)).astype(jnp.bfloat16)
    operands = {"X": stream(), "dXn": stream(), "wrap": wrap}
    operands["y"], operands["dh"] = (x[:, 0] for x in (stream(), stream()))
    with xla_passes():
        (_, post, res, _), (_, _, pre, p, ss) = hc._pre(
            operands["X"], wrap, settings, lambda v: v)
    operands.update(pre=pre, p=p, ss=ss, post=post, res=res)
    operands["dpost"] = jax.random.normal(next(keys), post.shape)
    operands["dres"] = jax.random.normal(next(keys), res.shape)
    tokens = B * T
    peak = None if args.rehearsal else peaks._peak(dev.device_kind, 2)  # HBM bytes/s

    def read(which, side, tile):
        fn, names = programs(settings)[which]
        line = {"pass": which, "side": side, "tile": tile, "device": dev.device_kind}
        try:
            with tempfile.TemporaryDirectory() as tmp:
                jitted = jax.jit(fn)
                a = tuple(operands[k] for k in names)
                ms = device_ms(jitted, a, None if args.rehearsal else tmp)
                outs = jitted(*a)
        except Exception as e:  # Mosaic refusing a tile is a reading too
            line["refused"] = f"{type(e).__name__}: {str(e)[:300]}"
            return line, None
        if ms:
            line["ms"] = ms
            if which != "wrap":
                least = least_bytes_per_token(which)
                line["least_KB_a_token"] = round(least / 1e3, 2)
                line["GB_per_s"] = round(least * tokens / (ms["all"] * 1e-3) / 1e9, 1)
                line["of_peak"] = round(100 * least * tokens / (ms["all"] * 1e-3) / peak, 1)
        return line, [np.asarray(o.astype(jnp.float32)) for o in jax.tree.leaves(outs)]

    for which in args.passes:
        with xla_passes():
            line, want = read(which, "xla", None)
        print(json.dumps(line), flush=True)
        for tile in args.tiles:
            jax.clear_caches()
            with mock.patch.object(kernels, "TILE", tile):
                line, got = read(which, "kernels", tile)
            if got is not None and want is not None:
                line["max_abs_diff_from_xla"] = [
                    float(np.abs(g - w).max()) for g, w in zip(got, want)]
                line["max_abs_of_xla"] = [float(np.abs(w).max()) for w in want]
            print(json.dumps(line), flush=True)
        jax.clear_caches()


if __name__ == "__main__":
    main()
