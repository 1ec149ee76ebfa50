"""On-chip timing of the sliding-window flash kernels
(``ops/flash_attention.py``, ``flash_attention(window=)``) by block shape,
one kernel at a time: what places the windowed rule of ``_default_blocks``
(``_FINE_BAND``: 512 x 512 blocks for a window of at most 2048)
(PERF.md §6, PR 31).

    chiprun -- python3 tools/probe_flash_band.py [--window W] [--blocks BQxBK ...]

One JSON line a block shape at Trinity-Mini's geometry (2 x 8192 tokens,
32 query / 4 key-value heads x 128, window 2048 or ``--window``, bf16):
device milliseconds a call of each of the three kernels (own time of their
events in a profiler trace of ten calls of forward + backward, reduced by
``benchmarks/lib/tracered.py``); the useful share of peak of each, counted
as ``flash_roofline_share`` counts it (``benchmarks/lib/afmoe_flops.py``:
attended pairs x 2·128 a pass x the kernel's 2 / 3 / 4 passes, over the
chip's peak); the grid's executed and live steps a head where the tree has
``band_grid``; and the worst difference of the output and the gradients
from the first shape's.  Each shape is read twice (``--backward``): with
the backward pass as the tree runs it at this shape, ONE kernel on the
dK/dV grid that carries dQ (``one``: no ``bwd_dq`` family, and the
``bwd_dkv`` family's share counted at its five products), and as the two
kernels a row too long for that keeps (``pair``), whose gradients the
``one`` line is held to (``max_abs_diff_from_pair``).  ``--window`` at the
row's length or past it reads the causal-full kernels.  The script uses the
public call only, so the same file times a checkout without the banded grid
or the one-kernel backward (run it from that checkout's root).  Needs a TPU: a timing from anywhere else is no timing
(``--rehearsal`` runs the control flow at a tiny size anywhere and prints
no time).
"""

from __future__ import annotations

import argparse
import contextlib
import importlib
import json
import os
import sys
import tempfile
from typing import Optional

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

# The module: ``ddl_tpu.ops.flash_attention`` the attribute is the function.
blockwise = importlib.import_module("ddl_tpu.ops.flash_attention")
from ddl_tpu.bringup import bring_up  # noqa: E402
from benchmarks.lib import afmoe_flops, peaks, tracered  # noqa: E402

B, T, H, HKV, D, WINDOW = 2, 8192, 32, 4, 128, 2048
DEFAULT_BLOCKS = ("1024x1024", "512x512", "512x1024", "1024x512",
                  "256x512", "512x256", "256x1024", "default")
CALLS = 10


def peak_share(kernel: str, ms: float, device_kind: str,
               passes: Optional[int] = None) -> float:
    """Useful FLOPs of one call over its device time, % of the chip's peak,
    counted as ``flash_roofline_share`` counts them (``passes``: another
    count than its table's)."""
    flops = ((passes or afmoe_flops.FLASH_PASSES[kernel]) * 2.0 * D
             * afmoe_flops.attended_pairs(T, WINDOW) * B * H)
    return round(100 * flops / (ms * 1e-3) / peaks.peak_flops(device_kind), 2)


#: The products of the one-kernel backward: s, dp, dv, dk and dq.
ONE_KERNEL_PASSES = 5


@contextlib.contextmanager
def backward_as(mode: str):
    """Trace under it: ``pair`` takes the VMEM the one-kernel backward's
    dQ rows may use away, so every shape keeps the two kernels; ``one``
    (and a checkout that has no such kernel) leaves the tree's choice."""
    if mode == "one" or not hasattr(blockwise, "_BWD_ROW_BYTES"):
        yield
        return
    was, blockwise._BWD_ROW_BYTES = blockwise._BWD_ROW_BYTES, 0
    try:
        yield
    finally:
        blockwise._BWD_ROW_BYTES = was


def kernel_ms(fn, args, trace_dir: Optional[str]) -> dict:
    """Device ms a call of each ``ddl_flash_*`` family in ``fn``, from a
    profiler trace of CALLS back-to-back calls (own time of the events)."""
    jax.block_until_ready(fn(*args))  # compile + warm
    if trace_dir is None:  # rehearsal: no device to time
        return {}
    with jax.profiler.trace(trace_dir):
        for _ in range(CALLS):
            out = fn(*args)
        jax.block_until_ready(out)
    trace = tracered.load(tracered.find_trace_file(trace_dir))
    ms: dict = {}
    for name, own in tracered.self_times(trace.ops[0]):
        family = tracered.op_family(tracered.op_name(name))
        if family.startswith("ddl_flash_"):
            ms[family] = ms.get(family, 0.0) + own * 1e3 / CALLS
    return ms


def main() -> None:
    global T, WINDOW
    ap = argparse.ArgumentParser()
    ap.add_argument("--blocks", nargs="*", default=list(DEFAULT_BLOCKS))
    ap.add_argument("--seed", type=int, default=2654435769)
    ap.add_argument("--window", type=int, default=2048)
    ap.add_argument("--backward", nargs="*", default=["pair", "one"],
                    choices=["pair", "one"])
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args()
    bring_up("cpu" if args.rehearsal else None)  # a TPU, or SystemExit
    dev = jax.devices()[0]
    WINDOW = args.window
    if args.rehearsal:
        T, WINDOW = 512, 160
    kq, kk, kv, kd = jax.random.split(jax.random.key(args.seed % (2**31)), 4)
    q = jax.random.normal(kq, (B, T, H, D), jnp.bfloat16)
    k = jax.random.normal(kk, (B, T, HKV, D), jnp.bfloat16)
    v = jax.random.normal(kv, (B, T, HKV, D), jnp.bfloat16)
    do = jax.random.normal(kd, (B, T, H, D), jnp.bfloat16)
    first = None
    for name in args.blocks:
        pair = None
        for backward in args.backward:
            with backward_as(backward):
                line, outs = read_shape(name, backward, (q, k, v, do),
                                        dev.device_kind, args.rehearsal)
            if outs is not None:
                if backward == "pair":
                    pair = outs
                elif pair is not None:
                    line["max_abs_diff_from_pair"] = diffs(outs, pair)
                if first is None:
                    first = outs
                else:
                    line["max_abs_diff_from_first"] = diffs(outs, first)
            print(json.dumps(line), flush=True)


def diffs(outs, ref) -> list:
    return [round(float(np.abs(a - b).max()), 5) for a, b in zip(outs, ref)]


def read_shape(name: str, backward: str, operands, device_kind: str,
               rehearsal: bool):
    """(the JSON line, [output, dq, dk, dv] as float32 or None if refused)
    of one block shape, traced here: under the caller's ``backward_as``."""
    q, k, v, do = operands
    bq, bk = (None, None) if name == "default" else map(int, name.split("x"))

    def attn(q, k, v):
        return blockwise.flash_attention(
            q, k, v, kv_repeat=H // HKV, window=WINDOW,
            block_q=bq, block_k=bk,
        )

    def loss(q, k, v):
        return jnp.sum(attn(q, k, v).astype(jnp.float32) * do)

    grads = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
    line = {"blocks": name, "window": WINDOW, "backward": backward,
            "device": device_kind}
    try:
        with tempfile.TemporaryDirectory() as tmp:
            ms = kernel_ms(grads, (q, k, v), None if rehearsal else tmp)
    except Exception as e:  # Mosaic refusing a shape is a reading too
        line["refused"] = f"{type(e).__name__}: {str(e)[:300]}"
        return line, None
    ms = {f.removeprefix("ddl_flash_").removeprefix("swa_"): t
          for f, t in ms.items()}
    if ms:
        one = "bwd_dq" not in ms
        line.update(
            ms={f: round(t, 4) for f, t in ms.items()},
            # a layer under selective remat: each kernel once
            ms_layer=round(sum(ms.values()), 4),
            peak={f: peak_share(
                f, t, device_kind,
                ONE_KERNEL_PASSES if one and f == "bwd_dkv" else None)
                  for f, t in ms.items()},
        )
    if hasattr(blockwise, "band_grid") and WINDOW < T:
        rbq, rbk = blockwise._default_blocks(T, bq, bk, WINDOW)
        g = blockwise.band_grid(T, WINDOW, rbq, rbk)
        line.update(blocks_run=f"{rbq}x{rbk}", steps=g.steps,
                    steps_dkv=g.steps_dkv, live=g.live)
    outs = [jax.jit(attn)(q, k, v), *grads(q, k, v)]
    outs = [np.asarray(o.astype(jnp.float32)) for o in outs]
    line["finite"] = bool(all(np.isfinite(o).all() for o in outs))
    return line, outs

if __name__ == "__main__":
    main()
