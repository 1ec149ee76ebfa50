"""On-chip timing of the latent-attention flash kernels
(``ops/flash_attention.py``, ``flash_attention(q_rope=, k_rope=)``) by
block shape, one kernel at a time: what places their default blocks
(PERF.md §6, PR 32).

    chiprun -- python3 tools/probe_flash_mla.py [--blocks BQxBK ...]

One JSON line a block shape at Kanana-2's geometry (2 x 8192 tokens, 32
heads, 128-wide q/k/v + a 64-wide rotary product with one shared key,
bf16): device milliseconds a call of each of the three kernels (own time
of their events in a profiler trace of ten calls of forward + backward,
reduced by ``benchmarks/lib/tracered.py``); the useful share of peak of
each, counted as ``mla_roofline_share`` counts it
(``benchmarks/lib/mla_flops.py``); and the worst difference of the output
and the gradients from the first shape's.  Each shape is read twice
(``--backward``, as ``tools/probe_flash_band.py`` reads its shapes): the
backward pass as the two kernels (``pair``) and as the ONE kernel on the
dK/dV grid that carries dQ and dQ_rope (``one``: no ``bwd_dq`` family, the
``bwd_dkv`` family's share counted at its five products, and its gradients
held to the pair's, ``max_abs_diff_from_pair``).  The last lines are the causal
one-product kernels at D = 128 on the same q, k, v: what the rotary product
costs.  Needs a TPU: a timing from anywhere else is no timing
(``--rehearsal`` runs the control flow at a tiny size anywhere and prints
no time).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from ddl_tpu.bringup import bring_up  # noqa: E402
from ddl_tpu.ops import flash_attention  # noqa: E402
from benchmarks.lib import mla_flops, peaks  # noqa: E402
from tools.probe_flash_band import backward_as, diffs, kernel_ms  # noqa: E402

B, T, H, D, R = 2, 8192, 32, 128, 64
DEFAULT_BLOCKS = ("1024x1024", "512x1024", "1024x512", "512x512",
                  "1024x2048", "default")


def main() -> None:
    global T
    ap = argparse.ArgumentParser()
    ap.add_argument("--blocks", nargs="*", default=list(DEFAULT_BLOCKS))
    ap.add_argument("--seed", type=int, default=2654435769)
    ap.add_argument("--backward", nargs="*", default=["pair", "one"],
                    choices=["pair", "one"])
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args()
    bring_up("cpu" if args.rehearsal else None)  # a TPU, or SystemExit
    dev = jax.devices()[0]
    if args.rehearsal:
        T = 256
    keys = jax.random.split(jax.random.key(args.seed % (2**31)), 6)
    q, k, v, do = (jax.random.normal(kk, (B, T, H, D), jnp.bfloat16)
                   for kk in keys[:4])
    qr = jax.random.normal(keys[4], (B, T, H, R), jnp.bfloat16)
    kr = jax.random.normal(keys[5], (B, T, 1, R), jnp.bfloat16)
    pairs = mla_flops.causal_pairs(T) * B * H
    first = None
    for name in [*args.blocks, "one_product_d128"]:
        pair = None
        for backward in args.backward:
            with backward_as(backward):
                line, outs = read_shape(
                    name, backward, (q, k, v, qr, kr, do), pairs,
                    dev.device_kind, args.rehearsal)
            if outs is not None:
                if backward == "pair":
                    pair = outs
                elif pair is not None:
                    line["max_abs_diff_from_pair"] = diffs(outs, pair)
                if name != "one_product_d128":
                    if first is None:
                        first = outs
                    else:
                        line["max_abs_diff_from_first"] = diffs(outs, first)
            print(json.dumps(line), flush=True)


def read_shape(name: str, backward: str, operands, pairs: int,
               device_kind: str, rehearsal: bool):
    """(the JSON line, [output, the five gradients] as float32 or None if
    refused) of one block shape, traced here: under the caller's
    ``backward_as``."""
    *operands, do = operands
    latent = name != "one_product_d128"
    bq, bk = (None, None) if "x" not in name else map(int, name.split("x"))

    def attn(q, k, v, qr, kr):
        rope = dict(q_rope=qr, k_rope=kr) if latent else {}
        return flash_attention(q, k, v, block_q=bq, block_k=bk, **rope)

    def loss(*a):
        return jnp.sum(attn(*a).astype(jnp.float32) * do)

    grads = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4)))
    line = {"blocks": name, "backward": backward, "device": device_kind}
    try:
        with tempfile.TemporaryDirectory() as tmp:
            ms = kernel_ms(grads, operands, None if rehearsal else tmp)
    except Exception as e:  # Mosaic refusing a shape is a reading too
        line["refused"] = f"{type(e).__name__}: {str(e)[:300]}"
        return line, None
    ms = {f.split("_", 2)[2].removeprefix("mla_"): t for f, t in ms.items()}
    if ms:
        # the one-product kernels: the same passes with no rotary width
        widths = mla_flops.kernel_widths({
            "qk_nope_head_dim": D, "qk_rope_head_dim": R if latent else 0,
            "v_head_dim": D})
        if "bwd_dq" not in ms:  # the one kernel: dq's score-wide product too
            widths["bwd_dkv"] += widths["bwd_dq"] - widths["fwd"]
        line.update(
            ms={f: round(t, 4) for f, t in ms.items()},
            # a layer under selective remat: each kernel once
            ms_layer=round(sum(ms.values()), 4),
            peak={f: round(100 * 2 * widths[f] * pairs / (t * 1e-3)
                           / peaks.peak_flops(device_kind), 2)
                  for f, t in ms.items()},
        )
    outs = [jax.jit(attn)(*operands), *grads(*operands)]
    outs = [np.asarray(o.astype(jnp.float32)) for o in outs]
    line["finite"] = bool(all(np.isfinite(o).all() for o in outs))
    return line, outs

if __name__ == "__main__":
    main()
