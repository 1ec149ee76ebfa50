"""What XLA makes of a family's attention block AROUND the flash kernels,
read OFF the chip: the block's forward + backward at the cell's shape,
compiled for a described ``v5e:2x2`` (no device: nothing runs, no time is
measured), and the compiler's own ``estimated_cycles`` of every op of the
entry computation that is not a kernel, summed by op family.

    JAX_PLATFORMS=cpu python3 tools/probe_attn_hlo.py kanana [--top 30]
        [--root <another checkout>] [--keep <file for compiled.as_text()>]

Families: ``kanana`` (latent attention, 2 x 8192, 32 heads of 128 + 64),
``mistral`` (4 x 4096, 32 / 8 heads of 128), ``trinity`` and
``trinity_swa`` (2 x 8192, 32 / 4 heads of 128, QK-norm and the gate; the
full and the banded layer).  ``--root`` reads another checkout's
``ddl_tpu`` (a parent unpacked under ``.pair/``): the same probe, two
trees, is the comparison.

The sums are an ORDER, not a time: on PR 48's two chip readings the
estimate ran ~3x the traced milliseconds (Kanana-2: +6.2 M cycles a layer
estimated where the trace read +2.1 ms), with the sign right both times.
The listing under the sums is the finding: every ``copy`` whose shape is
an activation's is a relayout XLA could not fuse, and its ``op_name`` says
which line of the model asked for it (PERF.md section 6, PR 48: a
``(B, T, H, D) -> (B, T, H * D)`` reshape is one on the TPU).
"""

from __future__ import annotations

import argparse
import collections
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")


def block(family: str):
    """(loss of one attention block, its (layer, x) shapes) of ``family``."""
    import jax
    import jax.numpy as jnp

    from ddl_tpu.models import afmoe, deepseek_v3, llama

    common = dict(vocab=256, d_ff=256, attn_impl="flash",
                  param_dtype=jnp.bfloat16, n_heads=32)
    if family == "kanana":
        B, T, mod = 2, 8192, deepseek_v3
        cfg = deepseek_v3.DeepseekV3Config(
            d_model=2048, n_layers=1, qk_nope_dim=128, qk_rope_dim=64,
            v_head_dim=128, kv_lora_rank=512, n_dense_layers=1, max_seq=T,
            **common)
        call = lambda l, x, p: deepseek_v3.attn(l, x, cfg, p, None)  # noqa: E731
    elif family == "mistral":
        B, T, mod = 4, 4096, llama
        cfg = llama.LlamaConfig(
            d_model=4096, n_layers=1, n_kv_heads=8, max_seq=T, rope_theta=1e6,
            **common)
        call = lambda l, x, p: llama.attn_block(l, x, cfg, p, None)  # noqa: E731
    else:
        B, T, mod = 2, 8192, afmoe
        sliding = family == "trinity_swa"
        kind = afmoe.SLIDING if sliding else afmoe.FULL
        cfg = afmoe.AfmoeConfig(
            d_model=2048, n_kv_heads=4, head_dim=128, d_expert=64,
            n_experts=16, topk=4, layer_types=(kind,), n_dense_layers=1,
            sliding_window=2048, max_seq=T, **common)
        call = lambda l, x, p: afmoe._attn_block(l, x, cfg, p, sliding, None)  # noqa: E731
    params = jax.eval_shape(lambda: mod.init_params(cfg, jax.random.key(0)))

    def loss(layer, x):
        return call(layer, x, jnp.arange(T)).astype(jnp.float32).sum()

    x = jax.ShapeDtypeStruct((B, T, cfg.d_model), jnp.bfloat16)
    return loss, (params["layers"][0], x)


def estimated(text: str):
    """[(cycles, name, shape, operands, op_name)] of the entry computation's
    ops that carry the compiler's estimate (a kernel carries none)."""
    rows = []
    for line in text[text.index("ENTRY "):].splitlines():
        m = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = (\S+) [\w\-]+\((.*?)\)", line)
        cycles = re.search(r'"estimated_cycles":"(\d+)"', line)
        if m and cycles:
            op_name = re.search(r'op_name="([^"]+)"', line)
            rows.append((int(cycles.group(1)), m.group(1), m.group(2),
                         m.group(3)[:48], op_name.group(1)[-64:] if op_name else ""))
    return sorted(rows, reverse=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("family", choices=["kanana", "mistral", "trinity", "trinity_swa"])
    ap.add_argument("--root", default=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    ap.add_argument("--top", type=int, default=24)
    ap.add_argument("--keep")
    args = ap.parse_args()
    sys.path.insert(0, args.root)

    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    # the kernels, not their interpreter: what the models ask of the backend
    jax.default_backend = lambda: "tpu"
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    loss, shapes = block(args.family)
    shapes = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one), shapes)
    text = jax.jit(jax.grad(loss, (0, 1))).lower(*shapes).compile().as_text()
    if args.keep:
        with open(args.keep, "w") as f:
            f.write(text)
    rows = estimated(text)
    by_family = collections.Counter()
    for cycles, name, *_ in rows:
        by_family[re.sub(r"[.\d]+$", "", name)] += cycles
    print(f"{args.family} ({args.root}): {sum(by_family.values()) / 1e6:.2f} M "
          "estimated cycles outside the kernels")
    for name, cycles in by_family.most_common(10):
        print(f"  {name:<36} {cycles / 1e6:8.2f} M")
    for row in rows[:args.top]:
        print("%9d %-26s %-50s <- %-48s %s" % row)


if __name__ == "__main__":
    main()
