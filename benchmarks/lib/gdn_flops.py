"""FLOPs and bytes of an Olmo-Hybrid decoder (``model_type: olmo_hybrid``)
from shapes alone: the model FLOPs a training step requires, and the least
time a chip could take over the gated-delta-rule recurrence of a step.

Both take the benchmark's configuration dict (``benchmarks/configs``), not
the program's config object, so the yardstick does not move when the
program's dataclasses do.  The recurrence is counted in its RECURRENT form:
what the mathematics needs a head and a token, whatever chunking, kernels
or XLA programs execute it.
"""

from __future__ import annotations

from typing import Dict

LINEAR, FULL = "linear_attention", "full_attention"

#: Passes over the recurrence per linear layer and optimizer step, by the
#: program's remat policy.  "selective" saves the chunk states and the
#: output, so its backward pass runs no forward chain; "full" and "dots"
#: keep neither and run it again.  Counted in the step lowered for the TPU
#: (``benchmarks/tests/test_olmo_hybrid.py``).
GDN_CALLS_PER_LAYER = {
    "none": {"fwd": 1, "bwd": 1},
    "selective": {"fwd": 1, "bwd": 1},
    "full": {"fwd": 2, "bwd": 1},
    "dots": {"fwd": 2, "bwd": 1},
}
#: The pass each kernel family on the trace belongs to
#: (``ddl_tpu/ops/gated_delta.py``).
PASS_OF_FAMILY = {"ddl_gdn_fwd": "fwd", "ddl_gdn_bwd": "bwd"}


def causal_pairs(seq: int) -> int:
    """(query, key) pairs a causal row of ``seq`` tokens attends."""
    return seq * (seq + 1) // 2


def recurrence_flops(c: dict) -> Dict[str, float]:
    """FLOPs a head and a token of the recurrent form: forward ``S k``, the
    rank-one update and ``S q``, ``2 d_k d_v`` each; the backward pass
    twice that, as a matmul's is (the cotangent of each product with
    respect to each of its two operands)."""
    fwd = 6.0 * c["linear_key_head_dim"] * c["linear_value_head_dim"]
    return {"fwd": fwd, "bwd": 2.0 * fwd}


def recurrence_bytes(c: dict, itemsize: int = 2) -> Dict[str, float]:
    """Bytes a head and a token that a pass has to move once: forward q, k,
    v in and o out at the operands' width plus g and beta in float32
    (1,160 at 96 / 192 in bfloat16); backward q, k, v and the output's
    cotangent in, the three operands' cotangents out, g, beta and their
    cotangents in float32."""
    dk, dv = c["linear_key_head_dim"], c["linear_value_head_dim"]
    return {
        "fwd": itemsize * (2 * dk + 2 * dv) + 8.0,
        "bwd": itemsize * (2 * dk + 2 * dv) + itemsize * (2 * dk + dv) + 16.0,
    }


def olmo_hybrid_flops_per_token(c: dict, seq: int) -> float:
    """Model FLOPs per token of a ``seq``-long row, forward + backward (x3;
    recomputed operations are not model FLOPs): a linear layer's six
    projections and ``Wo``, its three convolutions and the recurrence in
    its recurrent form; a full layer's four projections and the causal half
    of its pairs at the head size; a SwiGLU a layer; the head over the
    vocabulary slice."""
    d, ff = c["hidden_size"], c["intermediate_size"]
    heads = c["linear_num_value_heads"]
    qk = c["linear_num_key_heads"] * c["linear_key_head_dim"]
    vv = heads * c["linear_value_head_dim"]
    linear = (
        2 * d * (2 * qk + 2 * vv + 2 * heads)  # Wq, Wk, Wv, Wg, Wa, Wb
        + 2 * vv * d  # Wo
        + 2 * c["linear_conv_kernel_dim"] * (2 * qk + vv)
        + heads * recurrence_flops(c)["fwd"]
    )
    full = 4 * 2 * d * d + 2 * 2 * d * causal_pairs(seq) / seq
    forward = 2 * d * c["vocab_size"]
    for kind in c["layer_types"]:
        forward += (linear if kind == LINEAR else full) + 3 * 2 * d * ff
    return 3.0 * forward


def gdn_least_seconds_per_step(c: dict, batch_rows: int, seq: int, remat: str,
                               peak_flops: float, peak_bytes: float) -> Dict[str, float]:
    """The least seconds a chip could take over the recurrence's passes in
    one optimizer step, by pass: ``max(FLOPs / peak_flops, bytes /
    peak_bytes)`` a pass x its calls under ``remat`` x the linear layers.
    At 96 / 192 a forward pass is ~95 FLOP a byte, under a v5e's ridge at
    240: the bytes decide."""
    calls = GDN_CALLS_PER_LAYER[remat]
    units = (
        batch_rows * seq * c["linear_num_value_heads"]
        * sum(kind == LINEAR for kind in c["layer_types"])
    )
    flops, nbytes = recurrence_flops(c), recurrence_bytes(c)
    return {
        which: calls[which] * units * max(
            flops[which] / peak_flops, nbytes[which] / peak_bytes
        )
        for which in ("fwd", "bwd")
    }
