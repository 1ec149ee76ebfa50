"""FLOPs of a ``deepseek_v3``-shaped decoder (Kanana-2-30B-A3B's shape) as
ONE CHIP'S SHARE runs it, from shapes alone: the model FLOPs a training
step requires, and the useful FLOPs of the latent-attention flash kernels
in a step.

Both take the benchmark's configuration dict (``benchmarks/configs``),
not the program's config object, so the yardstick does not move when the
program's dataclasses do.
"""

from __future__ import annotations

from typing import Dict

#: Calls of each kernel per layer and optimizer step, by the program's
#: remat policy.  "selective" saves the attention's output but not the
#: kernel's logsumexp, so the backward pass runs the forward kernel once
#: more.  Counted in the step lowered for the TPU
#: (``benchmarks/tests/test_deepseek_v3.py``).
MLA_CALLS_PER_LAYER = {
    "none": {"fwd": 1, "bwd_dq": 1, "bwd_dkv": 1},
    "selective": {"fwd": 2, "bwd_dq": 1, "bwd_dkv": 1},
    "full": {"fwd": 2, "bwd_dq": 1, "bwd_dkv": 1},
    "dots": {"fwd": 2, "bwd_dq": 1, "bwd_dkv": 1},
}


def causal_pairs(seq: int) -> int:
    """(query, key) pairs a causal row of ``seq`` tokens attends."""
    return seq * (seq + 1) // 2


def kernel_widths(c: dict) -> Dict[str, int]:
    """Contraction widths of the matmul passes each blockwise kernel makes
    over a causal (query, key) pair and a head, 2 FLOPs a unit
    (``ddl_tpu/ops/flash_attention.py``) - at the published head sizes
    (score 192 = 128 + 64 rotary, value 128): fwd q k^T (192), p v (128);
    dq: q k^T again (192), dO v^T (128), dS k (192); dkv: q k^T again
    (192), p^T dO (128), dO v^T (128), dS^T q (192).  USEFUL widths: the
    MXU pads the 64-deep rotary product to its own depth, and that padding
    is not counted."""
    score = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    value = c["v_head_dim"]
    return {"fwd": score + value, "bwd_dq": 2 * score + value,
            "bwd_dkv": 2 * score + 2 * value}


def held_expectation(c: dict) -> float:
    """Routed experts a token runs HERE at perfect balance:
    ``experts_per_tok * held / router width`` (0.75 for 16 of 128, top-6)."""
    return (
        c["num_experts_per_tok"] * c["n_routed_experts"]
        / c["published"]["n_routed_experts"]
    )


def mla_flops_per_token(c: dict, seq: int) -> float:
    """Model FLOPs per token of a ``seq``-long row, forward + backward
    (x3; recomputed operations are not model FLOPs): the query projection,
    the latent down- and up-projections, the output projection, the causal
    pairs at the score's and the value's widths, the leading dense MLPs,
    the router over its whole width, the shared experts, the routed experts
    at the balanced expectation of the held share, and the head over the
    vocabulary slice."""
    d, heads = c["hidden_size"], c["num_attention_heads"]
    nope, rope, value = (
        c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    )
    rank = c["kv_lora_rank"]
    expert = 3 * 2 * d * c["moe_intermediate_size"]
    attention = (
        2 * d * heads * (nope + rope)  # Wq
        + 2 * d * (rank + rope)  # Wkv_a
        + 2 * rank * heads * (nope + value)  # Wkv_b
        + 2 * heads * value * d  # Wo
        + 2 * heads * (nope + rope + value) * causal_pairs(seq) / seq
    )
    forward = 2 * d * c["vocab_size"]
    for layer in range(c["num_hidden_layers"]):
        forward += attention
        if layer < c["first_k_dense_replace"]:
            forward += 3 * 2 * d * c["intermediate_size"]
        else:
            forward += (
                2 * d * c["published"]["n_routed_experts"]  # the router
                + c["n_shared_experts"] * expert
                + held_expectation(c) * expert
            )
    return 3.0 * forward


def mla_kernel_flops_per_step(c: dict, batch_rows: int, seq: int,
                              remat: str) -> Dict[str, float]:
    """USEFUL FLOPs of each ``ddl_flash_mla_*`` family in one optimizer
    step, by the family's name on the trace: causal pairs (not executed
    blocks) x rows x heads x 2 x the kernel's contraction widths x its
    calls under ``remat``, over every layer."""
    calls = MLA_CALLS_PER_LAYER[remat]
    per_unit = (
        causal_pairs(seq) * batch_rows * c["num_attention_heads"] * 2
        * c["num_hidden_layers"]
    )
    return {
        "ddl_flash_mla_" + kernel: float(per_unit * width * calls[kernel])
        for kernel, width in kernel_widths(c).items()
    }
