"""FLOPs of a sparse-expert decoder (OLMoE's shape), from shapes alone:
the model FLOPs a training step requires, and the FLOPs the grouped
matmuls of the routed expert layer execute in a step.

Both take the benchmark's configuration dict (``benchmarks/configs``),
not the program's config object, so the yardstick does not move when the
program's dataclasses do.
"""

from __future__ import annotations

#: Grouped-matmul calls per expert layer and optimizer step, by the
#: program's remat policy: gate, up and down forward (3), their
#: gradients with respect to the rows (3) and to the expert weights (3);
#: a policy that does not save the expert layer's intermediates runs the
#: three forward calls once more in the backward pass.  Counted in the
#: lowered and in the TPU-compiled step (``benchmarks/tests/test_olmoe.py``,
#: ``tests/test_tpu_compile.py``): nothing is eliminated, nothing added.
GMM_CALLS_PER_LAYER = {
    "none": 9, "selective": 12, "full": 12, "dots": 12,
}


def moe_decoder_flops_per_token(c: dict, seq: int) -> float:
    """Model FLOPs per token of a ``seq``-long row, forward + backward
    (x3; recomputed operations are not model FLOPs).  Projections,
    causal-half attention, the router, the ``num_experts_per_tok``
    ACTIVE experts only (a dropless step computes exactly those), and
    the untied head."""
    d, hd = c["hidden_size"], c["head_dim"]
    heads, kv = c["num_attention_heads"], c["num_key_value_heads"]
    per_layer = (
        2 * d * (heads + 2 * kv) * hd  # q, k, v projections
        + 2 * heads * hd * d  # output projection
        + 2 * 2 * seq * heads * hd / 2  # scores + attn@v, causal half
        + 2 * d * c["num_experts"]  # router
        + c["num_experts_per_tok"] * 3 * 2 * d * c["intermediate_size"]
    )
    forward = c["num_hidden_layers"] * per_layer + 2 * d * c["vocab_size"]
    return 3.0 * forward


def gmm_flops_per_step(c: dict, tokens_per_step: int, remat: str) -> float:
    """FLOPs the grouped matmuls EXECUTE in one optimizer step: every
    call is ``2 * (tokens * experts_per_tok) * hidden * expert_width``
    (the row count is the same for all nine shapes: the contraction is
    over ``hidden`` or ``expert_width``, the other is the output), times
    the calls a layer makes under ``remat``, times the layers."""
    rows = tokens_per_step * c["num_experts_per_tok"]
    one_call = 2.0 * rows * c["hidden_size"] * c["intermediate_size"]
    return c["num_hidden_layers"] * GMM_CALLS_PER_LAYER[remat] * one_call
