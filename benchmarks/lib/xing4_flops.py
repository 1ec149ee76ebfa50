"""FLOPs and bytes of a Xing4.0 decoder (``model_type: xing4_0``) as ONE
CHIP'S SHARE runs it, from shapes alone: the model FLOPs a training step
requires, and the least time a chip could take over the hyper-connected
residual path's passes of a step.

Both take the benchmark's configuration dict (``benchmarks/configs``), not
the program's config object, so the yardstick does not move when the
program's dataclasses do.  The path's floor is of the WORK - the bytes a
wrap has to move once a pass - whatever implements it: XLA's fusions today,
a kernel later, are read against the same bytes.
"""

from __future__ import annotations

from typing import Dict

#: Passes of each routine per LAYER (two wraps) and optimizer step, by the
#: program's remat policy.  A rematerialised layer reads the stream for ``h``
#: again in both wraps and writes the stream behind its first wrap again
#: (the second wrap's result is the next layer's saved input).  Counted in
#: the traced step (``tests/test_xing4.py``).
HC_PASSES_PER_LAYER = {
    "none": {"pre_fwd": 2, "post_fwd": 2, "pre_bwd": 2, "post_bwd": 2},
    "selective": {"pre_fwd": 4, "post_fwd": 3, "pre_bwd": 2, "post_bwd": 2},
    "full": {"pre_fwd": 4, "post_fwd": 3, "pre_bwd": 2, "post_bwd": 2},
    "dots": {"pre_fwd": 4, "post_fwd": 3, "pre_bwd": 2, "post_bwd": 2},
}
#: Passes over a stream's two ends (the replication behind the embedding,
#: the sum in front of the head), forward and backward; never recomputed.
END_PASSES_PER_STREAM = 4


def causal_pairs(seq: int) -> int:
    """(query, key) pairs a causal row of ``seq`` tokens attends."""
    return seq * (seq + 1) // 2


def held_experts_per_token(c: dict) -> float:
    """Routed experts a token on this chip at balance: ``num_experts_per_tok``
    x the held share of the router's outputs (4 x 8 / 64 = 0.5)."""
    return (c["num_experts_per_tok"] * c["n_routed_experts"]
            / c["published"]["n_routed_experts"])


def stack_layers(c: dict) -> int:
    """Layers that carry the stream: the stack's and one a multi-token-
    prediction module."""
    return c["num_hidden_layers"] + c["num_nextn_predict_layers"]


def xing4_flops_per_token(c: dict, seq: int) -> float:
    """Model FLOPs per token of a ``seq``-long row, forward + backward (x3;
    recomputed operations are not model FLOPs): latent attention's
    projections with the query's low-rank step and the causal pairs at the
    score's 192 and the value's 128; the dense SwiGLU of a leading layer;
    elsewhere the router over its whole width, the shared expert and the
    held share's balanced expectation of routed experts; each wrap's 24
    projections of the 4 x 3584 stream and its mixing products (``Hpre X``,
    ``Hres X``, ``Hpost^T y``); for the multi-token-prediction module ``W_eh``
    and one more routed layer; the head over the vocabulary slice once a
    head.  Sinkhorn's rounds are not counted."""
    d, heads, n = c["hidden_size"], c["num_attention_heads"], c["hc_mult"]
    nope, rope, value = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    rank, q_rank = c["kv_lora_rank"], c["q_lora_rank"]
    expert = 3 * 2 * d * c["moe_intermediate_size"]
    attention = (
        2 * d * q_rank + 2 * q_rank * heads * (nope + rope)  # Wq_a, Wq_b
        + 2 * d * (rank + rope)  # Wkv_a
        + 2 * rank * heads * (nope + value)  # Wkv_b
        + 2 * heads * value * d  # Wo
        + 2 * heads * (nope + rope + value) * causal_pairs(seq) / seq
    )
    wrap = 2 * n * d * (2 * n + n * n) + 2 * n * d + 2 * n * n * d + 2 * n * d
    routed = (
        2 * d * c["published"]["n_routed_experts"]
        + c["n_shared_experts"] * expert + held_experts_per_token(c) * expert
    )
    modules = c["num_nextn_predict_layers"]
    forward = (1 + modules) * 2 * d * c["vocab_size"]
    for layer in range(c["num_hidden_layers"]):
        dense = layer < c["first_k_dense_replace"]
        forward += attention + 2 * wrap + (
            3 * 2 * d * c["intermediate_size"] if dense else routed)
    forward += modules * (2 * 2 * d * d + attention + 2 * wrap + routed)
    return 3.0 * forward


def hc_pass_bytes(c: dict, itemsize: int = 2) -> float:
    """Bytes a token that one pass of one routine (``pre`` or ``post``, forward
    or backward) has to move once: the stream (4 x 3584) read or written once,
    one row beside it (``h``, ``y`` or their cotangents) and a wrap's matrices
    (24 float32): 35,936 at 4 x 3584 in bfloat16.  A wrap and pass is a ``pre``
    and a ``post``: the stream read once and written once, ``h``, ``y`` and the
    matrices."""
    return (c["hc_mult"] + 1) * c["hidden_size"] * itemsize + 4 * (
        2 * c["hc_mult"] + c["hc_mult"] ** 2)


def hc_least_seconds_per_step(c: dict, batch_rows: int, seq: int, remat: str,
                              peak_bytes: float) -> Dict[str, float]:
    """The least seconds a chip could take over the residual path's passes
    in one optimizer step, by routine: bytes / peak HBM bytes a second x the
    passes under ``remat`` x the layers that carry a stream, and the streams'
    two ends.  Bandwidth alone: a pass is a few FLOP a byte."""
    passes = HC_PASSES_PER_LAYER[remat]
    tokens = batch_rows * seq
    out = {
        which: n * stack_layers(c) * tokens * hc_pass_bytes(c) / peak_bytes
        for which, n in passes.items()
    }
    streams = 1 + c["num_nextn_predict_layers"]
    out["ends"] = (END_PASSES_PER_STREAM * streams * tokens
                   * (c["hc_mult"] + 1) * c["hidden_size"] * 2 / peak_bytes)
    return out
