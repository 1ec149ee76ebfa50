"""FLOPs of an AFMoE decoder (Trinity-Mini's shape) as ONE CHIP'S SHARE
runs it, from shapes alone: the model FLOPs a training step requires, and
the useful FLOPs of the flash-attention kernels in a step.

Both take the benchmark's configuration dict (``benchmarks/configs``),
not the program's config object, so the yardstick does not move when the
program's dataclasses do.
"""

from __future__ import annotations

from typing import Dict, Optional

#: Matmul passes over the attended (query, key) pairs that each blockwise
#: kernel makes, ``2 * head_dim`` FLOPs a pass, a pair and a head: forward
#: q k^T and p v; dq: q k^T again, dO v^T, dS k; dkv: q k^T again, p^T dO,
#: dO v^T, dS^T q (``ddl_tpu/ops/flash_attention.py``).
FLASH_PASSES = {"fwd": 2, "bwd_dq": 3, "bwd_dkv": 4}

#: Calls of each kernel per attention layer and optimizer step, by the
#: program's remat policy.  "selective" saves the attention's output but
#: not the kernel's logsumexp, so the backward pass runs the forward kernel
#: once more.  Counted in the step lowered for the TPU
#: (``benchmarks/tests/test_afmoe.py``): nothing eliminated, nothing added.
FLASH_CALLS_PER_LAYER = {
    "none": {"fwd": 1, "bwd_dq": 1, "bwd_dkv": 1},
    "selective": {"fwd": 2, "bwd_dq": 1, "bwd_dkv": 1},
    "full": {"fwd": 2, "bwd_dq": 1, "bwd_dkv": 1},
    "dots": {"fwd": 2, "bwd_dq": 1, "bwd_dkv": 1},
}


def attended_pairs(seq: int, window: Optional[int] = None) -> int:
    """(query, key) pairs a causal row of ``seq`` tokens attends:
    ``sum_i min(i + 1, window)``."""
    w = seq if window is None else min(window, seq)
    return w * (w + 1) // 2 + (seq - w) * w


def _window_of(c: dict, kind: str) -> Optional[int]:
    return c["sliding_window"] if kind == "sliding_attention" else None


def held_expectation(c: dict) -> float:
    """Routed experts a token runs HERE at perfect balance:
    ``experts_per_tok * held / router width``."""
    return (
        c["num_experts_per_tok"] * c["num_experts"] / c["published"]["num_experts"]
    )


def afmoe_flops_per_token(c: dict, seq: int) -> float:
    """Model FLOPs per token of a ``seq``-long row, forward + backward
    (x3; recomputed operations are not model FLOPs): the four projections
    and the gate's, the attended pairs counted exactly per layer kind, the
    dense MLP, the router over its whole width, the shared expert, the
    routed experts at the balanced expectation of the held share, and the
    head over the vocabulary slice."""
    d, hd = c["hidden_size"], c["head_dim"]
    heads, kv = c["num_attention_heads"], c["num_key_value_heads"]
    expert = 3 * 2 * d * c["moe_intermediate_size"]
    forward = 2 * d * c["vocab_size"]
    for layer, kind in enumerate(c["layer_types"]):
        forward += (
            2 * d * (2 * heads + 2 * kv) * hd  # q, gate, k, v projections
            + 2 * heads * hd * d  # output projection
            # scores + attn @ v over the pairs this kind attends
            + 2 * 2 * heads * hd * attended_pairs(seq, _window_of(c, kind)) / seq
        )
        if layer < c["num_dense_layers"]:
            forward += 3 * 2 * d * c["intermediate_size"]
        else:
            forward += (
                2 * d * c["published"]["num_experts"]  # the router
                + c["num_shared_experts"] * expert
                + held_expectation(c) * expert
            )
    return 3.0 * forward


def flash_flops_per_step(c: dict, batch_rows: int, seq: int,
                         remat: str) -> Dict[str, float]:
    """USEFUL FLOPs of each flash kernel family in one optimizer step, by
    the family's name on the trace: attended pairs (not executed blocks) x
    rows x heads x ``2 * head_dim`` a pass x the kernel's passes x its calls
    under ``remat``, over the layers of its kind.  Sliding layers narrower
    than the row run the ``ddl_flash_swa_*`` kernels, the others the
    ``ddl_flash_*`` ones."""
    calls = FLASH_CALLS_PER_LAYER[remat]
    out: Dict[str, float] = {}
    for kind in c["layer_types"]:
        window = _window_of(c, kind)
        banded = window is not None and window < seq
        per_pass = (
            attended_pairs(seq, window) * batch_rows
            * c["num_attention_heads"] * 2 * c["head_dim"]
        )
        for kernel, passes in FLASH_PASSES.items():
            name = ("ddl_flash_swa_" if banded else "ddl_flash_") + kernel
            out[name] = out.get(name, 0.0) + per_pass * passes * calls[kernel]
    return out
