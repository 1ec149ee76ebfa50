"""FLOPs and bytes of an LFM2-MoE decoder (``model_type: lfm2_moe``) from
shapes alone: the model FLOPs a training step requires, and the least time
a chip could take over the gated short convolution's passes of a step.

Both take the benchmark's configuration dict (``benchmarks/configs``), not
the program's config object, so the yardstick does not move when the
program's dataclasses do.  The convolution's floor is of the WORK - the
bytes ``C * taps(B * u)`` and its backward have to move once a pass -
whatever implements it: XLA's fusions today, a kernel later, are read
against the same bytes.
"""

from __future__ import annotations

from typing import Dict

CONV, FULL = "conv", "full_attention"

#: Passes over the gated short convolution per conv layer and optimizer
#: step, by the program's remat policy.  A layer's rule keeps ``BCx`` alone
#: (``models/lfm2_moe.py:gated_short_conv``): wherever a policy rematerialises
#: the layer, ``y`` is computed once more for ``W_out``'s gradient.  Counted in
#: the traced step (``tests/test_lfm2_moe.py``).
CONV_CALLS_PER_LAYER = {
    "none": {"fwd": 1, "bwd": 1},
    "selective": {"fwd": 2, "bwd": 1},
    "full": {"fwd": 2, "bwd": 1},
    "dots": {"fwd": 2, "bwd": 1},
}


def causal_pairs(seq: int) -> int:
    """(query, key) pairs a causal row of ``seq`` tokens attends."""
    return seq * (seq + 1) // 2


def held_experts_per_token(c: dict) -> float:
    """Routed experts a token on this chip at balance: ``num_experts_per_tok``
    x the held share of the router's outputs (4 x 8 / 64 = 0.5)."""
    return c["num_experts_per_tok"] * c["num_experts"] / c["published"]["num_experts"]


def lfm2_flops_per_token(c: dict, seq: int) -> float:
    """Model FLOPs per token of a ``seq``-long row, forward + backward (x3;
    recomputed operations are not model FLOPs): a conv layer's ``W_in`` and
    ``W_out`` and its taps (``2 K d``); an attention layer's four projections
    at 32 x 64 over 8 x 64 and the causal half of its pairs at the head
    size; the dense SwiGLU of a leading layer; elsewhere the router and the
    held share's balanced expectation of routed experts; the head over the
    vocabulary slice."""
    d, heads = c["hidden_size"], c["num_attention_heads"]
    kv = c["num_key_value_heads"] * (d // heads)
    conv = 2 * d * 3 * d + 2 * d * d + 2 * c["conv_L_cache"] * d
    attn = 2 * d * (2 * d + 2 * kv) + 2 * 2 * d * causal_pairs(seq) / seq
    dense = 3 * 2 * d * c["intermediate_size"]
    routed = (
        2 * d * c["published"]["num_experts"]
        + held_experts_per_token(c) * 3 * 2 * d * c["moe_intermediate_size"]
    )
    forward = 2 * d * c["vocab_size"]
    for li, kind in enumerate(c["layer_types"]):
        forward += conv if kind == CONV else attn
        forward += dense if li < c["num_dense_layers"] else routed
    return 3.0 * forward


def shortconv_bytes(c: dict, itemsize: int = 2) -> Dict[str, float]:
    """Bytes a token and conv layer that a pass has to move once: forward
    ``BCx`` in (3 d) and ``y`` out (d); backward ``BCx`` and ``dy`` in and
    ``dBCx`` out (3 + 1 + 3) d - 16,384 and 28,672 at 2048 in bfloat16.
    The taps and their cotangent are K d numbers a layer: nothing."""
    d = c["hidden_size"]
    return {"fwd": 4.0 * d * itemsize, "bwd": 7.0 * d * itemsize}


def shortconv_least_seconds_per_step(c: dict, batch_rows: int, seq: int,
                                     remat: str, peak_bytes: float) -> Dict[str, float]:
    """The least seconds a chip could take over the gated short convolution's
    passes in one optimizer step, by pass: bytes / peak HBM bytes a second x
    the calls under ``remat`` x the conv layers.  Bandwidth alone: a pass is
    ~2 FLOP a byte, far under any ridge."""
    calls = CONV_CALLS_PER_LAYER[remat]
    units = batch_rows * seq * sum(kind == CONV for kind in c["layer_types"])
    nbytes = shortconv_bytes(c)
    return {
        which: calls[which] * units * nbytes[which] / peak_bytes
        for which in ("fwd", "bwd")
    }
