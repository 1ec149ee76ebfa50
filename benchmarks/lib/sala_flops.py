"""FLOPs and bytes of a MiniCPM-SALA decoder (``model_type: minicpm_sala``)
from shapes alone: the model FLOPs a training step requires, the least time
a chip could take over the fixed-decay recurrence of a step, and the useful
FLOPs of the sparse attention's visible pairs.

All take the benchmark's configuration dict (``benchmarks/configs``), not
the program's config object, so the yardstick does not move when the
program's dataclasses do.  The recurrence is counted in its RECURRENT form,
the sparse attention by ``visible(t)``'s pairs: what the mathematics needs,
whatever chunking, tiles, merged lists or masks execute it.
"""

from __future__ import annotations

from typing import Dict

LIGHTNING, SPARSE = "lightning-attn", "minicpm4"

#: Calls of each kernel family per layer of its kind and optimizer step, by
#: the program's remat policy.  "selective" saves what each backward kernel
#: reads (outputs, logsumexp, block lists, chunk states), so no forward
#: kernel runs twice; "full" and "dots" keep none of it.  Counted in the
#: step lowered for the TPU (``tests/test_minicpm_sala.py``).
CALLS_PER_LAYER = {
    "none": {"fwd": 1, "bwd": 1},
    "selective": {"fwd": 1, "bwd": 1},
    "full": {"fwd": 2, "bwd": 1},
    "dots": {"fwd": 2, "bwd": 1},
}
#: Matmul passes over the visible pairs each sparse kernel makes: forward
#: ``q k^T`` and ``p v``; ``dq``: ``q k^T``, ``dO v^T``, ``ds k``; ``dkv``:
#: ``q k^T``, ``dO v^T``, ``p^T dO``, ``ds^T q``.
SPARSE_PASSES = {
    "ddl_flash_sparse_fwd": ("fwd", 2),
    "ddl_flash_sparse_bwd_dq": ("bwd", 3),
    "ddl_flash_sparse_bwd_dkv": ("bwd", 4),
}


def sparse_sizes(c: dict) -> Dict[str, int]:
    s = c["sparse_config"]
    return {
        "block": s["block_size"], "kernel": s["kernel_size"],
        "stride": s["kernel_stride"], "topk": s["topk"],
        "init_blocks": s["init_blocks"],
        "local_blocks": s["window_size"] // s["block_size"],
        "dense_len": s["dense_len"],
    }


def causal_pairs(seq: int) -> int:
    """(query, key) pairs a causal row of ``seq`` tokens attends."""
    return seq * (seq + 1) // 2


def visible_pairs(c: dict, seq: int) -> float:
    """(query, key) pairs ``visible(t)`` holds over a row of ``seq``
    positions (a head's): every causal pair of a row up to ``dense_len``;
    beyond, a query of block ``b`` sees ``min(b + 1, topk + local_blocks)``
    blocks, its own up to itself (20,016 of 32,896 block pairs at 16,384:
    60.8% of the triangle)."""
    s = sparse_sizes(c)
    if seq <= s["dense_len"]:
        return float(causal_pairs(seq))
    block = s["block"]
    most = s["topk"] + s["local_blocks"]
    pairs = 0.0
    for b in range(-(-seq // block)):
        rows = min(block, seq - b * block)
        whole = min(b + 1, most) - 1  # whole blocks, then the query's own
        pairs += rows * whole * block + rows * (rows + 1) / 2
    return pairs


def select_flops_per_token(c: dict, seq: int) -> float:
    """The selection's score FLOPs a token (forward only; no gradient): every
    query head against the compressed keys in its past, half of ``seq /
    stride`` on average; nothing for a row up to ``dense_len``."""
    s = sparse_sizes(c)
    if seq <= s["dense_len"]:
        return 0.0
    keys = ((seq - s["kernel"]) // s["stride"] + 1) / 2
    return 2.0 * c["head_dim"] * keys * c["num_attention_heads"]


def recurrence_flops(c: dict) -> Dict[str, float]:
    """FLOPs a head and a token of the recurrent form: forward the rank-one
    update and ``q S``, ``2 d^2`` each; the backward pass twice that."""
    fwd = 4.0 * c["lightning_head_dim"] ** 2
    return {"fwd": fwd, "bwd": 2.0 * fwd}


def recurrence_bytes(c: dict, itemsize: int = 2) -> Dict[str, float]:
    """Bytes a head and a token that a pass has to move once: forward q, k,
    v in and o out (1,024 at 128 in bfloat16); backward q, k, v and the
    output's cotangent in, three cotangents out (1,792)."""
    d = c["lightning_head_dim"]
    return {"fwd": itemsize * 4.0 * d, "bwd": itemsize * 7.0 * d}


def minicpm_sala_flops_per_token(c: dict, seq: int) -> float:
    """Model FLOPs per token of a ``seq``-long row, forward + backward (x3;
    the selection x1; recomputed operations are not model FLOPs): a
    lightning layer's five projections and the recurrence in its recurrent
    form; a sparse layer's projections (two of them over the key-value
    heads), ``visible(t)``'s pairs and the selection's scores; a SwiGLU a
    layer; the head over the vocabulary slice."""
    d, ff = c["hidden_size"], c["intermediate_size"]
    lw = c["lightning_nh"] * c["lightning_head_dim"]
    qw = c["num_attention_heads"] * c["head_dim"]
    kw = c["num_key_value_heads"] * c["head_dim"]
    lightning = 5 * 2 * d * lw + c["lightning_nh"] * recurrence_flops(c)["fwd"]
    sparse = (
        2 * d * (3 * qw + 2 * kw)
        + 2 * 2 * c["head_dim"] * c["num_attention_heads"] * visible_pairs(c, seq) / seq
    )
    forward = 2 * d * c["vocab_size"]
    selection = 0.0
    for kind in c["mixer_types"]:
        forward += (lightning if kind == LIGHTNING else sparse) + 3 * 2 * d * ff
        selection += select_flops_per_token(c, seq) if kind == SPARSE else 0.0
    return 3.0 * forward + selection


def lightning_least_seconds_per_step(c: dict, batch_rows: int, seq: int, remat: str,
                                     peak_flops: float, peak_bytes: float) -> Dict[str, float]:
    """The least seconds a chip could take over the recurrence's passes in
    one optimizer step, by pass: ``max(FLOPs / peak_flops, bytes /
    peak_bytes)`` a pass x its calls under ``remat`` x the lightning layers.
    At 128 a forward pass is 64 FLOP a byte, under a v5e's ridge at 240: the
    bytes decide."""
    calls = CALLS_PER_LAYER[remat]
    units = (
        batch_rows * seq * c["lightning_nh"]
        * sum(kind == LIGHTNING for kind in c["mixer_types"])
    )
    flops, nbytes = recurrence_flops(c), recurrence_bytes(c)
    return {
        which: calls[which] * units * max(
            flops[which] / peak_flops, nbytes[which] / peak_bytes
        )
        for which in ("fwd", "bwd")
    }


def sparse_useful_flops_per_step(c: dict, batch_rows: int, seq: int,
                                 remat: str) -> Dict[str, float]:
    """USEFUL FLOPs of each ``ddl_flash_sparse_*`` family in one optimizer
    step: ``visible(t)``'s pairs x the query heads x ``2 head_dim`` a
    matmul pass x the family's passes x its calls under ``remat`` x the
    sparse layers.  Nothing for rows that run the dense kernels."""
    if seq <= sparse_sizes(c)["dense_len"]:
        return {}
    calls = CALLS_PER_LAYER[remat]
    per_pass = (
        2.0 * c["head_dim"] * c["num_attention_heads"] * visible_pairs(c, seq)
        * batch_rows * sum(kind == SPARSE for kind in c["mixer_types"])
    )
    return {
        family: passes * calls[which] * per_pass
        for family, (which, passes) in SPARSE_PASSES.items()
    }


def select_flops_per_step(c: dict, batch_rows: int, seq: int, remat: str) -> float:
    """The selection kernel's score FLOPs in one optimizer step."""
    return (
        CALLS_PER_LAYER[remat]["fwd"] * batch_rows * seq
        * select_flops_per_token(c, seq)
        * sum(kind == SPARSE for kind in c["mixer_types"])
    )


def steps_traced(m: dict) -> float:
    """Optimizer steps the traced window holds, per chip: executions of the
    step program by time (``mla_roofline_share`` counts them the same way) x
    the steps a window."""
    busy = m["trace"]["step_program_busy_s"]
    return sum(busy) / busy[len(busy) // 2] / m["chips"] * m["steps_per_window"]
