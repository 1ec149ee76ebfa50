"""Model FLOPs a training step requires, from shapes alone.

Matmul FLOPs of the forward pass per sample, times three for forward +
backward (the backward of a matmul is two matmuls).  Recomputed
operations are not counted, nor are norms, activations, softmax or the
optimizer: the MFU convention.  The decoder count is copied from
``bench.py:_model_flops_per_token`` / ``_attn_lm_head_flops_per_token``
(a later PR deletes the original); the ViT count stands beside it.

Both take the benchmark's configuration dict (``benchmarks/configs``),
not the program's config object, so the yardstick does not move when the
program's dataclasses do.
"""

from __future__ import annotations


def decoder_flops_per_token(c: dict, seq: int) -> float:
    """Llama-shaped decoder (GQA, SwiGLU, untied head), per token of a
    ``seq``-long row.  Attention scores and attn@v count the causal
    half: masked positions are not model FLOPs."""
    d, hd = c["hidden_size"], c["head_dim"]
    heads, kv = c["num_attention_heads"], c["num_key_value_heads"]
    per_layer = (
        2 * d * (heads + 2 * kv) * hd  # q, k, v projections
        + 2 * heads * hd * d  # output projection
        + 2 * 2 * seq * heads * hd / 2  # scores + attn@v, causal half
        + 3 * 2 * d * c["intermediate_size"]  # gate, up, down
    )
    forward = c["num_hidden_layers"] * per_layer + 2 * d * c["vocab_size"]
    return 3.0 * forward


def vit_flops_per_image(c: dict) -> float:
    """``models/vit.py`` as it stands: patch embedding as one matmul,
    full (non-causal) attention over the patches, a two-matrix MLP, mean
    pool, linear head."""
    d, heads = c["hidden_size"], c["num_attention_heads"]
    hd = d // heads
    tokens = (c["image_size"] // c["patch_size"]) ** 2
    patch_dim = c["patch_size"] ** 2 * c["num_channels"]
    per_token_layer = (
        2 * d * 3 * d  # q, k, v projections
        + 2 * d * d  # output projection
        + 2 * 2 * tokens * heads * hd  # scores + attn@v, every position
        + 2 * 2 * d * c["intermediate_size"]  # up, down
    )
    forward = tokens * (
        2 * patch_dim * d + c["num_hidden_layers"] * per_token_layer
    ) + 2 * d * c["num_labels"]
    return 3.0 * forward
