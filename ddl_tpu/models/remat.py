"""Named rematerialisation policies, shared by every model family.

The old knob was all-or-nothing: ``remat=True`` wrapped each layer in a
bare ``jax.checkpoint``, recomputing EVERYTHING in the backward pass —
including the attention kernel, the most expensive op in the layer.
That buys HBM at a steep FLOPs price (what it costs on the current chip
is not measured; the policies below exist to let a config pay less).

Policies (``LlamaConfig.remat`` / ``MoeConfig.remat``; bools still
accepted for back compat — ``True`` is ``"full"``, ``False`` is
``"none"``):

- ``"none"`` — save every layer intermediate (fastest step, most HBM).
- ``"full"`` — save only each layer's residual-stream input; recompute
  everything else in the backward pass (classic per-layer remat;
  ``policy=nothing_saveable`` is ``jax.checkpoint``'s default spelled
  explicitly, so the models' lint guard — every ``jax.checkpoint``
  names a policy — holds by construction).
- ``"selective"`` — save each layer's ATTENTION OUTPUT (what the
  attention dispatcher returns, tagged :data:`ATTN_OUT_NAME`) and
  recompute the cheap rest: norms, qkv/rope projections, and the FFN.
  The blockwise flash kernels tag the two values their backward
  kernels read inside their own ``custom_vjp`` rules — that same output
  and the logsumexp, compact ``(B, H, T)`` float32 — so the backward
  pass never re-runs the attention kernel: the standard Megatron-style
  selective trade that buys back most of the full-remat MFU loss at a
  fraction of full activation memory.  Saved a layer beside its input:
  ``2·B·T·H·D`` bytes of bf16 output and ``4·B·H·T`` of logsumexp, 1/64
  of it at D = 128 (at 2 x 8192 rows, 32 heads x 128: 128 MiB + 2 MiB).
  The one-block kernels (``ops/flash_tile.py``, T <= 512) keep no
  logsumexp; their output is saved and their forward re-run.  The
  ``sp`` ring calls the same blockwise rules once a ring step, so it
  keeps each step's partial output and logsumexp beside the combined
  output (1 + ``sp`` local outputs a layer, where it kept the one and
  re-ran the ring's forward kernels): measured by no cell.
  A linear-attention layer's scan (``ops/gated_delta.py``) tags the same
  way what ITS backward reads: its output, and inside the kernels'
  ``custom_vjp`` the state every chunk starts from - ``2·B·(T/64)·H·
  d_k·d_v`` bytes of bf16 (283 MB at one row of 16,384, 30 heads of
  96 x 192) beside ``2·B·T·H·d_v`` of output - so the backward pass runs
  the backward kernel and no forward one; the kernel prepares a chunk
  again itself, and what is recomputed is its operands.
  The fixed-decay scan (``ops/lightning_attention.py``) tags its output and
  its chunk states the same way (``2·B·(T/128)·H·d²`` bytes: 134 MB at one
  row of 16,384, 32 heads of 128), and a block-sparse attention layer
  (``ops/sparse_attention.py``) its output, its compact logsumexp and what
  its selection made - the visibility bitmap (``2·B·G·T·T/64`` bytes: 16.8
  MB at 2 groups) and the merged block lists and their transposes (256 KB)
  - so the backward pass selects nothing again and runs the three
  backward kernels alone.
  A share's routed layer (``models/moe.py:_held_rows``, PR 40: a range
  narrower than half the router, two ``cond`` branches a pass) tags its
  result the same way, ``2·N·D`` bytes a layer (64 MiB at 16,384 tokens
  of 2048): where something behind the layer reads it in the backward
  pass (AFMoE's post-MLP norm) the recomputation holds no ``cond`` and
  no routed forward; where nothing does (DeepSeek-V3's stack) nothing is
  kept.
  A gated short convolution (``models/lfm2_moe.py:gated_short_conv``)
  tags ``BCx = h W_in`` inside its ``custom_vjp`` rule, the ONE value its
  backward pass reads (it computes ``a = B * u`` and the taps' output
  again): ``2·N·3·D`` bytes of bf16 a conv layer (201 MB at 16,384 tokens
  of 2048), for which the rematerialised backward runs neither the operator
  norm nor the layer's largest matmul again (25 MFLOP a token).  ``y`` is
  NOT saved: ``W_out``'s gradient needs it, and one more bandwidth-bound
  pass over ``BCx`` (16 KB a token) is cheaper than 67 MB a layer held from
  the forward pass to the backward.  So a conv layer and step is two
  forward passes of the convolution and one backward.
- ``"dots"`` — ``jax.checkpoint_policies.dots_with_no_batch_dims_
  saveable``: save every non-batched matmul output (all weight
  projections), recompute only elementwise ops and attention — the
  memory-heavier point between none and selective, which RE-RUNS the
  attention kernel in the backward pass (names are not its criterion).

One wrap site for the seven decoder families (:func:`wrap` around a layer's
body in ``models/decoder.py:forward``) and one in each pipelined forward
(``llama.forward_pp``, ``moe._forward_pp``), one tag function (:func:`tag_attn_out`), called by the one attention
dispatcher (``parallel.ring_attention.attention``), by the flash
kernels' rules (the block-sparse ones and their selection among them), by
the two scans, by the gated short convolution's rule and by the routed core's ``_held_rows`` rule, never by a model's layer — so a value is tagged once (a second
tag on the same output would save it twice) and the policy semantics
cannot drift between llama, moe, afmoe, deepseek_v3, olmo_hybrid,
minicpm_sala, lfm2_moe and the pipelined forwards.
"""

from __future__ import annotations

from typing import Any, Callable

#: Checkpoint name carried by every attention output and by the
#: blockwise flash kernels' logsumexp (``checkpoint_name`` is an
#: identity outside a policy-bearing ``jax.checkpoint``, so tagging is
#: unconditional and free).
ATTN_OUT_NAME = "ddl_attn_out"

#: Every accepted policy name, in cheapest-memory-first order.
POLICIES = ("none", "full", "selective", "dots")


def resolve(remat: Any) -> str:
    """Normalise a config's ``remat`` field to a policy name.

    Accepts the policy strings plus the legacy booleans (``True`` →
    ``"full"``, ``False``/``None`` → ``"none"``)."""
    if remat is None or remat is False:
        return "none"
    if remat is True:
        return "full"
    if remat in POLICIES:
        return str(remat)
    raise ValueError(
        f"remat must be a bool or one of {POLICIES}, got {remat!r}"
    )


def tag_attn_out(x: Any) -> Any:
    """Mark a value — an attention output, or a flash kernel's
    logsumexp — as saved under the ``"selective"`` policy (identity
    everywhere else)."""
    from jax.ad_checkpoint import checkpoint_name

    return checkpoint_name(x, ATTN_OUT_NAME)


def _policy(name: str) -> Any:
    import jax

    cp = jax.checkpoint_policies
    if name == "full":
        return cp.nothing_saveable
    if name == "selective":
        return cp.save_only_these_names(ATTN_OUT_NAME)
    if name == "dots":
        return cp.dots_with_no_batch_dims_saveable
    raise ValueError(name)


def wrap(layer_fn: Callable[..., Any], remat: Any) -> Callable[..., Any]:
    """Apply the configured remat policy to a per-layer body.

    ``layer_fn`` is the function scanned over a model's layers (any
    signature/pytree in-out — ``jax.checkpoint`` handles both the
    llama ``x -> x`` and the moe ``(x, aux) -> (x, aux)`` shapes).
    """
    import jax

    name = resolve(remat)
    if name == "none":
        return layer_fn
    return jax.checkpoint(layer_fn, policy=_policy(name))
