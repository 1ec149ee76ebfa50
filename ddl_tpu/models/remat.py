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
- ``"selective"`` — save each layer's ATTENTION OUTPUT (the tensors
  tagged :data:`ATTN_OUT_NAME` by the shared attention blocks) and
  recompute the cheap rest: norms, qkv/rope projections, and the FFN.
  The backward pass then never re-runs the attention kernel — the
  standard Megatron-style selective trade that buys back most of the
  full-remat MFU loss at a fraction of full activation memory.
- ``"dots"`` — ``jax.checkpoint_policies.dots_with_no_batch_dims_
  saveable``: save every non-batched matmul output (all weight
  projections), recompute only elementwise ops and attention — the
  memory-heavier, FLOPs-lighter point between none and selective.

One wrap site per model family (:func:`wrap` around the layer body),
one tag site per attention block (:func:`tag_attn_out`) — the policy
semantics cannot drift between llama, moe, and the pipelined forwards.
"""

from __future__ import annotations

from typing import Any, Callable

#: Checkpoint name carried by every attention block's output tensor
#: (``checkpoint_name`` is an identity outside a policy-bearing
#: ``jax.checkpoint``, so tagging is unconditional and free).
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
    """Mark an attention block's output as saveable under the
    ``"selective"`` policy (identity everywhere else)."""
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
