"""Attention on a sequence that fits one block: one Pallas kernel a direction.

``flash_attention.py``'s kernels are blockwise: a (batch, head, Q block, KV
block) grid, an online-softmax carry across KV blocks, operands moved to
``(B, H, T, D)`` and padded to the block.  When the whole sequence is one
block (ViT-B/16: T = 196, 12 heads x 64) none of that buys anything and
all of it costs: 1,536 grid steps of 11 MFLOP each, a carry for a loop of
length one, and 8-11 relayouts a layer of every operand.  The kernels here
are what is left when there is one block:

- operands stay in the projections' own layout, ``(B, T, H*D)`` — the
  ``(B, T, H, D)`` arguments viewed without a copy; heads are lane slices
  taken inside the kernel, the output and all three gradients leave in the
  same layout.  No ``moveaxis``, no pad, no slice: a block as long as the
  whole ``T`` is legal off the tiling, and the ragged tail is Mosaic's
  (masked) business;
- a grid step holds one batch row and as many heads as fit the VMEM
  budget, so a ViT call is 128 steps; inside it 128-lane groups of heads
  are a loop, two groups an iteration (PERF.md §6, PR 27:
  all twelve heads unrolled is 1.2% faster and triples the kernel body
  Mosaic compiles; one group an iteration is 7% slower);
- one KV block means a plain row softmax — no ``m``/``l``/``acc`` scratch,
  no rescale, no ``pl.when``.  Scores and softmax in float32, ``p`` cast to
  the operand dtype for the MXU, float32 accumulation: the blockwise
  kernels' arithmetic;
- one backward kernel: with q, k, v, do of a head resident, the
  probabilities are recomputed once — from the scores alone, so the
  forward keeps no ``lse`` and no output for it — and ``dq``, ``dk``,
  ``dv`` leave together.

:func:`fits` is the whole dispatch rule, shapes and arguments only;
``flash_attention()`` asks it and nothing else does.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ddl_tpu.ops.naming import named_pallas_call

_NEG_INF = -1e30
_LANES = 128
#: Longest sequence the one-block kernels take.  Placed by chip timings of
#: both paths (PERF.md §6, PR 27), not by the blockwise default block.
MAX_T = 512
#: VMEM the pipeline's double-buffered operand blocks of the backward (the
#: larger direction: 4 in, 3 out) may take, and the limit handed to Mosaic
#: (a v5e core has 128 MiB; 16 MiB is only its default scoped limit).
_BLOCK_BUDGET = 16 * 2**20
_VMEM_LIMIT = 64 * 2**20


def _plan(T: int, H: int, D: int, dtype) -> Optional[int]:
    """Heads a grid step holds (one batch row a step: more rows time the
    same at ViT's shape, PERF.md §6, PR 27), or None where the kernels do
    not take the shape.  A head is 32 or 64 lanes deep or fills whole
    128-lane groups: 80, 96 and 192 are neither a fraction of a group nor
    a number of them (Mosaic refuses a dynamic lane offset it cannot
    prove aligned), and shallower heads unroll 8 or 16 to a group, which
    Mosaic takes minutes to compile at T = 512 and no chip run has timed.
    And some head group both fits the budget and is a legal lane block (a
    multiple of 128 lanes, or all of ``H*D``)."""
    if D % _LANES and D not in (32, 64):
        return None
    itemsize = jnp.dtype(dtype).itemsize
    tile = 32 // itemsize  # sublanes a tile: 8 of float32, 16 of bf16
    row_bytes = -(-T // tile) * tile * itemsize
    per_head = 14 * row_bytes * D  # 7 slabs, double-buffered
    for hb in range(H, 0, -1):
        if H % hb or (hb != H and (hb * D) % _LANES):
            continue
        if per_head * hb <= _BLOCK_BUDGET:
            return hb
    return None


def fits(q, k, v, kv_repeat, block_q, block_k, segment_ids,
         window=None, q_rope=None) -> bool:
    """True where the one-block kernels take the call: the blockwise
    grid's last two axes would both be 1, every row is one segment, k/v
    have q's heads and length, and the sequence is short enough for the
    scores of a head to sit in VMEM whole.  Causal or not: both maskings
    are built in; a sliding ``window`` narrower than the sequence is not,
    nor is latent attention's second product (``q_rope``).
    bf16 or float32: the dtypes timed and compiled."""
    _, T, H, D = q.shape
    return (
        segment_ids is None
        and q_rope is None
        and (window is None or window >= T)
        and kv_repeat == 1
        and q.dtype in (jnp.bfloat16, jnp.float32)
        and k.shape == q.shape
        and v.shape == q.shape
        and k.dtype == q.dtype
        and v.dtype == q.dtype
        and 1 <= T <= min(block_q, block_k, MAX_T)
        and _plan(T, H, D, q.dtype) is not None
    )


def _precision_for(dtype):
    return (
        jax.lax.Precision.HIGHEST
        if dtype == jnp.float32
        else jax.lax.Precision.DEFAULT
    )


def _dot(a, b, contract, precision):
    return jax.lax.dot_general(
        a, b, ((contract[:1], contract[1:]), ((), ())),
        preferred_element_type=jnp.float32, precision=precision,
    )


def _future(T: int, causal: bool):
    """(T, T) True above the diagonal, built once a grid step."""
    if not causal:
        return None
    rows = jax.lax.broadcasted_iota(jnp.int32, (T, T), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (T, T), 1)
    return cols > rows


def _softmax_terms(q, k, future, scale, precision):
    """exp(s - rowmax) and its row sums, float32; every row has a key
    (its own position at the least), so the sums are positive."""
    s = _dot(q, k, (1, 1), precision) * scale
    if future is not None:
        s = jnp.where(future, _NEG_INF, s)
    e = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    return e, jnp.sum(e, axis=-1, keepdims=True)


def _each_group(heads: int, d: int, refs_in, refs_out, head):
    """``head(*operand slices) -> result slices`` for every head of the
    block's row.  128-lane groups of heads are a loop (a group's lane
    offset is dynamic but aligned); only the heads inside a group — two
    at 64 lanes — are unrolled, as static slices of the group's slab, and
    their results leave in one full-width store.  A kernel body the size
    of one group, not of all heads, is what Mosaic compiles.  Heads that
    do not fill whole groups (three of 64 lanes) are one static group."""
    per_group = max(1, _LANES // d)
    if heads % per_group:
        per_group = heads
    width = per_group * d
    groups = heads // per_group

    def group(g):
        lanes = (
            slice(0, width) if groups == 1
            else pl.ds(pl.multiple_of(g * width, _LANES), width)
        )
        slabs = [ref[:, lanes] for ref in refs_in]
        results = [
            head(*(x[:, h * d:(h + 1) * d] for x in slabs))
            for h in range(per_group)
        ]
        for ref, parts in zip(refs_out, zip(*results)):
            ref[:, lanes] = (
                parts[0] if per_group == 1
                else jnp.concatenate(parts, axis=-1)
            ).astype(ref.dtype)

    if groups == 1:
        group(0)
        return
    # Two groups an iteration: the scheduler overlaps one group's matmuls
    # with the other's softmax (Pallas lowers no partial ``unroll=``).
    together = 2 if groups % 2 == 0 else 1

    def several(i, carry):
        for j in range(together):
            group(i * together + j)
        return carry

    jax.lax.fori_loop(0, groups // together, several, 0)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, *, heads: int, d: int,
                scale: float, causal: bool, precision):
    future = _future(q_ref.shape[0], causal)

    def head(q, k, v):
        e, l = _softmax_terms(q, k, future, scale, precision)
        return (_dot(e.astype(v.dtype), v, (1, 0), precision) / l,)

    _each_group(heads, d, (q_ref, k_ref, v_ref), (o_ref,), head)


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, dq_ref, dk_ref, dv_ref, *,
                heads: int, d: int, scale: float, causal: bool, precision):
    future = _future(q_ref.shape[0], causal)

    def head(q, k, v, do):
        e, l = _softmax_terms(q, k, future, scale, precision)
        p = e * (1.0 / l)
        dv = _dot(p.astype(do.dtype), do, (0, 0), precision)
        dp = _dot(do, v, (1, 1), precision)
        # rowsum(p * dp) = rowsum(do * o): the softmax jacobian's diagonal
        # term, from what is resident.
        delta = jnp.sum(p * dp, axis=-1, keepdims=True)
        ds = (p * (dp - delta) * scale).astype(q.dtype)
        return (_dot(ds, k, (1, 0), precision),
                _dot(ds, q, (0, 0), precision), dv)

    _each_group(heads, d, (q_ref, k_ref, v_ref, do_ref),
                (dq_ref, dk_ref, dv_ref), head)


def _call(name, kernel, n_out, operands, causal, interpret):
    """Run ``kernel`` over (B, T, H, D) operands viewed as (B, T, H*D)."""
    B, T, H, D = operands[0].shape
    dtype = operands[0].dtype
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    hb = _plan(T, H, D, dtype)
    spec = pl.BlockSpec((None, T, hb * D), lambda b, h: (b, 0, h))
    flat = jax.ShapeDtypeStruct((B, T, H * D), dtype)
    outs = named_pallas_call(
        name,
        functools.partial(
            kernel, heads=hb, d=D, scale=1.0 / (D**0.5), causal=causal,
            precision=_precision_for(dtype),
        ),
        grid=(B, H // hb),
        in_specs=[spec] * len(operands),
        out_specs=[spec] * n_out,
        out_shape=[flat] * n_out,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
    )(*(x.reshape(B, T, H * D) for x in operands))
    return tuple(o.reshape(B, T, H, D) for o in outs)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def tile_attention(q, k, v, causal: bool, interpret: Optional[bool]):
    """softmax(q kᵀ / sqrt(D)) v over (B, T, H, D) operands of one shape and
    dtype, for calls :func:`fits` accepts."""
    return _call("ddl_flash_tile_fwd", _fwd_kernel, 1, (q, k, v), causal,
                 interpret)[0]


def _vjp_fwd(q, k, v, causal, interpret):
    return tile_attention(q, k, v, causal, interpret), (q, k, v)


def _vjp_bwd(causal, interpret, res, do):
    return _call("ddl_flash_tile_bwd", _bwd_kernel, 3, res + (do,), causal,
                 interpret)


tile_attention.defvjp(_vjp_fwd, _vjp_bwd)
