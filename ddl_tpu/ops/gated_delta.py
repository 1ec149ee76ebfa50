"""The gated delta rule over a sequence, in chunks, forward and backward.

The recurrence of a Gated DeltaNet head (arXiv:2412.06464), state
``S`` in ``R^{d_v x d_k}``, float32, ``S_0 = 0``::

    S_t = alpha_t S_{t-1} + beta_t (v_t - alpha_t S_{t-1} k_t) k_t^T
    o_t = S_t q_t                       alpha_t = exp(g_t), g_t <= 0

One step is a rank-one correction of a decayed state: a chain of ``T``
matrix-vector products, nothing for an MXU.  The chunked form
(arXiv:2406.06484, the WY representation) turns ``C`` steps into matmuls.
With ``H = S^T``, inside one chunk ``gam_i = sum_{j<=i} g_j``,
``D_ij = exp(gam_i - gam_j)`` for ``i >= j`` (never above 1), and

    A   = strict_lower(beta_i (k_i . k_j) D_ij)            (C, C)
    T   = (I + A)^-1                                        unit lower
    W   = T (beta exp(gam) k),     U = T (beta v)           (C, d_k), (C, d_v)
    P   = lower(q_i . k_j D_ij)                             (C, C)

the chunk is an AFFINE map of the state it starts from::

    H_next = M H + N        M = exp(gam_C) I - Kd^T W,  N = Kd^T U,
                            Kd_j = exp(gam_C - gam_j) k_j
    O      = (exp(gam) q - P W) H + P U

Everything but the first line is independent of the other chunks: batched
matmuls over all of them at once, which XLA runs and differentiates
(:func:`_one_pass` writes them as they stand above, for a pass of heads at
a time: a pass's intermediates are recomputed in the backward pass, not
kept, so that they are never all heads' at once).  What is left
is the serial chain ``H_{c+1} = M_c H_c + N_c`` over the chunks, and that
is the kernel here, with the backward pass that belongs to it:

- ``ddl_gdn_fwd``: grid (head groups, chunks), the chunk axis sequential;
  the state lives in VMEM scratch, float32, for the whole row; each step
  writes the state its chunk STARTS from (what ``O`` and the backward
  read) and applies the chunk's map, the ``d_k x d_k`` by ``d_k x d_v``
  product in float32 at the MXU's full precision: an operand rounded to
  bfloat16 inside the chain is a state carried in bfloat16.
- ``ddl_gdn_bwd``: the same chain run from the last chunk to the first
  with ``M^T``: ``G_c = dH_c + M_c^T G_{c+1}``.  It writes ``G_{c+1}``,
  which IS ``dN_c``; ``dM_c = G_{c+1} H_c^T`` is one batched matmul
  outside.  One kernel body serves both.

The chain's ``custom_vjp`` keeps the chunk states it wrote, tagged with
the name ``remat="selective"`` saves, and :func:`gated_delta_rule` tags
its output: a rematerialised backward recomputes the parallel part, reads
both, and runs no forward kernel (``models/remat.py``).  The states leave
the chain in the operands' dtype - what the output's matmul takes: ``2
(T/C) H d_k d_v`` bytes of bfloat16 a row, 283 MB at 16,384 positions, 30
heads, 96 x 192; the state the chain CARRIES stays float32.

The chunk length, the heads a grid step holds and the heads whose maps
are computed together come from the shapes (:func:`_chunk_len`,
:func:`_heads_per_step`, :func:`_heads_per_pass`); rows that are no multiple of
the chunk are padded with steps that leave the state alone (``beta`` 0,
``g`` 0).  ``d_k`` and ``d_v`` need not fill 128 lanes (96 and 192 do
not): a block spans the whole of its last two axes.  Off the TPU the
kernels run in Pallas' interpret mode, which is how the CPU tests hold
them to the plain recurrence.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ddl_tpu.ops.flash_attention import _precision_for
from ddl_tpu.ops.naming import named_pallas_call

#: Positions a chunk (arXiv:2406.06484's and its implementations' 64: the
#: inverse of ``I + A`` grows with the chunk, the chain shortens with it).
_CHUNK = 64
#: Side of the diagonal blocks whose inverse is the finite Neumann product;
#: larger blocks are merged from them (:func:`_unit_lower_inverse`).
_INVERSE_BASE = 16
#: The dtype the state is carried in from chunk to chunk.
_STATE_DTYPE = jnp.float32
#: VMEM a grid step's blocks may take, both pipeline buffers counted.
_BLOCK_BUDGET = 8 * 2**20
_LANES = 128


def _chunk_len(T: int) -> int:
    """:data:`_CHUNK`, or for a shorter row the power of two that holds it."""
    return min(_CHUNK, max(8, 1 << (T - 1).bit_length()))


def _heads_per_step(groups: int, dk: int, dv: int) -> int:
    """(batch x head) rows a grid step holds: the largest divisor of their
    number, up to 8, whose double-buffered float32 blocks (``M``, ``N`` in,
    the state out, lane-padded) fit :data:`_BLOCK_BUDGET`."""
    pad = lambda n: -(-n // _LANES) * _LANES
    per_row = 2 * 4 * dk * (pad(dk) + 2 * pad(dv))
    for heads in range(min(groups, 8), 0, -1):
        if groups % heads == 0 and heads * per_row <= _BLOCK_BUDGET:
            return heads
    return 1


def _chain_kernel(m_ref, n_ref, out_ref, state_ref, *, heads, transpose):
    """One chunk of ``state <- M state + N`` (``M^T`` where ``transpose``)
    for ``heads`` rows; ``out`` gets the state BEFORE the chunk."""

    @pl.when(pl.program_id(1) == 0)
    def _():
        state_ref[...] = jnp.zeros_like(state_ref)

    contract = (((0,), (0,)), ((), ())) if transpose else (((1,), (0,)), ((), ()))
    for h in range(heads):
        state = state_ref[h].astype(jnp.float32)
        out_ref[h, 0] = state.astype(out_ref.dtype)
        state_ref[h] = (
            jax.lax.dot_general(
                m_ref[h, 0], state, contract,
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32,
            )
            + n_ref[h, 0].astype(jnp.float32)
        ).astype(state_ref.dtype)


def _chain(m, n, reverse: bool, out_dtype, interpret: bool):
    """States of ``H <- M_c H + N_c`` from zero: ``out[:, c]`` is the state
    chunk ``c`` starts from, rounded to ``out_dtype`` on its way out (the
    chain itself stays float32).  ``reverse``: the chain of the backward
    pass, from the last chunk to the first with ``M_c^T``.  ``m`` (G, n,
    dk, dk) float32, ``n`` (G, n, dk, dv)."""
    G, chunks, dk, dv = n.shape
    heads = _heads_per_step(G, dk, dv)
    last = chunks - 1
    at = (lambda i, c: (i, last - c, 0, 0)) if reverse else (lambda i, c: (i, c, 0, 0))
    return named_pallas_call(
        "ddl_gdn_bwd" if reverse else "ddl_gdn_fwd",
        functools.partial(_chain_kernel, heads=heads, transpose=reverse),
        grid=(G // heads, chunks),
        in_specs=[
            pl.BlockSpec((heads, 1, dk, dk), at),
            pl.BlockSpec((heads, 1, dk, dv), at),
        ],
        out_specs=pl.BlockSpec((heads, 1, dk, dv), at),
        out_shape=jax.ShapeDtypeStruct((G, chunks, dk, dv), out_dtype),
        scratch_shapes=[pltpu.VMEM((heads, dk, dv), _STATE_DTYPE)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(m, n)


def _tag(x):
    """``x`` under the name ``remat="selective"`` saves (lazily: the
    models import this module)."""
    from ddl_tpu.models.remat import tag_attn_out

    return tag_attn_out(x)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _chunk_states(m, n, dtype, interpret):
    """The state every chunk starts from, (G, chunks, dk, dv) in ``dtype``
    (the operands' own: what the output's matmul takes)."""
    return _chain(m, n, False, dtype, interpret)


def _chunk_states_fwd(m, n, dtype, interpret):
    states = _tag(_chain(m, n, False, dtype, interpret))
    return states, (m, states)


def _chunk_states_bwd(dtype, interpret, res, d_states):
    m, states = res
    # G_{c+1}, the cotangent of the state chunk c hands on: dN_c itself.
    d_n = _chain(m, d_states, True, jnp.float32, interpret)
    d_m = jnp.einsum(
        "gcik,gcjk->gcij", d_n, states.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )
    return d_m, d_n


_chunk_states.defvjp(_chunk_states_fwd, _chunk_states_bwd)


def _blocks(x, size: int, below: bool):
    """(..., C, C) -> (..., C // step, size, size): the ``size`` blocks on
    the diagonal (``step`` = ``size``), or with ``below`` the one under the
    diagonal inside each diagonal block of twice the size."""
    step = 2 * size if below else size
    return jnp.stack([
        x[..., lo + (size if below else 0) : lo + step, lo : lo + size]
        for lo in range(0, x.shape[-1], step)
    ], axis=-3)


def _unit_lower_inverse(a):
    """``(I + a)^-1`` for strictly lower triangular ``a`` (..., C, C), C a
    power of two, in float32 at full precision.  Diagonal blocks of
    :data:`_INVERSE_BASE` by the finite product ``(I - a)(I + a^2)(I +
    a^4)...`` (``a`` is nilpotent, and no power above ``a^8`` is formed);
    then block forward substitution, two blocks into one::

        [[L11, 0], [L21, L22]]^-1 = [[X11, 0], [-X22 L21 X11, X22]]
    """
    C = a.shape[-1]
    size = min(_INVERSE_BASE, C)
    mm = functools.partial(jnp.matmul, precision=jax.lax.Precision.HIGHEST)
    neg = -_blocks(a, size, below=False)
    inv = jnp.eye(size, dtype=a.dtype) + neg
    power, order = neg, 1
    while 2 * order < size:
        power = mm(power, power)
        inv = inv + mm(inv, power)
        order *= 2
    while size < C:
        x11, x22 = inv[..., 0::2, :, :], inv[..., 1::2, :, :]
        x21 = -mm(mm(x22, _blocks(a, size, below=True)), x11)
        inv = jnp.concatenate([
            jnp.concatenate([x11, jnp.zeros_like(x11)], -1),
            jnp.concatenate([x21, x22], -1),
        ], -2)
        size *= 2
    return inv[..., 0, :, :]


def _heads_per_pass(B: int, T: int, H: int) -> int:
    """Heads whose chunks' maps are computed together: the largest divisor
    of ``H`` with at most 2^17 (row, position, head) triples a pass (6 of
    30 heads at one row of 16,384).  The parallel part keeps some twenty
    float32 arrays of (C, C) and (C, d) a chunk alive in its backward
    pass: all heads at once are gigabytes of them at that shape."""
    for heads in range(H, 0, -1):
        if H % heads == 0 and B * T * heads <= 2**17:
            return heads
    return 1


def _one_pass(q, k, v, g, beta, interpret):
    """The output for the heads given (the module's docstring has the
    algebra): ``q``, ``k`` (B, chunks, C, H d_k), ``v`` (B, chunks, C, H
    d_v) in the operands' dtype - the projections' own layout, heads side by
    side, cut into chunks - ``g``, ``beta`` (B, chunks, C, H) float32 ->
    (B, chunks, C, H d_v).  Index letters: b row, n chunk, i / j / c
    position in the chunk, h head, k / l key axis, v value axis."""
    B, chunks, C, H = g.shape
    heads = lambda x: x.reshape(x.shape[:3] + (H, x.shape[3] // H))
    q, k, v = heads(q), heads(k), heads(v)
    dk, dv, cd, f32 = q.shape[-1], v.shape[-1], q.dtype, jnp.float32
    ein = functools.partial(
        jnp.einsum, precision=_precision_for(cd), preferred_element_type=f32
    )
    gam = jnp.cumsum(g, axis=2)  # (b, n, c, h)
    grown = jnp.exp(gam)  # decay since the chunk's start, <= 1
    left = gam[:, :, -1:]  # the whole chunk's log decay
    gam_h, beta_h = jnp.moveaxis(gam, 2, 3), jnp.moveaxis(beta, 2, 3)  # (b, n, h, c)
    row = jnp.arange(C)[:, None]
    lower, strict = row >= row.T, row > row.T
    # exp only where it is kept: above the diagonal the difference is positive
    decay = jnp.where(
        lower,
        jnp.exp(jnp.where(lower, gam_h[..., :, None] - gam_h[..., None, :], 0.0)),
        0.0,
    )  # (b, n, h, i, j)
    a = jnp.where(
        strict, beta_h[..., None] * ein("bnihk,bnjhk->bnhij", k, k) * decay, 0.0
    )
    t = _unit_lower_inverse(a).astype(cd)
    rhs = jnp.concatenate([
        k.astype(f32) * (beta * grown)[..., None], v.astype(f32) * beta[..., None]
    ], -1).astype(cd)
    wu = ein("bnhij,bnjhd->bnihd", t, rhs).astype(cd)
    w, u = wu[..., :dk], wu[..., dk:]
    p = jnp.where(lower, ein("bnihk,bnjhk->bnhij", q, k) * decay, 0.0).astype(cd)
    q_eff = (
        q.astype(f32) * grown[..., None] - ein("bnhij,bnjhk->bnihk", p, w)
    ).astype(cd)
    kd = (k.astype(f32) * jnp.exp(left - gam)[..., None]).astype(cd)
    m = jnp.moveaxis(jnp.exp(left[:, :, 0]), 1, 2)[..., None, None] * jnp.eye(
        dk, dtype=f32
    ) - ein("bnchk,bnchl->bhnkl", kd, w)
    n = ein("bnchk,bnchv->bhnkv", kd, u)
    states = _chunk_states(
        m.reshape(B * H, chunks, dk, dk), n.reshape(B * H, chunks, dk, dv), cd,
        interpret,
    ).reshape(B, H, chunks, dk, dv)
    o = ein("bnhij,bnjhv->bnihv", p, u) + ein("bnchk,bhnkv->bnchv", q_eff, states)
    return o.astype(cd).reshape(B, chunks, C, H * dv)


def gated_delta_rule(q, k, v, g, beta, interpret: Optional[bool] = None):
    """``o_t = S_t q_t`` of the gated delta rule (the module's docstring).

    ``q``, ``k``: (B, T, H, d_k), as the recurrence takes them (the model
    normalises and scales them); ``v``: (B, T, H, d_v); ``g`` (log decay,
    <= 0) and ``beta``: (B, T, H).  Returns (B, T, H, d_v) in ``q``'s
    dtype.  Decay sums, the triangular inverse and the state are float32
    whatever the operands are; bfloat16 operands meet the MXU as bfloat16,
    float32 ones at full precision.  Differentiable in all five.
    """
    from ddl_tpu.models.remat import ATTN_OUT_NAME

    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    B, T, H, _ = q.shape
    C = _chunk_len(T)
    chunks = -(-T // C)
    heads = _heads_per_pass(B, T, H)

    def by_pass(x):
        """(B, T, H[, d]) -> (H / heads, B, chunks, C, heads [x d]), zero
        steps behind the row: a pass's heads stay side by side, as the
        projections left them, one lane-dense axis."""
        x = jnp.pad(x, ((0, 0), (0, chunks * C - T)) + ((0, 0),) * (x.ndim - 2))
        x = x.reshape(B, chunks, C, H // heads, -1)
        return jnp.moveaxis(x, 3, 0)

    # One pass of heads at a time, and in a backward pass again: a pass's
    # intermediates are never all heads' at once.  What a pass keeps for
    # its backward is what ``remat="selective"`` keeps: the chunk states.
    one_pass = jax.checkpoint(
        functools.partial(_one_pass, interpret=interpret),
        policy=jax.checkpoint_policies.save_only_these_names(ATTN_OUT_NAME),
    )
    o = jax.lax.map(lambda xs: one_pass(*xs), (
        by_pass(q), by_pass(k), by_pass(v),
        by_pass(g.astype(jnp.float32)), by_pass(beta.astype(jnp.float32)),
    ))
    # (H / heads, B, chunks, C, heads x d_v) -> (B, T, H, d_v)
    o = jnp.moveaxis(o, 0, 3).reshape(B, chunks * C, H, -1)
    return _tag(o[:, :T])
