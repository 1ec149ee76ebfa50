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

the chunk meets the state it starts from in three lines::

    Vn     = U - W H                    what the chunk writes, given H
    O      = Qg H + P Vn                Qg = exp(gam) q
    H_next = a H + Kd^T Vn              a = exp(gam_C),
                                        Kd_j = exp(gam_C - gam_j) k_j

(the affine map ``H_next = M H + N`` with ``M = a I - Kd^T W``, ``N = Kd^T
U`` and ``O = (Qg - P W) H + P U``, never formed: a chunk's and head's ``M``
and ``N`` are more bytes than its q, k, v and o together).  What does not
touch the state - decay sums, Gram matrices, the triangular inverse, ``W``,
``U``, ``P``, ``Qg``, ``Kd`` - is independent of the other chunks: batched
matmuls over all of them at once, head-major ((row, head, chunk) leading,
the layout the kernels' blocks take), which XLA runs and differentiates
(:func:`_one_pass`, for a pass of heads at a time: a pass's intermediates
are recomputed in the backward pass, not kept, so that they are never all
heads' at once).  The three lines are the kernels, one ``custom_vjp`` of
``(W, U, Kd, Qg, P, a) -> O``:

- ``ddl_gdn_fwd``: grid (head groups, chunks), the chunk axis sequential;
  the state lives in VMEM scratch, float32, for the whole row; each step
  reads a chunk's operands (68 KB a head in bfloat16), writes ``O`` and the
  state the chunk STARTS from (what the backward reads), and hands the
  next state on.  The two products on the path from state to state (``W
  H``, ``Kd^T Vn``) keep every bit of their float32 operand
  (:func:`_carried_dot`): an operand rounded to bfloat16 there is a state
  carried in bfloat16.  ``Qg H`` and ``P Vn`` take ``H`` and ``Vn`` in the
  operands' dtype, as any matmul of the model does.
- ``ddl_gdn_bwd``: the chunks last to first, ``G = dH_next`` in scratch,
  ``Vn`` computed again from the saved state::

      dVn = P^T dO + Kd G       dQg = dO H^T      dP  = dO Vn^T
      dU  = dVn                 dW  = -dVn H^T    dKd = Vn G^T
      da  = <G, H>              G  <- a G + Qg^T dO - W^T dVn

  with ``Kd G`` and ``W^T dVn``, the path from ``G`` to ``G``, carried the
  same way.  No forward kernel runs in a backward pass.

The ``custom_vjp`` keeps the chunk states the forward wrote, tagged with
the name ``remat="selective"`` saves, and :func:`gated_delta_rule` tags
its output: a rematerialised backward recomputes XLA's part, reads both,
and runs the backward kernel alone (``models/remat.py``).  The states
leave the kernel in the operands' dtype: ``2 (T/C) H d_k d_v`` bytes of
bfloat16 a row, 283 MB at 16,384 positions, 30 heads, 96 x 192; the state
the kernel CARRIES stays float32.  The triangular inverse has a backward
of its own (``-X^T dX X^T``: two products where autodiff would transpose
the ten that built it).

The chunk length, the heads a grid step holds and the heads whose chunks
are prepared together come from the shapes and the operands' dtype
(:func:`_chunk_len`, :func:`_heads_per_step`, :func:`_heads_per_pass`);
rows that are no multiple of the chunk are padded with steps that leave
the state alone (``beta`` 0, ``g`` 0).  ``d_k`` and ``d_v`` need not fill
128 lanes (96 and 192 do not): a block spans the whole of its last two
axes.  Off the TPU the kernels run in Pallas' interpret mode, which is how
the CPU tests hold them to the plain recurrence.
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
#: inverse of ``I + A`` grows with the chunk, the row's chain of states
#: shortens with it).
_CHUNK = 64
#: Side of the diagonal blocks whose inverse is the finite Neumann product;
#: larger blocks are merged from them (:func:`_unit_lower_inverse`).
_INVERSE_BASE = 16
#: The dtype the state is carried in from chunk to chunk.
_STATE_DTYPE = jnp.float32
#: VMEM a grid step's blocks may take, both pipeline buffers counted.
_BLOCK_BUDGET = 8 * 2**20
_LANES = 128

_NN = (((1,), (0,)), ((), ()))  # x y
_NT = (((1,), (1,)), ((), ()))  # x y^T
_TN = (((0,), (0,)), ((), ()))  # x^T y


def _chunk_len(T: int) -> int:
    """:data:`_CHUNK`, or for a shorter row the power of two that holds it."""
    return min(_CHUNK, max(8, 1 << (T - 1).bit_length()))


def _heads_per_step(groups: int, C: int, dk: int, dv: int, itemsize: int) -> int:
    """(batch x head) rows a grid step holds: the largest divisor of their
    number, up to 8, whose double-buffered blocks fit :data:`_BLOCK_BUDGET`
    at the operands' ``itemsize`` - the backward kernel's, which are the
    more: a chunk's ``W``, ``Kd``, ``Qg``, ``P``, ``U``, ``dO`` and the
    state it starts from in, five cotangents out, lane-padded."""
    pad = lambda n: -(-n // _LANES) * _LANES
    narrow, square, wide = C * pad(dk), C * pad(C), C * pad(dv)
    per_row = 2 * itemsize * (
        (3 * narrow + square + 2 * wide + dk * pad(dv))  # in
        + (3 * narrow + square + wide)  # out
    )
    for heads in range(min(groups, 8), 0, -1):
        if groups % heads == 0 and heads * per_row <= _BLOCK_BUDGET:
            return heads
    return 1


def _dot(x, y, dims):
    """A product of two operands in the operands' dtype, summed in float32:
    bfloat16 as the MXU takes it, float32 at its full precision."""
    return jax.lax.dot_general(
        x, y, dims, precision=_precision_for(x.dtype),
        preferred_element_type=jnp.float32,
    )


def _carried_dot(x, y, dims):
    """A product on the path from one chunk's state to the next: ``y`` is
    float32 (the state, or what was computed from it) and none of it is
    dropped.  A bfloat16 ``x`` - a chunk's operand, exact as it is - meets
    ``y`` as the sum of three bfloat16 parts, all 24 bits of it, in three
    passes of the MXU (``HIGHEST`` would split ``x`` too and take six); a
    float32 ``x`` takes ``HIGHEST``."""
    if x.dtype != jnp.bfloat16:
        return jax.lax.dot_general(
            x.astype(jnp.float32), y, dims, precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )
    hi = y.astype(jnp.bfloat16)
    rest = y - hi.astype(jnp.float32)
    mid = rest.astype(jnp.bfloat16)
    lo = (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    return _dot(x, hi, dims) + (_dot(x, mid, dims) + _dot(x, lo, dims))


def _each_head(carried_ref, heads, one_head):
    """``one_head(h)`` for the ``heads`` rows of a grid step, behind a
    scratch zeroed at the row's first chunk.  Unrolled: the rows are
    independent, so their products interleave (the two kernels as one
    program each ran 12% faster so on the chip, PR 37) and both still
    compile for a v5e in about a second."""

    @pl.when(pl.program_id(1) == 0)
    def _():
        carried_ref[...] = jnp.zeros_like(carried_ref)

    def body(h, carry):
        one_head(h)
        return carry

    jax.lax.fori_loop(0, heads, body, 0, unroll=True)


def _fwd_kernel(w_ref, u_ref, kd_ref, qg_ref, p_ref, a_ref, o_ref, states_ref,
                state_ref, *, heads):
    """One chunk of ``heads`` rows: the output, the state the chunk starts
    from (``states``), and in scratch the state it hands on."""

    def one_head(h):
        cd = o_ref.dtype
        state = state_ref[h].astype(jnp.float32)
        low = state.astype(cd)
        states_ref[h, 0] = low
        vn = u_ref[h, 0].astype(jnp.float32) - _carried_dot(w_ref[h, 0], state, _NN)
        o_ref[h, 0] = (
            _dot(qg_ref[h, 0], low, _NN) + _dot(p_ref[h, 0], vn.astype(cd), _NN)
        ).astype(cd)
        state_ref[h] = (
            a_ref[h, 0] * state + _carried_dot(kd_ref[h, 0], vn, _TN)
        ).astype(state_ref.dtype)

    _each_head(state_ref, heads, one_head)


def _bwd_kernel(w_ref, u_ref, kd_ref, qg_ref, p_ref, a_ref, states_ref, do_ref,
                dw_ref, du_ref, dkd_ref, dqg_ref, dp_ref, da_ref, grad_ref, *,
                heads):
    """The same chunk on the way back (the grid runs the chunks last to
    first): ``grad`` in scratch is the cotangent of the state the chunk
    hands on; ``Vn`` is computed again from the saved state."""

    def one_head(h):
        cd = do_ref.dtype
        grad = grad_ref[h].astype(jnp.float32)
        state, d_o = states_ref[h, 0], do_ref[h, 0]
        w, kd, qg = w_ref[h, 0], kd_ref[h, 0], qg_ref[h, 0]
        vn = (u_ref[h, 0].astype(jnp.float32) - _dot(w, state, _NN)).astype(cd)
        d_vn = _dot(p_ref[h, 0], d_o, _TN) + _carried_dot(kd, grad, _NN)
        low = d_vn.astype(cd)
        du_ref[h, 0] = low
        dw_ref[h, 0] = (-_dot(low, state, _NT)).astype(cd)
        dqg_ref[h, 0] = _dot(d_o, state, _NT).astype(cd)
        dp_ref[h, 0] = _dot(d_o, vn, _NT).astype(cd)
        dkd_ref[h, 0] = _dot(vn, grad.astype(cd), _NT).astype(cd)
        da_ref[h, 0] = jnp.sum(grad * state.astype(jnp.float32), axis=0, keepdims=True)
        grad_ref[h] = (
            a_ref[h, 0] * grad + _dot(qg, d_o, _TN) - _carried_dot(w, d_vn, _TN)
        ).astype(grad_ref.dtype)

    _each_head(grad_ref, heads, one_head)


def _call(name, kernel, ins, outs, reverse, interpret):
    """``kernel`` over the grid (groups of rows, chunks), the chunk axis
    sequential - last to first where ``reverse`` - each operand a block of
    ``heads`` rows' one chunk, a float32 state in scratch.  ``ins``: the
    arrays, (G, chunks, ., .); ``outs``: their shapes and dtypes."""
    G, chunks, C, dk = ins[0].shape
    dv = ins[1].shape[-1]
    heads = _heads_per_step(G, C, dk, dv, ins[0].dtype.itemsize)
    last = chunks - 1
    at = (lambda i, c: (i, last - c, 0, 0)) if reverse else (lambda i, c: (i, c, 0, 0))
    spec = lambda x: pl.BlockSpec((heads, 1) + tuple(x.shape[2:]), at)
    return named_pallas_call(
        name,
        functools.partial(kernel, heads=heads),
        grid=(G // heads, chunks),
        in_specs=[spec(x) for x in ins],
        out_specs=[spec(x) for x in outs],
        out_shape=outs,
        scratch_shapes=[pltpu.VMEM((heads, dk, dv), _STATE_DTYPE)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(*ins)


def _tag(x):
    """``x`` under the name ``remat="selective"`` saves (lazily: the
    models import this module)."""
    from ddl_tpu.models.remat import tag_attn_out

    return tag_attn_out(x)


def _forward(w, u, kd, qg, p, a, interpret):
    """The chunks' outputs (G, chunks, C, dv) and the state each starts
    from (G, chunks, dk, dv), both in the operands' dtype."""
    G, chunks, C, dk = w.shape
    like = lambda *shape: jax.ShapeDtypeStruct((G, chunks) + shape, w.dtype)
    return _call(
        "ddl_gdn_fwd", _fwd_kernel, (w, u, kd, qg, p, a),
        [like(C, u.shape[-1]), like(dk, u.shape[-1])], False, interpret,
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _chunks(w, u, kd, qg, p, a, interpret):
    """``O`` of every chunk, each met with the state the chunks before it
    leave: ``w``, ``kd``, ``qg`` (G, chunks, C, dk), ``u`` (G, chunks, C,
    dv), ``p`` (G, chunks, C, C) in the operands' dtype, ``a`` (G, chunks,
    1, dv) float32, the chunk's whole decay along a lane-dense row."""
    return _forward(w, u, kd, qg, p, a, interpret)[0]


def _chunks_fwd(w, u, kd, qg, p, a, interpret):
    o, states = _forward(w, u, kd, qg, p, a, interpret)
    return o, (w, u, kd, qg, p, a, _tag(states))


def _chunks_bwd(interpret, res, d_o):
    w, u, kd, qg, p, a, states = res
    like = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)
    return tuple(_call(
        "ddl_gdn_bwd", _bwd_kernel, res + (d_o,),
        [like(x) for x in (w, u, kd, qg, p, a)], True, interpret,
    ))


_chunks.defvjp(_chunks_fwd, _chunks_bwd)


def _blocks(x, size: int, below: bool):
    """(..., C, C) -> (..., C // step, size, size): the ``size`` blocks on
    the diagonal (``step`` = ``size``), or with ``below`` the one under the
    diagonal inside each diagonal block of twice the size."""
    step = 2 * size if below else size
    return jnp.stack([
        x[..., lo + (size if below else 0) : lo + step, lo : lo + size]
        for lo in range(0, x.shape[-1], step)
    ], axis=-3)


@jax.custom_vjp
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


def _unit_lower_inverse_bwd(inv, d_inv):
    # d (I + a)^-1 = -X da X: two products with the inverse itself, where
    # autodiff would keep and transpose every product that built it
    mm = functools.partial(jnp.matmul, precision=jax.lax.Precision.HIGHEST)
    inv_t = jnp.swapaxes(inv, -1, -2)
    return (-mm(mm(inv_t, d_inv), inv_t),)


_unit_lower_inverse.defvjp(
    lambda a: (_unit_lower_inverse(a),) * 2, _unit_lower_inverse_bwd
)


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
    algebra), head-major: ``q``, ``k`` (B, H, chunks, C, d_k), ``v`` (B, H,
    chunks, C, d_v) in the operands' dtype, ``g``, ``beta`` (B, H, chunks,
    C) float32 -> (B, H, chunks, C, d_v).  Every product is a matmul
    batched over the leading three axes; i / j are positions in the chunk."""
    B, H, chunks, C = g.shape
    dk, dv, cd, f32 = q.shape[-1], v.shape[-1], q.dtype, jnp.float32
    ein = functools.partial(
        jnp.einsum, precision=_precision_for(cd), preferred_element_type=f32
    )
    gam = jnp.cumsum(g, axis=-1)
    grown = jnp.exp(gam)  # decay since the chunk's start, <= 1
    left = gam[..., -1:]  # the whole chunk's log decay
    row = jnp.arange(C)[:, None]
    lower, strict = row >= row.T, row > row.T
    # exp only where it is kept: above the diagonal the difference is positive
    decay = jnp.where(
        lower,
        jnp.exp(jnp.where(lower, gam[..., :, None] - gam[..., None, :], 0.0)),
        0.0,
    )  # (B, H, chunks, i, j)
    a = jnp.where(
        strict, beta[..., None] * ein("...ik,...jk->...ij", k, k) * decay, 0.0
    )
    t = _unit_lower_inverse(a).astype(cd)
    scaled = lambda x, by: (x.astype(f32) * by[..., None]).astype(cd)
    w = ein("...ij,...jk->...ik", t, scaled(k, beta * grown)).astype(cd)
    u = ein("...ij,...jv->...iv", t, scaled(v, beta)).astype(cd)
    p = jnp.where(lower, ein("...ik,...jk->...ij", q, k) * decay, 0.0).astype(cd)
    rows = lambda x: x.reshape((B * H,) + x.shape[2:])
    o = _chunks(
        rows(w), rows(u), rows(scaled(k, jnp.exp(left - gam))),
        rows(scaled(q, grown)), rows(p),
        rows(jnp.broadcast_to(jnp.exp(left)[..., None], (B, H, chunks, 1, dv))),
        interpret,
    )
    return o.reshape(B, H, chunks, C, dv)


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
        """(B, T, H[, d]) -> (H / heads, B, heads, chunks, C[, d]), zero
        steps behind the row: head-major, the layout the kernels' blocks
        take, by the one transpose an operand gets."""
        x = jnp.pad(x, ((0, 0), (0, chunks * C - T)) + ((0, 0),) * (x.ndim - 2))
        x = jnp.moveaxis(x, 2, 1)  # (B, H, T[, d])
        x = x.reshape((B, H // heads, heads, chunks, C) + x.shape[3:])
        return jnp.moveaxis(x, 1, 0)

    # One pass of heads at a time, and in a backward pass again: a pass's
    # intermediates are never all heads' at once.  What a pass keeps for
    # its backward is what ``remat="selective"`` keeps: the chunk states.
    # (B, H, T, d) before the heads are split into passes: a (.., passes,
    # heads, d) view of the projections' layout has a 6-row second-minor axis
    # and every float32 copy XLA makes of it is padded 8 / 6 x 256 / 192.
    one_pass = jax.checkpoint(
        functools.partial(_one_pass, interpret=interpret),
        policy=jax.checkpoint_policies.save_only_these_names(ATTN_OUT_NAME),
    )
    o = jax.lax.map(lambda xs: one_pass(*xs), (
        by_pass(q), by_pass(k), by_pass(v),
        by_pass(g.astype(jnp.float32)), by_pass(beta.astype(jnp.float32)),
    ))
    # (H / heads, B, heads, chunks, C, d_v) -> (B, T, H, d_v)
    o = jnp.moveaxis(o, 0, 1).reshape(B, H, chunks * C, -1)
    return _tag(jnp.moveaxis(o, 1, 2)[:, :T])
