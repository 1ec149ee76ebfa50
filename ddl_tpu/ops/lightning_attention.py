"""Linear attention with a fixed decay a head (Lightning Attention-2,
arXiv:2401.04658), in chunks, forward and backward.

The recurrence of a head, state ``S`` in ``R^{d x d}``, float32, ``S_0 =
0``, the decay ``lam`` a CONSTANT of the head (not learned, not of the
data)::

    S_t = lam S_{t-1} + k_t^T v_t,      o_t = scale q_t S_t,   scale = 1 / sqrt(d)
        (= scale sum_{j<=t} lam^(t-j) (q_t . k_j) v_j)

Nothing has to be prepared (no delta correction, no triangular inverse, no
decay sums: ``ops/gated_delta.py`` has all three), so a chunk of ``C``
positions is ONE kernel from q, k, v to o.  With ``D_ij = lam^(i-j)`` for
``i >= j`` (else 0), ``L_i = lam^(i+1)`` and ``R_j = lam^(C-1-j)`` - all
powers of ``lam`` that are never above 1, tables of (head, chunk length)
made on the host in float64::

    O      = scale ((Q K^T * D) V + L * (Q S))      S the state the chunk starts from
    S_next = lam^C S + K^T (R * V)

- ``ddl_lightning_fwd``: grid (row x head groups, chunks), the chunk axis
  sequential, the state in VMEM scratch, float32, for the whole row.  The
  operands are read where the projections left them, (B, T, H d): a block
  is ``heads`` heads' lanes of one chunk, so no operand and no output is
  transposed in HBM.  A step writes ``O`` and the state the chunk STARTS
  from (what the backward reads).  ``K^T (R * V)``, the one product that is
  summed into the state, keeps every bit of its float32 operand
  (``gated_delta._carried_dot``); ``Q S`` takes the state in the operands'
  dtype, as any matmul of the model does.
- ``ddl_lightning_bwd``: the chunks last to first, ``G = dS_next`` in
  scratch, ``dO`` scaled on the way in::

      dP = (dO V^T) * D        dQ = dP K + L * (dO S^T)
      dK = dP^T Q + (R * V) G^T        dV = P^T dO + R * (K G)
      G <- lam^C G + Q^T (L * dO)

  No forward kernel runs in a backward pass.

The ``custom_vjp`` keeps q, k, v and the chunk states, the states tagged
with the name ``remat="selective"`` saves; :func:`lightning_attention`
tags its output.  The states leave the kernel in the operands' dtype: ``2
(T/C) H d^2`` bytes of bfloat16 a row (134 MB at 16,384 positions, 32
heads of 128, chunks of 128); the state the kernel CARRIES stays float32.

Rows that are no multiple of the chunk are padded with zero steps (``k =
v = 0`` leaves the state alone, ``q = 0`` reads nothing).  On the chip a
head's width has to fill whole lanes (``d`` a multiple of 128) unless a
block takes all heads; off the TPU the kernels run in Pallas' interpret
mode, which is how the CPU tests hold them to the plain recurrence.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ddl_tpu.ops.gated_delta import _NN, _NT, _TN, _carried_dot, _dot, _tag
from ddl_tpu.ops.naming import named_pallas_call

#: Positions a chunk: the intra-chunk product is (C, C) and a head is 128
#: wide, so at 128 every product of a chunk is a whole MXU tile.
_CHUNK = 128
#: The dtype the state is carried in from chunk to chunk.
_STATE_DTYPE = jnp.float32
#: VMEM a grid step's blocks may take, both pipeline buffers counted.
_BLOCK_BUDGET = 10 * 2**20


def slopes(n_heads: int) -> np.ndarray:
    """``s_h = 2^(-8 (h + 1) / H)``: Lightning Attention's ALiBi-style
    slopes, ``lam_h = exp(-s_h)`` (float64)."""
    return 2.0 ** (-8.0 * np.arange(1, n_heads + 1) / n_heads)


def _chunk_len(T: int) -> int:
    """:data:`_CHUNK`, or for a shorter row the power of two that holds it."""
    return min(_CHUNK, max(8, 1 << (T - 1).bit_length()))


def _tables(log_decay: np.ndarray, C: int, d: int):
    """The powers of ``lam`` a chunk needs, float32 from float64: ``D`` (H,
    C, C) with ``D_ij = lam^(i-j)``, ``i >= j``; ``edge`` (H, 2 C, d): rows
    ``i < C`` hold ``lam^(i+1)`` (what the state a chunk starts from is
    worth at its position ``i``; the last of them is the whole chunk's
    decay), rows ``C + j`` hold ``lam^(C-1-j)`` (what position ``j`` is
    worth to the state the chunk hands on), along all ``d`` lanes."""
    s = -np.asarray(log_decay, np.float64)[:, None, None]  # log lam, <= 0
    i, j = np.arange(C)[:, None], np.arange(C)[None, :]
    decay = np.where(i >= j, np.exp(s * np.maximum(i - j, 0)), 0.0)
    column = np.concatenate([np.arange(1, C + 1), np.arange(C - 1, -1, -1)])
    edge = np.exp(s * column[None, :, None]) * np.ones((1, 1, d))
    return jnp.asarray(decay, jnp.float32), jnp.asarray(edge, jnp.float32)


def _heads_per_step(H: int, C: int, d: int, itemsize: int, lanes_ok: bool) -> int:
    """Heads a grid step holds: the largest divisor of ``H``, up to 8, whose
    double-buffered blocks fit :data:`_BLOCK_BUDGET` - the backward
    kernel's: q, k, v, dO and the state in, three cotangents out, the two
    float32 tables.  All of them where a head does not fill whole lanes."""
    if not lanes_ok:
        return H
    per_head = 2 * (itemsize * (7 * C * d + d * d) + 4 * (C * C + 2 * C * d))
    for heads in range(min(H, 8), 0, -1):
        if H % heads == 0 and heads * per_head <= _BLOCK_BUDGET:
            return heads
    return 1


def _each_head(carried_ref, heads, one_head):
    """``one_head(h)`` for the ``heads`` heads of a grid step, behind a
    scratch zeroed at the row's first chunk.  A loop with ``h`` static: a
    head here is a slice of LANES, which the chip takes at a static offset
    only."""

    @pl.when(pl.program_id(1) == 0)
    def _():
        carried_ref[...] = jnp.zeros_like(carried_ref)

    for h in range(heads):
        one_head(h)


def _wide_dot(x, y, dims):
    """``x y`` for a float32 ``x`` that a bfloat16 ``y`` meets as TWO
    bfloat16 parts (16 bits of it, two passes of the MXU); float32 operands
    meet at full precision as they are.  For ``P V``: without a softmax the
    decayed scores of a row have both signs, so wherever the values share a
    direction (a deep layer's do, at random weights) the output along it is
    what is left of large terms that cancel, and scores rounded to 8 bits
    leave it noise (found on the chip and on the CPU, PR 39: a layer's
    ``wq`` / ``wk`` gradient norms 68-228% off the float32 reference with
    one part, 3% with two; PERF.md section 6)."""
    if y.dtype != jnp.bfloat16:
        return _dot(x, y, dims)
    hi = x.astype(jnp.bfloat16)
    lo = (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    return _dot(hi, y, dims) + _dot(lo, y, dims)


def _fwd_kernel(q_ref, k_ref, v_ref, decay_ref, edge_ref, o_ref, states_ref,
                state_ref, *, heads, d, C):
    """One chunk of ``heads`` heads: the output, the state the chunk starts
    from (``states``), and in scratch the state it hands on."""
    scale = d**-0.5

    def one_head(h):
        cd = o_ref.dtype
        lanes = slice(h * d, (h + 1) * d)
        q, k, v = q_ref[0, :, lanes], k_ref[0, :, lanes], v_ref[0, :, lanes]
        left, right = edge_ref[h, :C], edge_ref[h, C:]
        state = state_ref[h].astype(jnp.float32)
        low = state.astype(cd)
        states_ref[0, h, 0] = low
        p = _dot(q, k, _NT) * decay_ref[h]
        o = _wide_dot(p, v, _NN) + left * _dot(q, low, _NN)
        o_ref[0, :, lanes] = (scale * o).astype(cd)
        state_ref[h] = (
            left[C - 1 :] * state + _carried_dot(k, right * v.astype(jnp.float32), _TN)
        ).astype(state_ref.dtype)

    _each_head(state_ref, heads, one_head)


def _bwd_kernel(q_ref, k_ref, v_ref, decay_ref, edge_ref, states_ref, do_ref,
                dq_ref, dk_ref, dv_ref, grad_ref, *, heads, d, C):
    """The same chunk on the way back (the grid runs the chunks last to
    first): ``grad`` in scratch is the cotangent of the state the chunk
    hands on."""
    scale = d**-0.5

    def one_head(h):
        cd = do_ref.dtype
        lanes = slice(h * d, (h + 1) * d)
        q, k, v = q_ref[0, :, lanes], k_ref[0, :, lanes], v_ref[0, :, lanes]
        d_o = scale * do_ref[0, :, lanes].astype(jnp.float32)
        left, right = edge_ref[h, :C], edge_ref[h, C:]
        decay, state = decay_ref[h], states_ref[0, h, 0]
        grad = grad_ref[h].astype(jnp.float32)
        low, d_low = grad.astype(cd), d_o.astype(cd)
        p = (_dot(q, k, _NT) * decay).astype(cd)
        d_p = (_dot(d_low, v, _NT) * decay).astype(cd)
        faded = (right * v.astype(jnp.float32)).astype(cd)
        dq_ref[0, :, lanes] = (
            _dot(d_p, k, _NN) + left * _dot(d_low, state, _NT)
        ).astype(cd)
        dk_ref[0, :, lanes] = (_dot(d_p, q, _TN) + _dot(faded, low, _NT)).astype(cd)
        dv_ref[0, :, lanes] = (
            _dot(p, d_low, _TN) + right * _dot(k, low, _NN)
        ).astype(cd)
        grad_ref[h] = (
            left[C - 1 :] * grad + _carried_dot(q, left * d_o, _TN)
        ).astype(grad_ref.dtype)

    _each_head(grad_ref, heads, one_head)


def _call(name, kernel, rows, states, d_o, tables, H, d, interpret):
    """``kernel`` over the grid (row x head groups, chunks), the chunk axis
    sequential - last to first for the backward kernel (``d_o`` given).
    ``rows``: q, k, v (B, Tp, H d); ``states`` (B, H, chunks, d, d) or
    ``None`` (the forward writes it)."""
    B, Tp, _ = rows[0].shape
    decay, edge = tables
    C = decay.shape[-1]
    chunks = Tp // C
    cd = rows[0].dtype
    lanes_ok = d % 128 == 0
    if not (lanes_ok or interpret):
        raise NotImplementedError(
            f"lightning_attention: a head of {d} does not fill whole lanes"
        )
    heads = _heads_per_step(H, C, d, cd.itemsize, lanes_ok)
    groups = H // heads
    reverse = d_o is not None
    at = (lambda c: chunks - 1 - c) if reverse else (lambda c: c)
    row = pl.BlockSpec((1, C, heads * d), lambda i, c: (i // groups, at(c), i % groups))
    state = pl.BlockSpec(
        (1, heads, 1, d, d), lambda i, c: (i // groups, i % groups, at(c), 0, 0)
    )
    table = lambda x: pl.BlockSpec(
        (heads,) + x.shape[1:], lambda i, c: (i % groups, 0, 0)
    )
    like = lambda x: jax.ShapeDtypeStruct(x.shape, cd)
    states_shape = jax.ShapeDtypeStruct((B, H, chunks, d, d), cd)
    if reverse:
        ins, in_specs = rows + (decay, edge, states, d_o), (
            [row] * 3 + [table(decay), table(edge), state, row]
        )
        outs, out_specs = [like(x) for x in rows], [row] * 3
    else:
        ins, in_specs = rows + (decay, edge), [row] * 3 + [table(decay), table(edge)]
        outs, out_specs = [like(rows[0]), states_shape], [row, state]
    return named_pallas_call(
        name,
        functools.partial(kernel, heads=heads, d=d, C=C),
        grid=(B * groups, chunks),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=outs,
        scratch_shapes=[pltpu.VMEM((heads, d, d), _STATE_DTYPE)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(*ins)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _chunks(q, k, v, log_decay, H, C, interpret):
    """``O`` (B, Tp, H d) of rows of whole chunks; ``log_decay`` a tuple of
    ``-log lam`` a head (static: the tables are constants)."""
    return _forward(q, k, v, log_decay, H, C, interpret)[0]


def _forward(q, k, v, log_decay, H, C, interpret):
    d = q.shape[-1] // H
    tables = _tables(np.asarray(log_decay), C, d)
    return _call(
        "ddl_lightning_fwd", _fwd_kernel, (q, k, v), None, None, tables, H, d,
        interpret,
    )


def _chunks_fwd(q, k, v, log_decay, H, C, interpret):
    o, states = _forward(q, k, v, log_decay, H, C, interpret)
    return o, (q, k, v, _tag(states))


def _chunks_bwd(log_decay, H, C, interpret, res, d_o):
    q, k, v, states = res
    d = q.shape[-1] // H
    tables = _tables(np.asarray(log_decay), C, d)
    return tuple(_call(
        "ddl_lightning_bwd", _bwd_kernel, (q, k, v), states, d_o, tables, H, d,
        interpret,
    ))


_chunks.defvjp(_chunks_fwd, _chunks_bwd)


def lightning_attention(q, k, v, log_decay=None, interpret: Optional[bool] = None):
    """``o_t = (1 / sqrt(d)) sum_{j<=t} lam_h^(t-j) (q_t . k_j) v_j`` (the
    module's docstring).

    ``q``, ``k``, ``v``: (B, T, H, d) as the recurrence takes them (the
    model norms and rotates q and k).  ``log_decay``: ``-log lam`` a head,
    a static sequence of H floats (default :func:`slopes`).  Returns (B, T,
    H, d) in ``q``'s dtype.  The state is float32 whatever the operands
    are; bfloat16 operands meet the MXU as bfloat16, float32 ones at full
    precision.  Differentiable in q, k and v.
    """
    return _in_chunks(q, k, v, log_decay, _chunk_len(q.shape[1]), interpret)


def _in_chunks(q, k, v, log_decay, C, interpret=None):
    """:func:`lightning_attention` in chunks of ``C`` positions."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    B, T, H, d = q.shape
    log_decay = tuple(float(s) for s in (slopes(H) if log_decay is None else log_decay))
    assert len(log_decay) == H and min(log_decay) >= 0.0, log_decay
    pad = -T % C
    flat = lambda x: jnp.pad(x.reshape(B, T, H * d), ((0, 0), (0, pad), (0, 0)))
    o = _chunks(flat(q), flat(k), flat(v), log_decay, H, C, interpret)
    return _tag(o[:, :T].reshape(B, T, H, d))
