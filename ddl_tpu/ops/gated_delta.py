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
and ``N`` are more bytes than its q, k, v and o together).  Everything of
a chunk is the kernels', one ``custom_vjp`` of ``(q, k, v, [gam; beta]) ->
O`` on head-major operands ((row x head, tile) leading, a tile the two
chunks a grid step takes): what does not touch the state - ``D``, the Gram
matrices, the triangular inverse, ``W``, ``U``, ``P``, ``Qg``, ``Kd``
(:func:`_prepare`) - is made in VMEM from a tile's operands and stays
there, so no (C, C) array and no (C, d) array but q, k, v, o and their
cotangents ever reaches HBM.  XLA keeps what is O(T H) scalars - the casts
of ``g`` and ``beta``, a chunk's decay sums ``gam`` (and, through
autodiff, the reverse sums that make ``g``'s cotangent of ``gam``'s) - the
pad to whole tiles, and the head-major transposes of q, k, v in and o out.

- ``ddl_gdn_fwd``: grid (groups of rows, tiles), the tile axis sequential;
  the state lives in VMEM scratch, float32, for the whole row; each step
  reads a tile's q, k, v and its (2, P) float32 ``gam`` and ``beta`` along
  lanes (a column of either is a masked sum against the identity, inside),
  prepares the tile's chunks together - their (C, C) tiles side by side on
  the 128 lanes (:class:`_Tile`) - runs the three lines chunk after chunk,
  writes ``O`` and the state each chunk STARTS from (what the backward
  reads), and hands the last state on.  The kernel body has no loop over
  the step's (row, head) groups: every array leads with them, so each
  operation is issued for all of them before the next and the MXU is kept
  full by independent work (a group after a group, the schedule followed
  one group's chain of dependent products at half the MXU's rate).  The
  inverse is the blocked one (:func:`_unit_lower_inverse`: ten float32
  products at full precision, the blocks of a size side by side along
  lanes).  The two products on the path from state to state (``W H``,
  ``Kd^T Vn``) keep every bit of their float32 operand
  (:func:`_carried_dot`): an operand rounded to bfloat16 there is a state
  carried in bfloat16.  ``Qg H`` and ``P Vn`` take ``H`` and ``Vn`` in the
  operands' dtype, as any matmul of the model does; ``T``, ``W``, ``U``,
  ``P``, ``Qg``, ``Kd`` are rounded to it where they are made.
- ``ddl_gdn_bwd``: the tiles, and a tile's chunks, last to first, ``G =
  dH_next`` in scratch, the tile prepared again and ``Vn`` computed again
  from the saved states::

      dVn = P^T dO + Kd G       dQg = dO H^T      dP  = dO Vn^T
      dU  = dVn                 dW  = -dVn H^T    dKd = Vn G^T
      da  = <G, H>              G  <- a G + Qg^T dO - W^T dVn

  with ``Kd G`` and ``W^T dVn``, the path from ``G`` to ``G``, carried the
  same way; then the preparation's transpose in place: ``dT = dW (beta
  exp(gam) k)^T + dU (beta v)^T``, ``dA = -T^T dT T^T`` below the diagonal
  (two products with the inverse itself, where a transpose of its ten
  would keep them all), the Gram and decay products back to ``dq``,
  ``dk``, ``dv`` and, along lanes again, ``dbeta`` and a position's
  ``dgam``.  No forward kernel runs in a backward pass.

The ``custom_vjp`` keeps the chunk states the forward wrote, tagged with
the name ``remat="selective"`` saves, and :func:`gated_delta_rule` tags
its output: a rematerialised backward recomputes the operands, reads
both, and runs the backward kernel alone (``models/remat.py``).  The
states leave the kernel in the operands' dtype: ``2 (T/C) H d_k d_v`` bytes
of bfloat16 a row, 283 MB at 16,384 positions, 30 heads, 96 x 192; the
state the kernel CARRIES stays float32.

The chunk length, the tile and the (row, head) groups a grid step holds
come from the shapes and the operands' dtype (:func:`_chunk_len`,
:func:`_tile_len`, :func:`_heads_per_step`); rows that are no multiple of
the tile are padded with steps that leave the state alone (``beta`` 0,
``g`` 0).  ``d_k`` and ``d_v`` need not fill 128 lanes (96 and 192 do not):
a block spans the whole of its last two axes.  Off the TPU the kernels run
in Pallas' interpret mode, which is how the CPU tests hold them to the
plain recurrence.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

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
#: Chunks a grid step takes together: two chunks' (C, C) tiles side by side
#: fill the 128 lanes of an elementwise pass and the 128 rows of a matmul's
#: right-hand side (:class:`_Tile`).
_TILE_CHUNKS = 2
#: The dtype the state is carried in from chunk to chunk.
_STATE_DTYPE = jnp.float32
#: (Row, head) groups a grid step holds at most: the leading axis of every
#: array of the kernels' bodies (ten read the same a group as six, on the
#: chip and in the compiler's schedule, PR 41).
_MAX_HEADS = 8
#: VMEM a grid step may take - its blocks, both pipeline buffers counted,
#: and what the kernel body keeps of a group between them - and what the
#: compiler is told it may use (a v5e core has 128 MiB).
_STEP_BUDGET = 20 * 2**20
_VMEM_LIMIT = 40 * 2**20
_LANES = 128

_NN = (((1,), (0,)), ((), ()))  # x y
_NT = (((1,), (1,)), ((), ()))  # x y^T
_TN = (((0,), (0,)), ((), ()))  # x^T y
# ... and the same of every group of a grid step, the groups leading
_GNN = (((2,), (1,)), ((0,), (0,)))
_GNT = (((2,), (2,)), ((0,), (0,)))
_GTN = (((1,), (1,)), ((0,), (0,)))


def _chunk_len(T: int) -> int:
    """:data:`_CHUNK`, or for a shorter row the power of two that holds it."""
    return min(_CHUNK, max(8, 1 << (T - 1).bit_length()))


def _tile_len(T: int, C: int) -> int:
    """Positions a grid step takes: :data:`_TILE_CHUNKS` chunks where the
    row has more than one (the row is padded to whole tiles), else the one."""
    return C * (_TILE_CHUNKS if T > C else 1)


def _heads_per_pass(B: int, T: int, H: int) -> int:
    """(Row, head) groups that share a grid step where VMEM allows: the
    largest divisor of their number up to :data:`_MAX_HEADS` (6 of one
    row's 30).  ``T`` has no say (the name and the signature are from when
    XLA prepared a pass of heads at a time; the benchmark's tests read
    them)."""
    return max(n for n in range(1, _MAX_HEADS + 1) if (B * H) % n == 0)


def _heads_per_step(groups: int, P: int, dk: int, dv: int, itemsize: int) -> int:
    """(Row, head) groups a grid step holds, a tile of ``P`` positions each:
    the largest divisor of their number, up to :data:`_MAX_HEADS`, that
    fits :data:`_STEP_BUDGET`, lane-padded - the backward kernel's step,
    which is the more: q, k, v, ``dO``, the decay sums and beta and the
    states the tile's chunks start from in, four cotangents out,
    double-buffered at the operands' ``itemsize``; and in float32 what the
    body has alive of one group: sixteen (P, P) tiles' worth (Gram
    matrices, decay, the inverse and its powers, their cotangents, paired
    or block-diagonal), eight (P, dk) and six (P, dv)."""
    pad = lambda n: -(-n // _LANES) * _LANES
    narrow, square, wide = P * pad(dk), P * pad(P), P * pad(dv)
    scalars = 2 * 4 * 8 * pad(P)  # (2, P) float32 in, and out
    states = _TILE_CHUNKS * dk * pad(dv)
    blocks = 2 * (itemsize * (4 * narrow + 3 * wide + states) + scalars)
    body = 4 * (16 * square + 8 * narrow + 6 * wide)
    for heads in range(_heads_per_pass(1, P, groups), 0, -1):
        if groups % heads == 0 and heads * (blocks + body) <= _STEP_BUDGET:
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


def _zero_at_the_rows_start(carried_ref):
    """The scratch that carries a row's state (or its cotangent) from tile
    to tile starts every row at zero."""

    @pl.when(pl.program_id(1) == 0)
    def _():
        carried_ref[...] = jnp.zeros_like(carried_ref)


def _full_dot(x, y, dims=_GNN):
    """A product of two float32 arrays that drops nothing of either."""
    return jax.lax.dot_general(
        x, y, dims, precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


def _keep(mask, x, other=None):
    """``x`` where ``mask``, else ``other`` (or zero): ``jnp.where`` of three
    arrays of one shape already.  ``jnp.where`` (and ``//``, ``%``) reach a
    kernel's body as nested ``jit``s, which Pallas traces AGAIN each time it
    lowers the body - three hundred of them made a warm set-up of the cell
    9 s longer (PR 41) - so the masks here are made once, whole."""
    return jax.lax.select(mask, x, jnp.zeros_like(x) if other is None else other)


class _Tile:
    """The layouts of a grid step's tile of ``n`` chunks of ``C`` positions,
    ``P = n C``, for its ``G`` (row, head) groups, and the ways between
    them; every array leads with the groups, which every operation here
    serves at once.  *Stacked*: (G, P, .), a position a row - q, k, v,
    ``W``, ``U``, a column of scalars.  *Paired*: (G, C, P), the chunks'
    (C, C) tiles side by side along lanes, row ``i`` and lane ``c C + j``
    position ``i`` against position ``j`` of chunk ``c`` - Gram matrices,
    decay, the inverse: an elementwise pass or a product over it serves
    all ``n`` chunks at once, on full lanes.  And for the inverse's smaller
    blocks the same at any side ``s``: (G, s, P), the (s, s) blocks of the
    tiles' diagonals side by side.  Masks (each made once), selects and
    8-aligned row slices only: nothing moves along lanes.  ``C`` and ``s``
    are powers of two."""

    def __init__(self, G: int, C: int, n: int):
        self.G, self.C, self.n, self.P = G, C, n, n * C
        # one kernel trace's masks, a few dozen keys (side, block, of how many)
        self._masks = {}  # ddl-lint: disable=DDL013
        row, col = self.row(C), self.lane(C) & (C - 1)
        self.lower, self.strict = row >= col, row > col
        at, of = self.row(self.P), self.lane(self.P)
        self.same = at == of  # a position against itself
        # ... and against the last position of its chunk
        self.ends = (at >> self.bits(C) == of >> self.bits(C)) & (of & (C - 1) == C - 1)
        self.last = jax.lax.broadcasted_iota(
            jnp.int32, (G, self.P, 1), 1) & (C - 1) == C - 1

    @staticmethod
    def bits(s):
        return s.bit_length() - 1

    def row(self, s):
        return jax.lax.broadcasted_iota(jnp.int32, (self.G, s, self.P), 1)

    def lane(self, s):
        return jax.lax.broadcasted_iota(jnp.int32, (self.G, s, self.P), 2)

    def block_is(self, s, b, among=None):
        """(G, s, P): the lanes of block ``b`` of side ``s`` (``among``: of
        the ``among`` blocks of each larger block), made once."""
        key = (s, b, among)
        if key not in self._masks:
            block = self.lane(s) >> self.bits(s)
            if among is not None:
                block = block & (among - 1)
            self._masks[key] = block == b
        return self._masks[key]

    def rows(self, x, c):
        """Chunk ``c`` of a stacked array."""
        return x[..., c * self.C : (c + 1) * self.C, :]

    def stacked(self, chunks):
        """A stacked array of its chunks'."""
        return chunks[0] if self.n == 1 else jnp.concatenate(chunks, axis=-2)

    def blocks(self, x, s):
        """(G, R, P), blocks of side ``R`` side by side (``R = P``: a
        position against a position; a stacked column (G, P, 1) is taken
        along the lanes of every row) -> the (s, s) blocks on their
        diagonals, side by side: (G, s, P)."""
        R = x.shape[-2]
        part = lambda m: jnp.broadcast_to(
            x[..., m * s : (m + 1) * s, :], (self.G, s, self.P))
        out = part(0)
        for m in range(1, R // s):
            out = _keep(self.block_is(s, m, R // s), part(m), out)
        return out

    def diagonal(self, x, shift=0):
        """(G, s, P) -> (G, P, P) block-diagonal: block ``b`` at rows ``(b +
        shift) s``, at its own lanes - what ``y`` (G, s, P) is multiplied by
        for ``y_b x_b`` (with ``shift`` 1: ``y_{b+1} x_b``), every block at
        once; and of a paired array what a stacked one is multiplied by,
        each chunk's rows by its own tile."""
        s = x.shape[-2]
        if s == self.P:
            return x
        return jnp.concatenate([
            _keep(self.block_is(s, b - shift), x) if b >= shift else jnp.zeros_like(x)
            for b in range(self.P // s)
        ], axis=-2)

    def paired(self, square):
        """(G, P, P), a position against a position -> the chunks' diagonal
        blocks, paired.  Of a stacked column (G, P, 1): the column along
        the lanes of a paired row."""
        return self.blocks(square, self.C)

    def row_sums(self, x):
        """Paired -> a stacked column of each tile's row sums."""
        return self.stacked([
            jnp.sum(_keep(self.block_is(self.C, c), x), axis=-1, keepdims=True)
            for c in range(self.n)
        ])

    def column(self, along_lanes):
        """(G, 1, P) -> the stacked column (G, P, 1): a masked sum against
        the identity (exact; a (P, 1) block's DMA would be a word a row)."""
        whole = jnp.broadcast_to(along_lanes, (self.G, self.P, self.P))
        return jnp.sum(_keep(self.same, whole), axis=-1, keepdims=True)

    def lanes(self, column):
        """The stacked column (G, P, 1) -> (G, 1, P)."""
        whole = jnp.broadcast_to(column, (self.G, self.P, self.P))
        return jnp.sum(_keep(self.same, whole), axis=-2, keepdims=True)


def _unit_lower_inverse(a, tile):
    """``(I + a)^-1`` for strictly lower triangular ``a`` (C, C), C a power
    of two - paired, every chunk of the tile and every group at once - in
    float32 at full precision.  The diagonal blocks of :data:`_INVERSE_BASE`
    by the finite product ``(I - a)(I + a^2)(I + a^4)(I + a^8)`` (``a`` is
    nilpotent: no power above ``a^8`` of a block is formed); then block
    forward substitution, two blocks into one::

        [[L11, 0], [L21, L22]]^-1 = [[X11, 0], [-X22 L21 X11, X22]]

    Ten products whatever ``C`` is, each over every block at once: the
    blocks of side ``s`` lie side by side along lanes, (s, P) - the paired
    layout is that of ``s = C`` - so a product streams ``s`` rows through
    the MXU against the blocks as a (P, P) block-diagonal, and the six
    products of the smallest blocks cost a quarter of what they would on
    (C, C) tiles (:class:`_Tile`)."""
    s = min(_INVERSE_BASE, tile.C)
    neg = -tile.blocks(a, s)
    inv = _keep(tile.row(s) == tile.lane(s) & (s - 1), jnp.ones_like(neg), neg)
    power, order = neg, 1
    while 2 * order < s:
        power = _full_dot(power, tile.diagonal(power))
        inv = inv + _full_dot(inv, tile.diagonal(power))
        order *= 2
    while s < tile.C:
        # the blocks pair off along lanes, X11 and X22; under the pair's
        # diagonal blocks L21, at X11's lanes
        left = tile.block_is(s, 0, 2)
        under = _keep(left, tile.blocks(a, 2 * s)[..., s:, :])
        below = _full_dot(
            inv, tile.diagonal(_full_dot(under, tile.diagonal(inv)), shift=1)
        )
        inv = jnp.concatenate([_keep(left, inv), _keep(left, -below, inv)], axis=-2)
        s *= 2
    return inv


class _Chunks(NamedTuple):
    """What :func:`_prepare` makes of a tile's operands: the three lines'
    operands in the operands' dtype - stacked, ``t`` and ``p`` (G, P, P)
    block-diagonal - and in float32 what the backward pass multiplies its
    cotangents by."""

    w: jax.Array  # (G, P, dk)
    u: jax.Array  # (G, P, dv)
    kd: jax.Array  # (G, P, dk)
    qg: jax.Array  # (G, P, dk)
    p: jax.Array  # (G, P, P)
    a: tuple  # n of (G, 1, 1) float32: each chunk's whole decay
    inverse: jax.Array  # paired float32: T
    t: jax.Array  # (G, P, P): T in the operands' dtype
    kb: jax.Array  # beta exp(gam) k
    vb: jax.Array  # beta v
    decay: jax.Array  # paired float32: D, zero above the diagonal
    kk: jax.Array  # paired float32: k_i . k_j
    qk: jax.Array  # paired float32: q_i . k_j
    beta: jax.Array  # (G, P, 1) float32, as are the two below
    grown: jax.Array  # exp(gam)
    left: jax.Array  # exp(gam_C - gam)


def _prepare(q, k, v, gam_beta, tile):
    """The chunks of a grid step's tiles from their operands (the module's
    docstring has the algebra): ``q``, ``k`` (G, P, dk), ``v`` (G, P, dv) in
    the operands' dtype; ``gam_beta`` (G, 2, P) float32, the decay sums and
    beta along lanes.  i / j are positions in a chunk: rows and, chunk by
    chunk, lanes of the paired layout (:class:`_Tile`)."""
    cd, f32 = q.dtype, jnp.float32
    gam_j, beta_j = gam_beta[:, 0:1], gam_beta[:, 1:2]  # (G, 1, P)
    gam, beta = tile.column(gam_j), tile.column(beta_j)  # (G, P, 1)
    # the whole chunk's log decay, at each of its positions
    whole = jnp.sum(
        _keep(tile.ends, jnp.broadcast_to(gam_j, tile.ends.shape)),
        axis=-1, keepdims=True,
    )
    # exp only where it is kept: above the diagonal the difference is positive
    decay = _keep(tile.lower, jnp.exp(_keep(tile.lower, tile.paired(gam) - gam_j)))
    grown = jnp.exp(gam)  # decay since the chunk's start, <= 1
    left = jnp.exp(whole - gam)
    kk, qk = tile.paired(_dot(k, k, _GNT)), tile.paired(_dot(q, k, _GNT))
    inverse = _unit_lower_inverse(
        _keep(tile.strict, tile.paired(beta) * kk * decay), tile
    )
    t = tile.diagonal(inverse).astype(cd)
    scaled = lambda x, by: (x.astype(f32) * by).astype(cd)
    kb, vb = scaled(k, beta * grown), scaled(v, beta)
    return _Chunks(
        w=_dot(t, kb, _GNN).astype(cd), u=_dot(t, vb, _GNN).astype(cd),
        kd=scaled(k, left), qg=scaled(q, grown),
        p=tile.diagonal(_keep(tile.lower, qk * decay)).astype(cd),
        a=tuple(jnp.exp(tile.rows(whole, c)[:, :1]) for c in range(tile.n)),
        inverse=inverse, t=t, kb=kb, vb=vb, decay=decay, kk=kk, qk=qk,
        beta=beta, grown=grown, left=left,
    )


def _fwd_kernel(q_ref, k_ref, v_ref, gb_ref, o_ref, states_ref, state_ref, *, C):
    """One tile of each of a grid step's groups: the output, the state each
    chunk of ``C`` positions starts from (``states``), and in scratch the
    state the last hands on.  No loop over the groups: every array leads
    with them, so the program runs an operation for all of them before the
    next - one group's products fill the MXU while another's wait."""
    _zero_at_the_rows_start(state_ref)
    tile, cd = _Tile(q_ref.shape[0], C, q_ref.shape[2] // C), o_ref.dtype
    c = _prepare(q_ref[:, 0], k_ref[:, 0], v_ref[:, 0], gb_ref[:, 0], tile)
    carried = state_ref[...]  # from chunk to chunk in the scratch's dtype
    written, read = [], []
    for s in range(tile.n):
        state = carried.astype(jnp.float32)
        low = state.astype(cd)
        states_ref[:, s] = low
        vn = tile.rows(c.u, s).astype(jnp.float32) - _carried_dot(
            tile.rows(c.w, s), state, _GNN)
        written.append(vn.astype(cd))
        read.append(_dot(tile.rows(c.qg, s), low, _GNN))
        carried = (
            c.a[s] * state + _carried_dot(tile.rows(c.kd, s), vn, _GTN)
        ).astype(state_ref.dtype)
    state_ref[...] = carried
    o_ref[:, 0] = (
        tile.stacked(read) + _dot(c.p, tile.stacked(written), _GNN)
    ).astype(cd)


def _bwd_kernel(q_ref, k_ref, v_ref, gb_ref, states_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dgb_ref, grad_ref, *, C):
    """The same tiles on the way back (the grid runs the tiles, and a tile's
    chunks, last to first): ``grad`` in scratch is the cotangent of the
    state the tile hands on; the chunks are prepared again and ``Vn``
    computed again from the saved states; then the three lines' backward,
    then the preparation's, to the cotangents of q, k, v and, along lanes,
    of the decay sums and beta."""
    _zero_at_the_rows_start(grad_ref)
    tile, cd, f32 = _Tile(q_ref.shape[0], C, q_ref.shape[2] // C), do_ref.dtype, jnp.float32
    q, k, v, d_o = q_ref[:, 0], k_ref[:, 0], v_ref[:, 0], do_ref[:, 0]
    c = _prepare(q, k, v, gb_ref[:, 0], tile)
    # the three lines
    carried = grad_ref[...]  # from chunk to chunk in the scratch's dtype
    from_o = _dot(c.p, d_o, _GTN)
    vn, d_u, d_w, d_qg, d_kd, d_whole = ([None] * tile.n for _ in range(6))
    for s in reversed(range(tile.n)):
        of = lambda x: tile.rows(x, s)
        state, grad = states_ref[:, s], carried.astype(f32)
        vn[s] = (of(c.u).astype(f32) - _dot(of(c.w), state, _GNN)).astype(cd)
        d_vn = of(from_o) + _carried_dot(of(c.kd), grad, _GNN)
        d_u[s] = d_vn.astype(cd)
        d_w[s] = (-_dot(d_u[s], state, _GNT)).astype(cd)
        d_qg[s] = _dot(of(d_o), state, _GNT)
        d_kd[s] = _dot(vn[s], grad.astype(cd), _GNT)
        d_whole[s] = c.a[s] * jnp.sum(
            jnp.sum(grad * state.astype(f32), axis=-2, keepdims=True),
            axis=-1, keepdims=True,
        )
        carried = (
            c.a[s] * grad + _dot(of(c.qg), of(d_o), _GTN)
            - _carried_dot(of(c.w), d_vn, _GTN)
        ).astype(grad_ref.dtype)
    grad_ref[...] = carried
    vn, d_u, d_w, d_qg, d_kd = (tile.stacked(x) for x in (vn, d_u, d_w, d_qg, d_kd))
    d_p = tile.paired(_dot(d_o, vn, _GNT))
    # W = T kb, U = T vb; d (I + A)^-1 = -T dA T: two products with the
    # inverse itself, where a transpose of its ten would keep them all
    d_kb, d_vb = _dot(c.t, d_w, _GTN), _dot(c.t, d_u, _GTN)
    d_t = tile.paired(_dot(d_w, c.kb, _GNT) + _dot(d_u, c.vb, _GNT))
    d_tri = _keep(tile.strict, -_full_dot(
        tile.paired(_full_dot(c.inverse, d_t, _GTN)), tile.diagonal(c.inverse), _GNT
    ))
    # A = beta kk D below the diagonal, P = qk D on and below it
    beta = tile.paired(c.beta)
    by_beta = d_tri * c.kk * c.decay
    d_kk = tile.diagonal(d_tri * beta * c.decay).astype(cd)
    d_qk = _keep(tile.lower, d_p) * c.decay
    # D_ij = exp(gam_i - gam_j): what D's cotangent is worth to gam_i,
    # summed along the row, and to gam_j, along the column
    d_diff = by_beta * beta + d_qk * c.qk
    d_qk = tile.diagonal(d_qk).astype(cd)
    k32, sums = k.astype(f32), lambda x: jnp.sum(x, axis=-1, keepdims=True)
    d_scale = sums(d_kb * k32)  # of beta exp(gam), k's scale in kb
    d_left = sums(d_kd * k32) * c.left  # of log(exp(gam_C - gam))
    # ... and of a chunk's whole log decay, which is its last gam
    d_last = _keep(tile.last, tile.stacked([
        jnp.broadcast_to(
            d_whole[s] + jnp.sum(tile.rows(d_left, s), axis=-2, keepdims=True),
            (tile.G, C, 1),
        ) for s in range(tile.n)
    ]))
    d_gam = (
        tile.row_sums(d_diff) + d_scale * c.beta * c.grown
        + sums(d_qg * q.astype(f32)) * c.grown - d_left + d_last
    )
    d_beta = tile.row_sums(by_beta) + d_scale * c.grown + sums(d_vb * v.astype(f32))
    dgb_ref[:, 0, 0:1, :] = tile.lanes(d_gam) - jnp.sum(d_diff, axis=-2, keepdims=True)
    dgb_ref[:, 0, 1:2, :] = tile.lanes(d_beta)
    dq_ref[:, 0] = (_dot(d_qk, k, _GNN) + d_qg * c.grown).astype(cd)
    dk_ref[:, 0] = (
        _dot(d_kk, k, _GNN) + _dot(d_kk, k, _GTN) + _dot(d_qk, q, _GTN)
        + d_kb * (c.beta * c.grown) + d_kd * c.left
    ).astype(cd)
    dv_ref[:, 0] = (d_vb * c.beta).astype(cd)


def _call(name, kernel, ins, outs, C, reverse, interpret):
    """``kernel`` over the grid (groups of rows, tiles), the tile axis
    sequential - last to first where ``reverse`` - each operand a block of
    ``heads`` rows' one tile, a float32 state in scratch.  ``ins``: the
    arrays, (G, tiles, P, .), q, k, v first, the states (G, chunks, dk,
    dv); ``outs``: their shapes and dtypes.  ``C``: positions a chunk."""
    G, tiles, P, dk = ins[0].shape
    dv = ins[2].shape[-1]
    heads = _heads_per_step(G, P, dk, dv, ins[0].dtype.itemsize)
    last = tiles - 1
    at = (lambda i, c: (i, last - c, 0, 0)) if reverse else (lambda i, c: (i, c, 0, 0))
    spec = lambda x: pl.BlockSpec((heads, x.shape[1] // tiles) + tuple(x.shape[2:]), at)
    return named_pallas_call(
        name,
        functools.partial(kernel, C=C),
        grid=(G // heads, tiles),
        in_specs=[spec(x) for x in ins],
        out_specs=[spec(x) for x in outs],
        out_shape=outs,
        scratch_shapes=[pltpu.VMEM((heads, dk, dv), _STATE_DTYPE)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
    )(*ins)


def _tag(x):
    """``x`` under the name ``remat="selective"`` saves (lazily: the
    models import this module)."""
    from ddl_tpu.models.remat import tag_attn_out

    return tag_attn_out(x)


def _forward(q, k, v, gb, C, interpret):
    """The tiles' outputs (G, tiles, P, dv) and the state each chunk starts
    from (G, chunks, dk, dv), both in the operands' dtype."""
    G, tiles, P, dk = q.shape
    like = lambda *shape: jax.ShapeDtypeStruct((G,) + shape, q.dtype)
    return _call(
        "ddl_gdn_fwd", _fwd_kernel, (q, k, v, gb),
        [like(tiles, P, v.shape[-1]), like(tiles * (P // C), dk, v.shape[-1])],
        C, False, interpret,
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _chunks(q, k, v, gb, C, interpret):
    """``O`` of every chunk of ``C`` positions, each met with the state the
    chunks before it leave: ``q``, ``k`` (G, tiles, P, dk), ``v`` (G, tiles,
    P, dv) in the operands' dtype, a tile the ``P / C`` chunks a grid step
    takes; ``gb`` (G, tiles, 2, P) float32: the chunks' decay sums ``gam``
    and their beta, each along a lane-dense row."""
    return _forward(q, k, v, gb, C, interpret)[0]


def _chunks_fwd(q, k, v, gb, C, interpret):
    o, states = _forward(q, k, v, gb, C, interpret)
    return o, (q, k, v, gb, _tag(states))


def _chunks_bwd(C, interpret, res, d_o):
    like = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)
    return tuple(_call(
        "ddl_gdn_bwd", _bwd_kernel, res + (d_o,), [like(x) for x in res[:4]],
        C, True, interpret,
    ))


_chunks.defvjp(_chunks_fwd, _chunks_bwd)


def gated_delta_rule(q, k, v, g, beta, interpret: Optional[bool] = None):
    """``o_t = S_t q_t`` of the gated delta rule (the module's docstring).

    ``q``, ``k``: (B, T, H, d_k), as the recurrence takes them (the model
    normalises and scales them); ``v``: (B, T, H, d_v); ``g`` (log decay,
    <= 0) and ``beta``: (B, T, H).  Returns (B, T, H, d_v) in ``q``'s
    dtype.  Decay sums, the triangular inverse and the state are float32
    whatever the operands are; bfloat16 operands meet the MXU as bfloat16,
    float32 ones at full precision.  Differentiable in all five.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    B, T, H, _ = q.shape
    C = _chunk_len(T)
    P = _tile_len(T, C)
    tiles = -(-T // P)

    def head_major(x, span=P):
        """(B, T, H[, d]) -> (B H, tiles, P[, d]) (or in other spans of
        positions), zero steps behind the row: the layout the kernels'
        blocks take, by the one transpose an operand gets."""
        x = jnp.pad(x, ((0, 0), (0, tiles * P - T)) + ((0, 0),) * (x.ndim - 2))
        x = jnp.moveaxis(x, 2, 1)  # (B, H, T[, d])
        return x.reshape((B * H, -1, span) + x.shape[3:])

    # what is O(T H) scalars stays XLA's: a chunk's decay sums (and, through
    # autodiff, the reverse sums that make g's cotangent of theirs)
    gam = jnp.cumsum(head_major(g.astype(jnp.float32), C), axis=-1)
    gb = jnp.stack(
        [gam.reshape(B * H, tiles, P), head_major(beta.astype(jnp.float32))], axis=2
    )
    o = _chunks(head_major(q), head_major(k), head_major(v), gb, C, interpret)
    # (B H, tiles, P, d_v) -> (B, T, H, d_v)
    o = o.reshape(B, H, tiles * P, -1)
    return _tag(jnp.moveaxis(o, 1, 2)[:, :T])
