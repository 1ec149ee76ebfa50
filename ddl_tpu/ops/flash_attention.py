"""Causal flash attention as Pallas TPU kernels (forward + backward).

Blockwise attention with online softmax (the same math as
``parallel/ring_attention.py``, which runs it *across* devices; these
kernels run it *within* one device so the (T, T) score matrix never leaves
VMEM):

- Forward: grid = (batch, heads, Q blocks, KV blocks); the innermost KV
  axis is sequential on TPU, so running max / denominator / output
  accumulate in VMEM scratch across KV steps and the output block is
  written once, on the last step.  The per-row logsumexp is emitted as a
  residual for the backward pass and (via
  :func:`flash_attention_with_lse`) for cross-device online-softmax
  combination — ring attention calls this kernel once per ring step and
  merges steps with the logsumexp identity.
- Backward: ONE kernel on the dK/dV grid (batch, heads, KV blocks, Q
  blocks).  dK/dV accumulate over Q blocks for a fixed KV block, and the
  step's ``ds`` also goes into dQ, whose float32 rows of the whole (batch,
  head) stay in VMEM across the head's steps (``_dkv_kernel``'s ``dq``;
  each Q block is rounded and written out once, after its last addition:
  ``_dq_block_of``) — so a block pair's scores, probabilities and
  ``dO Vᵀ`` are computed once, five products a pair where a ``dq`` kernel
  beside a ``dkv`` kernel does seven.  A row whose dQ would not fit the
  VMEM the kernel may take (``_BWD_ROW_BYTES``: past 65,536 positions at
  128 lanes) keeps those two kernels, ``_dq_kernel`` on the forward's grid
  and ``_dkv_kernel`` without dQ: decided from the operands' shapes alone,
  the same bodies, the same gradients bit for bit.  Probabilities are
  recomputed from the saved logsumexp — nothing quadratic is ever
  materialised.  An incoming lse cotangent (from the ring combine) folds
  into the score gradient as ``ds += p * dlse`` (since d lse_i / d s_ik =
  p_ik).  Under GQA the per-Q-head dK/dV are summed over each query-head
  group outside the kernel.
- What a forward call keeps for its backward (the ``custom_vjp``
  residuals) is its output as the caller gets it, ``(B, T, H, D)``, and
  the compact logsumexp ``(B, H, T)`` float32, both tagged with the name
  ``remat="selective"`` saves (``_saved``): a rematerialised backward
  then reads them and runs no forward kernel.  ``delta`` is taken in the
  caller's layout and the kernels' ``(B, H, Tq, 1)`` row operands are
  rebuilt from the compact values, so no lane-padded row array and no
  second copy of the output outlives the forward pass.
- Global-position offsets ride in as scalar-prefetch arguments (they are
  traced values inside a ring ``lax.scan``), so causal masking uses global
  token positions and blocks strictly above the (global) diagonal skip
  their matmuls via ``pl.when`` — a ring step that is entirely in the
  masked future costs DMAs but no FLOPs.
- K/V stay compact under grouped-query attention — the head index map
  divides by ``kv_repeat``.
- A sliding ``window`` (offsets (0, 0) only) is the same kernels on
  grids whose innermost axis covers the band's blocks alone
  (``band_grid``), under the names ``ddl_flash_swa_*``.
- Latent attention (``q_rope`` / ``k_rope``): the score is the sum of two
  products, ``q . k`` over the heads' own width and ``q_rope . k_rope``
  over a rotary width whose key is ONE a position, shared by all heads
  (its index map sends every head to it, as ``kv_repeat`` sends a group);
  the scale is ``1/sqrt`` of the two widths together and the value keeps
  the heads' own.  The same kernels with two more operands, under the
  names ``ddl_flash_mla_*`` (dQ_rope rides the backward kernel beside dQ);
  ``dk_rope`` is summed over the heads outside the kernel, as a GQA
  group's ``dk`` is.

The public wrappers pad ragged sequence lengths to the block size (padded
keys are masked out, padded query rows sliced off) and fall back to
``interpret=True`` off-TPU, which is how the CPU test suite validates them
bit-for-bit against the dense oracle.

A sequence that fits ONE block needs none of this — no carry, no block
skipping, no ``(B, H, T, D)`` relayout, no pad — and pays for all of it;
``flash_attention()`` hands such calls (``flash_tile.fits``: shapes and
arguments only) to the one-block kernels of ``ops/flash_tile.py``, which
share nothing with the kernels here.  ``flash_attention_with_lse``, packed
rows, GQA and every longer sequence stay here.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ddl_tpu.ops import flash_tile
from ddl_tpu.ops.naming import named_pallas_call

_NEG_INF = -1e30
_LANES = 128  # TPU vector lane count: scratch accumulators are (bq, 128)


def _positions(offs_ref, i, j, block_q, block_k):
    """(global q, global k, local q, local k) position grids."""
    q_loc = i * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0
    )
    k_loc = j * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1
    )
    return offs_ref[0] + q_loc, offs_ref[1] + k_loc, q_loc, k_loc


def _live(offs_ref, i, j, block_q, block_k, causal, window=None):
    """False only when block (i, j) lies strictly above the global causal
    diagonal (then every entry is masked and the matmuls can be skipped)
    — or, with a ``window``, wholly below the band: its newest key is
    already out of the oldest query's window."""
    if not causal:
        return j >= 0  # traced True
    live = (
        offs_ref[1] + j * block_k
        <= offs_ref[0] + i * block_q + block_q - 1
    )
    if window is None:
        return live
    return live & (
        offs_ref[1] + (j + 1) * block_k - 1
        > offs_ref[0] + i * block_q - window
    )


def _crosses_diag(offs_ref, i, j, block_q, block_k, causal, window=None):
    """True when block (i, j) straddles the global causal diagonal (some
    entries masked, some not) — or, with a ``window``, the band's lower
    edge: its oldest key is out of the newest query's window.  Interior
    blocks — fully below the diagonal, fully inside the band — skip mask
    construction entirely: the two (bq, bk) position grids, compares, and
    selects are the kernel's dominant VPU cost after exp."""
    if not causal:
        return j < 0  # traced False
    crosses = (
        offs_ref[1] + (j + 1) * block_k - 1
        > offs_ref[0] + i * block_q
    )
    if window is None:
        return crosses
    return crosses | (
        offs_ref[0] + (i + 1) * block_q - 1 - (offs_ref[1] + j * block_k)
        >= window
    )


class BandGrid(NamedTuple):
    """Static shape of the windowed kernels' grids for one row (see
    :func:`band_grid`)."""

    nqb: int  # query blocks a row
    nkb: int  # key blocks a row
    nk: int  # inner steps of fwd / dq: key blocks a query block
    nq: int  # inner steps of dkv: query blocks a key block
    live: int  # blocks a head that hold a visible pair

    @property
    def steps(self) -> int:
        """Executed grid steps a head, forward (dq alike)."""
        return self.nqb * self.nk

    @property
    def steps_dkv(self) -> int:
        return self.nkb * self.nq


def band_grid(T: int, window: int, block_q: int, block_k: int) -> BandGrid:
    """The band's extent in blocks for a row of ``T`` tokens at offsets
    (0, 0) — all static, so the windowed kernels' inner grid axis runs
    over the band's blocks and not over the whole row.

    Query block ``i`` sees keys ``i*block_q - window + 1 ..
    (i+1)*block_q - 1`` and key block ``j`` is seen by queries
    ``j*block_k .. (j+1)*block_k + window - 2``; ``nk`` / ``nq`` are the
    most blocks either span covers over the row, every block inside a span
    holds a visible pair, and ``live`` counts them.  At Trinity-Mini's
    8192 / 2048 / 1024 / 1024: 8 x 3 = 24 steps a head, 21 live (64 steps
    before the grid followed the band)."""
    nqb, nkb = -(-T // block_q), -(-T // block_k)
    k_spans = [
        (max(0, (i * block_q - window + 1) // block_k),
         min(nkb - 1, ((i + 1) * block_q - 1) // block_k))
        for i in range(nqb)
    ]
    q_spans = [
        ((j * block_k) // block_q,
         min(nqb - 1, ((j + 1) * block_k + window - 2) // block_q))
        for j in range(nkb)
    ]
    return BandGrid(
        nqb, nkb,
        nk=max(hi - lo + 1 for lo, hi in k_spans),
        nq=max(hi - lo + 1 for lo, hi in q_spans),
        live=sum(hi - lo + 1 for lo, hi in k_spans),
    )


def _band_k_block(i, jj, block_q, block_k, band: BandGrid):
    """Key block of inner step ``jj`` of query block ``i`` (fwd, dq): the
    ``nk`` blocks that end on the diagonal's.  Below 0 at the row's head:
    a dead step, whose index map clamps to block 0 — the neighbour's, so
    nothing is fetched for it."""
    top = jnp.minimum(((i + 1) * block_q - 1) // block_k, band.nkb - 1)
    return top - (band.nk - 1) + jj


def _band_q_block(j, ii, block_q, block_k):
    """Query block of inner step ``ii`` of key block ``j`` (dkv): the
    ``nq`` blocks from the diagonal's on.  Past ``nqb - 1`` at the row's
    tail: dead, clamped to the last block."""
    return (j * block_k) // block_q + ii


def _k_block_of(block_q, block_k, band: BandGrid):
    """(query block, inner step) -> key block to fetch, for index maps."""
    return lambda i, jj: jnp.maximum(
        _band_k_block(i, jj, block_q, block_k, band), 0)


def _q_block_of(block_q, block_k, band: BandGrid):
    """(key block, inner step) -> query block to fetch, for index maps."""
    return lambda j, ii: jnp.minimum(
        _band_q_block(j, ii, block_q, block_k), band.nqb - 1)


def _seg_invalid(seg):
    """(bq, bk) True where query and key belong to different packed
    segments.  ``seg`` is the (seg_q_ref, seg_k_ref) pair of (1,1,b,1)
    int32 blocks, or None when the batch is unpacked."""
    sq = seg[0][0, 0][:, 0]  # (bq,)
    sk = seg[1][0, 0][:, 0]  # (bk,)
    return sq[:, None] != sk[None, :]


def _fwd_kernel(offs_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_ref, l_ref, acc_ref, *, scale: float, causal: bool,
                block_q: int, block_k: int, kv_len: int, precision,
                seg=None, window=None, band=None, rope=None):
    i = pl.program_id(2)  # Q block
    jj = pl.program_id(3)  # inner step (sequential): the KV block ...
    j = jj  # ... itself, or with a band its place among the band's blocks
    if band is not None:
        j = _band_k_block(i, jj, block_q, block_k, band)

    @pl.when(jj == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def _scores():  # (bq, bk) f32
        return _block_scores(q_ref, k_ref, scale, precision, rope)

    def _update(s):
        """Online-softmax accumulate of one score block into m/l/acc."""
        v = v_ref[0, 0]
        m_prev = jnp.max(m_ref[:], axis=-1)  # lanes replicated -> any reduce
        l_prev = jnp.max(l_ref[:], axis=-1)
        m_cur = jnp.max(s, axis=-1)
        m_next = jnp.maximum(m_prev, m_cur)
        # Fully-masked-so-far rows keep m at -inf; zero the exponent shift
        # so exp() sees finite args.  Masked scores are the finite
        # _NEG_INF, so exp(s - safe_m) underflows to exactly 0 for them —
        # no explicit zeroing select is needed.
        safe_m = jnp.where(m_next <= _NEG_INF / 2, 0.0, m_next)
        alpha = jnp.exp(jnp.where(m_prev <= _NEG_INF / 2, _NEG_INF,
                                  m_prev - safe_m))
        p = jnp.exp(s - safe_m[:, None])

        l_next = alpha * l_prev + jnp.sum(p, axis=-1)
        # p drops to the input dtype for the MXU (standard flash practice;
        # the fp32 path keeps p fp32 since v.dtype is fp32 there).
        acc_ref[:] = acc_ref[:] * alpha[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=precision,
        )
        m_ref[:] = jnp.broadcast_to(m_next[:, None], m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_next[:, None], l_ref.shape)

    # Two real branches (pl.when lowers to an scf.if, executed
    # conditionally — a value-level lax.cond computed both sides):
    # interior blocks fully below the diagonal with no padded keys skip
    # mask construction entirely, the dominant VPU cost after exp.
    live = _live(offs_ref, i, j, block_q, block_k, causal, window)
    if band is not None:
        live = live & (j >= 0)  # a step before the row's head
    needs_mask = (
        _crosses_diag(offs_ref, i, j, block_q, block_k, causal, window)
        | ((j + 1) * block_k > kv_len)
    )
    if seg is not None:
        # Packed segments can differ anywhere — every live block masks.
        needs_mask = needs_mask | (j >= 0)

    @pl.when(live & needs_mask)
    def _attend_masked():
        s = _scores()
        q_pos, k_pos, _, k_loc = _positions(offs_ref, i, j, block_q, block_k)
        invalid = k_loc >= kv_len  # padded keys
        if causal:
            invalid |= k_pos > q_pos
        if window is not None:
            invalid |= q_pos - k_pos >= window
        if seg is not None:
            invalid |= _seg_invalid(seg)
        _update(jnp.where(invalid, _NEG_INF, s))

    @pl.when(live & jnp.logical_not(needs_mask))
    def _attend_fast():
        _update(_scores())

    @pl.when(jj == pl.num_programs(3) - 1)
    def _finish():
        m = jnp.max(m_ref[:], axis=-1)
        l = jnp.max(l_ref[:], axis=-1)
        # logsumexp residual; -inf marks rows with no valid keys.
        lse = jnp.where(
            l > 0.0, jnp.where(m <= _NEG_INF / 2, 0.0, m) + jnp.log(l),
            _NEG_INF,
        )
        lse_ref[0, 0] = lse[:, None]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[:] / l_safe[:, None]).astype(o_ref.dtype)


def _block_scores(q_ref, k_ref, scale, precision, rope=None):
    """Scaled q·kᵀ of the current blocks, fp32 accumulation with operands
    in the input dtype (bf16 runs the MXU at full rate; fp32 would quarter
    it) — shared by the forward and both backward kernels.  ``rope``: the
    latent form's (q_rope_ref, k_rope_ref, ...), whose product joins the
    score before the scale."""
    s = jax.lax.dot_general(
        q_ref[0, 0], k_ref[0, 0], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32, precision=precision,
    )
    if rope is not None:
        s = s + jax.lax.dot_general(
            rope[0][0, 0], rope[1][0, 0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision,
        )
    return s * scale


def _bwd_p_dispatch(offs_ref, q_ref, k_ref, lse_ref, i, j, accum, *,
                    scale, causal, block_q, block_k, seq_len, kv_len,
                    precision, seg=None, window=None, in_row=None,
                    rope=None):
    """Backward-pass block dispatch shared by the dQ and dK/dV kernels:
    dead blocks skipped, boundary blocks recompute p with full masking,
    interior blocks use the bare ``exp(s - lse)`` fast path (statement-
    level ``pl.when`` — real branches, unlike a value-level cond which
    Mosaic computes on both sides).  ``in_row`` (windowed grids): False
    on a step whose block lies before the row's head or past its tail."""
    live = _live(offs_ref, i, j, block_q, block_k, causal, window)
    if in_row is not None:
        live = live & in_row
    needs_mask = _needs_mask_bwd(
        offs_ref, i, j, block_q, block_k, causal, seq_len, kv_len, window
    )
    if seg is not None:
        needs_mask = needs_mask | (j >= 0)  # packed: every block masks

    def scores():
        return _block_scores(q_ref, k_ref, scale, precision, rope)

    @pl.when(live & needs_mask)
    def _accum_masked():
        accum(_p_masked(
            offs_ref, scores(), lse_ref[0, 0][:, 0], i, j, causal=causal,
            block_q=block_q, block_k=block_k, seq_len=seq_len,
            kv_len=kv_len, seg=seg, window=window,
        ))

    @pl.when(live & jnp.logical_not(needs_mask))
    def _accum_fast():
        accum(jnp.exp(scores() - lse_ref[0, 0][:, 0][:, None]))


def _p_masked(offs_ref, s, lse, i, j, *, causal, block_q, block_k,
              seq_len, kv_len, seg=None, window=None):
    """p = exp(s - lse) with mask/padding/empty-row handling (the slow,
    boundary-block path — interior blocks use the bare exp)."""
    q_pos, k_pos, q_loc, k_loc = _positions(offs_ref, i, j, block_q, block_k)
    invalid = (k_loc >= kv_len) | (q_loc >= seq_len)
    if causal:
        invalid |= k_pos > q_pos
    if window is not None:
        invalid |= q_pos - k_pos >= window
    if seg is not None:
        invalid |= _seg_invalid(seg)
    empty = lse <= _NEG_INF / 2  # (bq,)
    p = jnp.exp(s - jnp.where(empty, 0.0, lse)[:, None])
    return jnp.where(invalid | empty[:, None], 0.0, p)


def _needs_mask_bwd(offs_ref, i, j, block_q, block_k, causal, seq_len,
                    kv_len, window=None):
    """True unless block (i, j) is interior: fully below the diagonal
    (and inside the band, with a ``window``) with no padded keys/queries.  Interior blocks cannot contain masked entries
    or globally-empty rows (the block itself supplies valid keys), so
    ``exp(s - lse)`` is exact there and mask construction is skipped."""
    return (
        _crosses_diag(offs_ref, i, j, block_q, block_k, causal, window)
        | ((j + 1) * block_k > kv_len)
        | ((i + 1) * block_q > seq_len)
    )


def _dq_add(accs, rows, ds, k_ref, rope, precision):
    """``dq += ds k`` (and for the latent form ``dq_rope += ds k_rope``)
    into ``rows`` of the float32 accumulators ``accs``: the whole of the
    ``dq`` kernel's, or in the one kernel of the backward pass Q block ``i``
    of the row's."""
    keys = (k_ref,) if rope is None else (k_ref, rope[1])
    for acc, key in zip(accs, keys):
        k = key[0, 0]
        acc[rows] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision,
        )


def _dq_kernel(offs_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
               dlse_ref, dq_ref, dq_acc, *, scale: float, causal: bool,
               block_q: int, block_k: int, seq_len: int, kv_len: int,
               precision, seg=None, window=None, band=None, rope=None):
    i = pl.program_id(2)  # Q block
    jj = pl.program_id(3)  # inner step (sequential): the KV block, or ...
    j, in_row = jj, None
    if band is not None:  # ... its place among the band's blocks
        j = _band_k_block(i, jj, block_q, block_k, band)
        in_row = j >= 0

    @pl.when(jj == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)
        if rope is not None:  # (q_rope, k_rope, dq_rope, its accumulator)
            rope[3][:] = jnp.zeros_like(rope[3])

    def _accum(p):
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision,
        )  # (bq, bk) fp32
        ds = p * (dp - delta_ref[0, 0] + dlse_ref[0, 0]) * scale
        _dq_add((dq_acc,) if rope is None else (dq_acc, rope[3]),
                slice(None), ds, k_ref, rope, precision)

    _bwd_p_dispatch(
        offs_ref, q_ref, k_ref, lse_ref, i, j, _accum, scale=scale,
        causal=causal, block_q=block_q, block_k=block_k, seq_len=seq_len,
        kv_len=kv_len, precision=precision, seg=seg, window=window,
        in_row=in_row, rope=rope,
    )

    @pl.when(jj == pl.num_programs(3) - 1)
    def _finish():
        dq_ref[0, 0] = dq_acc[:].astype(dq_ref.dtype)
        if rope is not None:
            rope[2][0, 0] = rope[3][:].astype(rope[2].dtype)


def _dkv_kernel(offs_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dlse_ref, dk_ref, dv_ref, dk_acc, dv_acc, *, scale: float,
                causal: bool, block_q: int, block_k: int, seq_len: int,
                kv_len: int, precision, seg=None, window=None, band=None,
                rope=None, dq=None):
    """dK/dV of key block ``j`` over its Q blocks.  With ``dq`` — (dQ's
    output refs, their float32 accumulators), one each or with dQ_rope's
    two — the whole backward pass: the step's ``ds`` also goes into Q block
    ``i`` of the accumulators, which hold the (batch, head)'s whole row in
    VMEM ((Q blocks, block_q, width): zeroed at the head's first step,
    added to in the ``dq`` kernel's order, ``j`` ascending for a fixed
    ``i``), and Q block ``i`` is rounded and written out on the last step
    that touches it (``_dq_final``)."""
    j = pl.program_id(2)  # KV block
    ii = pl.program_id(3)  # inner step (sequential): the Q block, or ...
    i, in_row = ii, None
    if band is not None:  # ... its place among the band's blocks
        i = _band_q_block(j, ii, block_q, block_k)
        in_row = i < band.nqb

    @pl.when(ii == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)
        if rope is not None:  # (q_rope, k_rope, dk_rope, its accumulator)
            rope[3][:] = jnp.zeros_like(rope[3])

    if dq is not None:
        @pl.when((j == 0) & (ii == 0))
        def _init_row():
            for acc in dq[1]:
                acc[:] = jnp.zeros_like(acc)

    def _accum(p):
        q = q_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        dv_acc[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision,
        )  # (bk, d)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision,
        )
        ds = p * (dp - delta_ref[0, 0] + dlse_ref[0, 0]) * scale
        dk_acc[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision,
        )
        if rope is not None:
            rope[3][:] += jax.lax.dot_general(
                ds.astype(q.dtype), rope[0][0, 0], (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32, precision=precision,
            )
        if dq is not None:
            _dq_add(dq[1], i, ds, k_ref, rope, precision)

    _bwd_p_dispatch(
        offs_ref, q_ref, k_ref, lse_ref, i, j, _accum, scale=scale,
        causal=causal, block_q=block_q, block_k=block_k, seq_len=seq_len,
        kv_len=kv_len, precision=precision, seg=seg, window=window,
        in_row=in_row, rope=rope,
    )

    @pl.when(ii == pl.num_programs(3) - 1)
    def _finish():
        dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)
        if rope is not None:
            rope[2][0, 0] = rope[3][:].astype(rope[2].dtype)

    if dq is not None:
        final = _dq_final(j, i, pl.num_programs(2), block_q, block_k, band)
        if in_row is not None:
            final = final & in_row

        @pl.when(final)
        def _finish_rows():
            for out, acc in zip(*dq):
                out[0, 0] = acc[i].astype(out.dtype)


def _dq_final(j, i, nkb, block_q, block_k, band):
    """True on the last step of the dK/dV grid that touches Q block ``i``:
    the row's last key block, or with a band the block that holds the Q
    block's last position (the diagonal's: ``band_grid``'s spans end on
    it, and a key block's Q blocks start at the first such ``i``)."""
    if band is None:
        return j == nkb - 1
    return jnp.minimum(((i + 1) * block_q - 1) // block_k, nkb - 1) == j


def _dq_block_of(nqb, nkb, block_q, block_k, band, q_block):
    """(key block, inner step) -> the Q block dQ's output block stands at,
    for its index map on the dK/dV grid.  The pipeline writes an output
    block back when the next step's index differs, so the index is the
    step's own Q block where the step finishes it (``_dq_final``) and
    otherwise the NEXT block to be finished - block 0 until the row's
    last key block, or with a band the first Q block of the next key block
    - which no step writes before its turn: every block leaves VMEM once,
    rounded, after its last addition."""
    def at(j, ii):
        i = q_block(j, ii)  # clamped into the row by the band's map
        park = 0 if band is None else jnp.minimum(
            _band_q_block(j + 1, 0, block_q, block_k), nqb - 1)
        return jnp.where(
            _dq_final(j, i, nkb, block_q, block_k, band), i, park)
    return at


# Packed-segment kernel adapters: same bodies, two extra int32 input refs
# (query-/key-segment blocks) spliced in by position.  Separate entry
# points keep the unpacked kernels' ref layout byte-identical.


def _fwd_kernel_seg(offs_ref, q_ref, k_ref, v_ref, sq_ref, sk_ref, o_ref,
                    lse_ref, m_ref, l_ref, acc_ref, **kw):
    _fwd_kernel(offs_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref,
                l_ref, acc_ref, seg=(sq_ref, sk_ref), **kw)


def _dq_kernel_seg(offs_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                   delta_ref, dlse_ref, sq_ref, sk_ref, dq_ref, dq_acc,
                   **kw):
    _dq_kernel(offs_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
               dlse_ref, dq_ref, dq_acc, seg=(sq_ref, sk_ref), **kw)


def _dkv_kernel_seg(offs_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                    delta_ref, dlse_ref, sq_ref, sk_ref, dk_ref, dv_ref,
                    dk_acc, dv_acc, **kw):
    _dkv_kernel(offs_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dlse_ref, dk_ref, dv_ref, dk_acc, dv_acc,
                seg=(sq_ref, sk_ref), **kw)


# Latent-attention kernel adapters, in the packed ones' manner: the same
# bodies with the rotary operands (and their gradient's output and
# accumulator) spliced in by position.


def _fwd_kernel_mla(offs_ref, q_ref, k_ref, v_ref, qr_ref, kr_ref, o_ref,
                    lse_ref, m_ref, l_ref, acc_ref, **kw):
    _fwd_kernel(offs_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref,
                l_ref, acc_ref, rope=(qr_ref, kr_ref), **kw)


def _dq_kernel_mla(offs_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                   delta_ref, dlse_ref, qr_ref, kr_ref, dq_ref, dqr_ref,
                   dq_acc, dqr_acc, **kw):
    _dq_kernel(offs_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
               dlse_ref, dq_ref, dq_acc,
               rope=(qr_ref, kr_ref, dqr_ref, dqr_acc), **kw)


def _dkv_kernel_mla(offs_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                    delta_ref, dlse_ref, qr_ref, kr_ref, dk_ref, dv_ref,
                    dkr_ref, dk_acc, dv_acc, dkr_acc, **kw):
    _dkv_kernel(offs_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dlse_ref, dk_ref, dv_ref, dk_acc, dv_acc,
                rope=(qr_ref, kr_ref, dkr_ref, dkr_acc), **kw)


def _bwd_kernel(offs_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dlse_ref, *refs, packed: bool, latent: bool, **kw):
    """The one kernel of the backward pass: ``_dkv_kernel`` carrying dQ,
    whatever the form - the packed ids and the rotary operands come after
    the row operands as in the adapters above, dQ's outputs after dK/dV's
    and its accumulators after theirs."""
    refs = iter(refs)

    def take(n):
        return tuple(next(refs) for _ in range(n))

    seg = take(2) if packed else None
    qr_kr = take(2) if latent else None
    n = 3 if latent else 2  # dk, dv (, dk_rope); dq (, dq_rope) is one fewer
    outs, dq_outs, accs, dq_accs = take(n), take(n - 1), take(n), take(n - 1)
    _dkv_kernel(
        offs_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dlse_ref,
        outs[0], outs[1], accs[0], accs[1], seg=seg,
        rope=qr_kr + (outs[2], accs[2]) if latent else None,
        dq=(dq_outs, dq_accs), **kw)


def _prep_rope(rope, Tq, Tk):
    """The rotary operands in the kernels' layout, padded as ``_prep``
    padded q and k: (B, H, Tq, R) and the shared key's (B, 1, Tk, R)."""
    qr, kr = (jnp.moveaxis(x, 2, 1) for x in rope)
    if Tq != qr.shape[2]:
        qr = jnp.pad(qr, ((0, 0), (0, 0), (0, Tq - qr.shape[2]), (0, 0)))
    if Tk != kr.shape[2]:
        kr = jnp.pad(kr, ((0, 0), (0, 0), (0, Tk - kr.shape[2]), (0, 0)))
    return qr, kr


def _prep(q, k, v, block_q, block_k):
    """Common layout work: (B,T,H,D)→(B,H,T,D), tile-aligned blocks, pads."""
    B, Tq0, H, D = q.shape
    Tk0 = k.shape[1]
    tile = {4: 8, 2: 16, 1: 32}.get(jnp.dtype(q.dtype).itemsize, 8)
    align = lambda n: -(-n // tile) * tile  # noqa: E731
    block_q = min(block_q, align(max(Tq0, 1)))
    block_k = min(block_k, align(max(Tk0, 1)))
    pad_q = (-Tq0) % block_q
    pad_k = (-Tk0) % block_k
    qt = jnp.moveaxis(q, 2, 1)
    kt = jnp.moveaxis(k, 2, 1)
    vt = jnp.moveaxis(v, 2, 1)
    if pad_q:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    return qt, kt, vt, block_q, block_k


def _precision_for(dtype):
    # f32 inputs get 6-pass MXU precision (err ~1e-6 vs the single-pass
    # bf16 default's ~5e-3 — enough to perturb small-key-count softmax
    # rows); bf16 inputs keep the fast default, as everywhere else.
    return (
        jax.lax.Precision.HIGHEST
        if dtype == jnp.float32
        else jax.lax.Precision.DEFAULT
    )


def _offsets_arr(q_offset, k_offset):
    return jnp.stack(
        [jnp.asarray(q_offset, jnp.int32), jnp.asarray(k_offset, jnp.int32)]
    )


def _prep_seg(seg, T_padded):
    """(B, T) segment ids → (B, 1, T_padded, 1) int32 for block mapping.
    Pad rows get -1; padded keys are independently masked by ``kv_len``
    and padded query rows are sliced off the output."""
    B, T = seg.shape
    s = jnp.asarray(seg, jnp.int32)
    if T_padded != T:
        s = jnp.pad(s, ((0, 0), (0, T_padded - T)), constant_values=-1)
    return s[:, None, :, None]


def _seg_specs(block_q, block_k, transposed: bool = False):
    """Block specs for the (B, 1, T, 1) segment-id arrays (no head axis).

    ``transposed``: the dK/dV grid is (b, h, KV block, Q block), so the
    Q-block index is grid axis 3 and the KV-block index axis 2."""
    if transposed:
        sq = pl.BlockSpec(
            (1, 1, block_q, 1), lambda b, h, j, i, *_refs: (b, 0, i, 0)
        )
        sk = pl.BlockSpec(
            (1, 1, block_k, 1), lambda b, h, j, i, *_refs: (b, 0, j, 0)
        )
        return sq, sk
    sq = pl.BlockSpec(
        (1, 1, block_q, 1), lambda b, h, i, j, *_refs: (b, 0, i, 0)
    )
    sk = pl.BlockSpec(
        (1, 1, block_k, 1), lambda b, h, i, j, *_refs: (b, 0, j, 0)
    )
    return sq, sk


def _mla_scale(q, rope, score_scale: float = 1.0) -> float:
    """``1/sqrt`` of the score's whole width - the heads' own plus the
    rotary one - times the caller's ``score_scale`` (YaRN's ``mscale^2``;
    1 leaves the float what it was)."""
    return score_scale / ((q.shape[-1] + rope[0].shape[-1]) ** 0.5)


#: Scoped VMEM the latent kernels may use.  Their two rotary blocks, the
#: rotary gradient's block and its accumulator come on top of what the
#: one-product kernels hold, and at 1024 x 1024 blocks that passes
#: Mosaic's default 16 MiB by 1.3 (dq; AOT for a described v5e, PR 32).
_MLA_VMEM_LIMIT = 32 * 1024 * 1024


#: Scoped VMEM the one-kernel backward may use (a v5e's VMEM is 128 MiB):
#: dQ's float32 rows of a whole (batch, head) come on top of what the dK/dV
#: kernel holds - 4 MiB at 8,192 x 128, as much again for the latent form's
#: 64-wide rotary rows (lane-padded), 8 MiB at 16,384 x 128.
_BWD_VMEM_LIMIT = 64 * 1024 * 1024
#: The most of it those rows may take: half, the kernel's blocks and its
#: float32 intermediates (16 MiB of them at 1024 x 1024) keep the rest.  A
#: longer row's backward stays the two kernels.
_BWD_ROW_BYTES = _BWD_VMEM_LIMIT // 2


def _dq_row_bytes(Tq: int, *widths: int) -> int:
    """VMEM bytes of dQ's float32 accumulators for one (batch, head)'s
    whole row, a width (D, and the rotary R) padded to the lanes each."""
    return sum(Tq * -(-w // _LANES) * _LANES * 4 for w in widths)


def _mla_call_args(rope, fused: bool = False) -> dict:
    """What a latent kernel's ``pallas_call`` takes besides the others'
    arguments, or the one-kernel backward's (``fused``); nothing without
    the rotary operands."""
    if fused:
        limit = _BWD_VMEM_LIMIT
    elif rope is not None:
        limit = _MLA_VMEM_LIMIT
    else:
        return {}
    return {"compiler_params": pltpu.CompilerParams(vmem_limit_bytes=limit)}


def _rope_specs(rope, block_q, block_k, k_block):
    """Block specs of (q_rope (B, H, Tq, R), k_rope (B, 1, Tk, R)) on the
    forward / dq grid: every head reads the one shared key."""
    R = rope[0].shape[-1]
    return [
        pl.BlockSpec((1, 1, block_q, R),
                     lambda b, h, i, j, *_refs: (b, h, i, 0)),
        pl.BlockSpec((1, 1, block_k, R),
                     lambda b, h, i, j, *_refs: (b, 0, k_block(i, j), 0)),
    ]


def _fwd_impl(q, k, v, offsets, causal, kv_repeat, block_q, block_k,
              interpret, seg_q=None, seg_k=None, window=None, rope=None,
              score_scale=1.0):
    assert q.shape[2] == k.shape[2] * kv_repeat, (q.shape, k.shape, kv_repeat)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    B, T, H, D = q.shape
    Tkv = k.shape[1]
    qt, kt, vt, block_q, block_k = _prep(q, k, v, block_q, block_k)
    Tq, Tk = qt.shape[2], kt.shape[2]
    precision = _precision_for(q.dtype)
    packed = seg_q is not None
    common = dict(
        scale=1.0 / (D**0.5), causal=causal, block_q=block_q,
        block_k=block_k, kv_len=Tkv, precision=precision, window=window,
    )
    inner, k_block = Tk // block_k, lambda i, j: j
    if window is not None:
        band = common["band"] = band_grid(T, window, block_q, block_k)
        inner, k_block = band.nk, _k_block_of(block_q, block_k, band)
    kernel, name = _fwd_kernel_seg if packed else _fwd_kernel, "ddl_flash_fwd"
    if window is not None:
        name = "ddl_flash_swa_fwd"
    if rope is not None:
        kernel, name = _fwd_kernel_mla, "ddl_flash_mla_fwd"
        common["scale"] = _mla_scale(q, rope, score_scale)
    kernel = functools.partial(kernel, **common)
    kv_spec = pl.BlockSpec(
        (1, 1, block_k, D),
        lambda b, h, i, j, *_refs, rep=kv_repeat: (
            b, h // rep, k_block(i, j), 0),
    )
    q_spec = pl.BlockSpec(
        (1, 1, block_q, D), lambda b, h, i, j, *_refs: (b, h, i, 0)
    )
    row_spec = pl.BlockSpec(
        (1, 1, block_q, 1), lambda b, h, i, j, *_refs: (b, h, i, 0)
    )
    in_specs = [q_spec, kv_spec, kv_spec]
    inputs = [qt, kt, vt]
    if packed:
        sq_spec, sk_spec = _seg_specs(block_q, block_k)
        in_specs += [sq_spec, sk_spec]
        inputs += [_prep_seg(seg_q, Tq), _prep_seg(seg_k, Tk)]
    if rope is not None:
        in_specs += _rope_specs(rope, block_q, block_k, k_block)
        inputs += _prep_rope(rope, Tq, Tk)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, H, Tq // block_q, inner),
        in_specs=in_specs,
        out_specs=[q_spec, row_spec],
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),  # running max m
            pltpu.VMEM((block_q, _LANES), jnp.float32),  # running denom l
            pltpu.VMEM((block_q, D), jnp.float32),  # output accumulator
        ],
    )
    out, lse = named_pallas_call(
        name, kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Tq, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, Tq, 1), jnp.float32),
        ],
        interpret=interpret, **_mla_call_args(rope),
    )(offsets, *inputs)
    o = out[:, :, :T] if Tq != T else out
    return jnp.moveaxis(o, 1, 2), lse[:, :, :T, 0], (interpret, block_q, block_k)


def _tag(x):
    """``x`` under the name ``remat="selective"`` saves (lazily: the
    models import this module)."""
    from ddl_tpu.models.remat import tag_attn_out

    return tag_attn_out(x)


def _saved(out, lse):
    """The two values of a forward call that its backward reads, under the
    name ``remat="selective"`` saves: the output as the caller gets it
    (B, T, H, D) and the compact logsumexp (B, H, T) float32 — 4 bytes a
    (row, head), where the kernels' own (B, H, Tq, 1) operand is padded
    to 128 lanes in HBM and the rows to the block.  With both kept, a
    rematerialised backward has nothing left to run the forward kernel
    for."""
    return _tag(out), _tag(lse)


def _row_operand(x, Tq):
    """(B, H, T) float32 -> the kernels' (B, H, Tq, 1) row operand."""
    pad = Tq - x.shape[2]
    if pad:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, pad)))
    return x[..., None]


def _bwd_impl(causal, kv_repeat, _block_q, _block_k, _interpret, res, cts,
              window=None, rope=None, score_scale=1.0):
    do, dlse = cts
    # Resolved block sizes / interpret flag ride in the residuals so both
    # passes use identical values (the nondiff args are pre-resolution).
    (q, k, v, offsets, out, lse, interpret, block_q, block_k,
     seg_q, seg_k) = res
    B, T, H, D = q.shape
    Tkv, Hkv = k.shape[1], k.shape[2]
    qt, kt, vt, block_q, block_k = _prep(q, k, v, block_q, block_k)
    Tq, Tk = qt.shape[2], kt.shape[2]
    precision = _precision_for(q.dtype)
    packed = seg_q is not None

    dot = jnp.moveaxis(do, 2, 1)
    if Tq != T:
        dot = jnp.pad(dot, ((0, 0), (0, 0), (0, Tq - T), (0, 0)))
    # delta_i = rowsum(dO_i * O_i), the softmax-jacobian diagonal term,
    # taken in the caller's layout (the saved output's): only the
    # (B, T, H) sums are transposed.
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    delta = _row_operand(jnp.moveaxis(delta, 2, 1), Tq)
    lse = _row_operand(lse, Tq)  # pad rows: their p is masked to 0
    # lse cotangent from the caller (zero for plain flash_attention; the
    # ring combine's weights make it nonzero there).
    dl = _row_operand(dlse.astype(jnp.float32), Tq)

    common = dict(
        scale=1.0 / (D**0.5), causal=causal, block_q=block_q,
        block_k=block_k, seq_len=T, kv_len=Tkv, precision=precision,
        window=window,
    )
    # Inner grid sizes and the blocks their steps fetch: the whole row, or
    # with a window the band's blocks only (``band_grid``).
    inner_k, k_block = Tk // block_k, lambda i, j: j
    inner_q, q_block = Tq // block_q, lambda j, i: i
    if window is not None:
        band = common["band"] = band_grid(T, window, block_q, block_k)
        inner_k, k_block = band.nk, _k_block_of(block_q, block_k, band)
        inner_q, q_block = band.nq, _q_block_of(block_q, block_k, band)
    dq_kernel = _dq_kernel_seg if packed else _dq_kernel
    dkv_kernel = _dkv_kernel_seg if packed else _dkv_kernel
    names = ("ddl_flash_bwd_dq", "ddl_flash_bwd_dkv")
    if window is not None:
        names = ("ddl_flash_swa_bwd_dq", "ddl_flash_swa_bwd_dkv")
    if rope is not None:
        dq_kernel, dkv_kernel = _dq_kernel_mla, _dkv_kernel_mla
        names = ("ddl_flash_mla_bwd_dq", "ddl_flash_mla_bwd_dkv")
        common["scale"] = _mla_scale(q, rope, score_scale)
        R = rope[0].shape[-1]
        rope_t = _prep_rope(rope, Tq, Tk)
    q_spec = pl.BlockSpec(
        (1, 1, block_q, D), lambda b, h, i, j, *_refs: (b, h, i, 0)
    )
    kv_spec = pl.BlockSpec(
        (1, 1, block_k, D),
        lambda b, h, i, j, *_refs, rep=kv_repeat: (
            b, h // rep, k_block(i, j), 0),
    )
    row_spec = pl.BlockSpec(
        (1, 1, block_q, 1), lambda b, h, i, j, *_refs: (b, h, i, 0)
    )
    # One kernel where dQ's float32 rows of a (batch, head) fit the VMEM
    # it may take, the two kernels past that: read off the shapes alone.
    dq_widths = (D,) if rope is None else (D, R)
    fused = _dq_row_bytes(Tq, *dq_widths) <= _BWD_ROW_BYTES
    if not fused:
        dq_in_specs = [q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec,
                       row_spec]
        dq_inputs = [qt, kt, vt, dot, lse, delta, dl]
        if packed:
            sq_spec, sk_spec = _seg_specs(block_q, block_k)
            dq_in_specs += [sq_spec, sk_spec]
            dq_inputs += [_prep_seg(seg_q, Tq), _prep_seg(seg_k, Tk)]
        dq_out_specs = q_spec
        dq_scratch = [pltpu.VMEM((block_q, D), jnp.float32)]
        dq_shape = jax.ShapeDtypeStruct((B, H, Tq, D), q.dtype)
        if rope is not None:
            qr_spec, kr_spec = _rope_specs(rope, block_q, block_k, k_block)
            dq_in_specs += [qr_spec, kr_spec]
            dq_inputs += rope_t
            dq_out_specs = [q_spec, qr_spec]
            dq_scratch.append(pltpu.VMEM((block_q, R), jnp.float32))
            dq_shape = [dq_shape,
                        jax.ShapeDtypeStruct((B, H, Tq, R), q.dtype)]
        dq = named_pallas_call(
            names[0], functools.partial(dq_kernel, **common),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(B, H, Tq // block_q, inner_k),
                in_specs=dq_in_specs,
                out_specs=dq_out_specs,
                scratch_shapes=dq_scratch,
            ),
            out_shape=dq_shape,
            interpret=interpret, **_mla_call_args(rope),
        )(offsets, *dq_inputs)

    # dK/dV: grid transposed so the Q axis is innermost (sequential).
    q_spec_t = pl.BlockSpec(
        (1, 1, block_q, D),
        lambda b, h, j, i, *_refs: (b, h, q_block(j, i), 0),
    )
    kv_spec_t = pl.BlockSpec(
        (1, 1, block_k, D),
        lambda b, h, j, i, *_refs, rep=kv_repeat: (b, h // rep, j, 0),
    )
    row_spec_t = pl.BlockSpec(
        (1, 1, block_q, 1),
        lambda b, h, j, i, *_refs: (b, h, q_block(j, i), 0),
    )
    out_kv_t = pl.BlockSpec(
        (1, 1, block_k, D), lambda b, h, j, i, *_refs: (b, h, j, 0)
    )
    dkv_in_specs = [q_spec_t, kv_spec_t, kv_spec_t, q_spec_t, row_spec_t,
                    row_spec_t, row_spec_t]
    dkv_inputs = [qt, kt, vt, dot, lse, delta, dl]
    if packed:
        sq_spec_t, sk_spec_t = _seg_specs(block_q, block_k, transposed=True)
        dkv_in_specs += [sq_spec_t, sk_spec_t]
        dkv_inputs += [_prep_seg(seg_q, Tq), _prep_seg(seg_k, Tk)]
    dkv_out_specs = [out_kv_t, out_kv_t]
    dkv_scratch = [
        pltpu.VMEM((block_k, D), jnp.float32),
        pltpu.VMEM((block_k, D), jnp.float32),
    ]
    dkv_shapes = [
        jax.ShapeDtypeStruct((B, H, Tk, D), k.dtype),
        jax.ShapeDtypeStruct((B, H, Tk, D), v.dtype),
    ]
    if rope is not None:
        dkv_in_specs += [
            pl.BlockSpec((1, 1, block_q, R),
                         lambda b, h, j, i, *_refs: (b, h, q_block(j, i), 0)),
            pl.BlockSpec((1, 1, block_k, R),
                         lambda b, h, j, i, *_refs: (b, 0, j, 0)),
        ]
        dkv_inputs += rope_t
        # Every head's part of the shared key's gradient, float32: the sum
        # over the heads is taken outside, once, before it is rounded.
        dkv_out_specs.append(pl.BlockSpec(
            (1, 1, block_k, R), lambda b, h, j, i, *_refs: (b, h, j, 0)))
        dkv_scratch.append(pltpu.VMEM((block_k, R), jnp.float32))
        dkv_shapes.append(jax.ShapeDtypeStruct((B, H, Tk, R), jnp.float32))
    if fused:
        # dQ rides the dK/dV grid: float32 accumulators for the (batch,
        # head)'s whole row, and an output block that stands at the Q block
        # being finished (``_dq_block_of``).
        nqb, nkb = Tq // block_q, Tk // block_k
        dq_block = _dq_block_of(
            nqb, nkb, block_q, block_k, common.get("band"), q_block)
        for width in dq_widths:
            dkv_out_specs.append(pl.BlockSpec(
                (1, 1, block_q, width),
                lambda b, h, j, i, *_refs: (b, h, dq_block(j, i), 0)))
            dkv_shapes.append(jax.ShapeDtypeStruct((B, H, Tq, width), q.dtype))
            dkv_scratch.append(pltpu.VMEM((nqb, block_q, width), jnp.float32))
        dkv_kernel = functools.partial(
            _bwd_kernel, packed=packed, latent=rope is not None)
    dk, dv, *more = named_pallas_call(
        names[1], functools.partial(dkv_kernel, **common),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, H, Tk // block_k, inner_q),
            in_specs=dkv_in_specs,
            out_specs=dkv_out_specs,
            scratch_shapes=dkv_scratch,
        ),
        out_shape=dkv_shapes,
        interpret=interpret, **_mla_call_args(rope, fused),
    )(offsets, *dkv_inputs)
    if rope is not None:
        dkr, *more = more
    if fused:
        dq = more if rope is not None else more[0]

    if rope is not None:
        dq, dqr = dq
        dqr = jnp.moveaxis(dqr[:, :, :T], 1, 2)
        dkr = jnp.sum(dkr[:, :, :Tkv], axis=1, keepdims=True)
        dkr = jnp.moveaxis(dkr, 1, 2).astype(rope[1].dtype)
    if Tq != T:
        dq = dq[:, :, :T]
    if Tk != Tkv:
        dk = dk[:, :, :Tkv]
        dv = dv[:, :, :Tkv]
    dq = jnp.moveaxis(dq, 1, 2)
    # Per-Q-head dK/dV collapse onto the compact KV heads (GQA group sum).
    if kv_repeat > 1:
        dk = dk.reshape(B, Hkv, kv_repeat, Tkv, D).sum(axis=2)
        dv = dv.reshape(B, Hkv, kv_repeat, Tkv, D).sum(axis=2)
    dk = jnp.moveaxis(dk, 1, 2)
    dv = jnp.moveaxis(dv, 1, 2)
    d_offsets = np.zeros((2,), jax.dtypes.float0)  # int arg: zero cotangent
    if rope is not None:  # in the latent core's argument order
        return dq, dk.astype(k.dtype), dv.astype(v.dtype), dqr, dkr, d_offsets
    return dq, dk.astype(k.dtype), dv.astype(v.dtype), d_offsets


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash_core(q, k, v, offsets, causal, kv_repeat, block_q, block_k,
                interpret):
    out, lse, _ = _fwd_impl(
        q, k, v, offsets, causal, kv_repeat, block_q, block_k, interpret
    )
    return out, lse


def _vjp_fwd(q, k, v, offsets, causal, kv_repeat, block_q, block_k,
             interpret):
    out, lse, resolved = _fwd_impl(
        q, k, v, offsets, causal, kv_repeat, block_q, block_k, interpret
    )
    out, lse = _saved(out, lse)
    return (out, lse), (q, k, v, offsets, out, lse, *resolved, None, None)


_flash_core.defvjp(_vjp_fwd, _bwd_impl)


# Packed-segment core: identical math plus the segment mask.  A separate
# custom_vjp keeps the unpacked core's signature (and its validated
# behavior) untouched; segment ids are integer inputs with float0
# cotangents, like ``offsets``.
@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10))
def _flash_core_seg(q, k, v, offsets, seg_q, seg_k, causal, kv_repeat,
                    block_q, block_k, interpret):
    out, lse, _ = _fwd_impl(
        q, k, v, offsets, causal, kv_repeat, block_q, block_k, interpret,
        seg_q=seg_q, seg_k=seg_k,
    )
    return out, lse


def _vjp_fwd_seg(q, k, v, offsets, seg_q, seg_k, causal, kv_repeat,
                 block_q, block_k, interpret):
    out, lse, resolved = _fwd_impl(
        q, k, v, offsets, causal, kv_repeat, block_q, block_k, interpret,
        seg_q=seg_q, seg_k=seg_k,
    )
    out, lse = _saved(out, lse)
    return (out, lse), (q, k, v, offsets, out, lse, *resolved, seg_q, seg_k)


def _bwd_impl_seg(causal, kv_repeat, block_q, block_k, interpret, res, cts):
    dq, dk, dv, d_offsets = _bwd_impl(
        causal, kv_repeat, block_q, block_k, interpret, res, cts
    )
    seg_q, seg_k = res[-2], res[-1]
    d_seg_q = np.zeros(seg_q.shape, jax.dtypes.float0)
    d_seg_k = np.zeros(seg_k.shape, jax.dtypes.float0)
    return dq, dk, dv, d_offsets, d_seg_q, d_seg_k


_flash_core_seg.defvjp(_vjp_fwd_seg, _bwd_impl_seg)


# Sliding-window core: the same kernels with the band's lower edge
# (``window`` static: key j is visible to query i iff 0 <= i - j < window)
# on grids whose inner axis runs over the band's blocks only
# (``band_grid``; offsets are (0, 0) here, so the band's extent is static).
# A custom_vjp of its own, like the packed one, so that the causal-full
# core's signature, jaxpr and lowered program stay what they were.
@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash_core_win(q, k, v, offsets, kv_repeat, block_q, block_k,
                    interpret, window):
    out, lse, _ = _fwd_impl(
        q, k, v, offsets, True, kv_repeat, block_q, block_k, interpret,
        window=window,
    )
    return out, lse


def _vjp_fwd_win(q, k, v, offsets, kv_repeat, block_q, block_k, interpret,
                 window):
    out, lse, resolved = _fwd_impl(
        q, k, v, offsets, True, kv_repeat, block_q, block_k, interpret,
        window=window,
    )
    out, lse = _saved(out, lse)
    return (out, lse), (q, k, v, offsets, out, lse, *resolved, None, None)


def _bwd_impl_win(kv_repeat, block_q, block_k, interpret, window, res, cts):
    return _bwd_impl(
        True, kv_repeat, block_q, block_k, interpret, res, cts, window=window
    )


_flash_core_win.defvjp(_vjp_fwd_win, _bwd_impl_win)


# Latent-attention core (causal, offsets (0, 0), one key/value head a query
# head): the same kernels with the rotary product in the score, under the
# names ``ddl_flash_mla_*``.  A custom_vjp of its own for the reason the
# windowed core has one.
@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9))
def _flash_core_mla(q, k, v, q_rope, k_rope, offsets, block_q, block_k,
                    interpret, score_scale):
    out, lse, _ = _fwd_impl(
        q, k, v, offsets, True, 1, block_q, block_k, interpret,
        rope=(q_rope, k_rope), score_scale=score_scale,
    )
    return out, lse


def _vjp_fwd_mla(q, k, v, q_rope, k_rope, offsets, block_q, block_k,
                 interpret, score_scale):
    out, lse, resolved = _fwd_impl(
        q, k, v, offsets, True, 1, block_q, block_k, interpret,
        rope=(q_rope, k_rope), score_scale=score_scale,
    )
    out, lse = _saved(out, lse)
    return (out, lse), (
        (q, k, v, offsets, out, lse, *resolved, None, None),
        (q_rope, k_rope),
    )


def _bwd_impl_mla(block_q, block_k, interpret, score_scale, res, cts):
    res, rope = res
    return _bwd_impl(True, 1, block_q, block_k, interpret, res, cts, rope=rope,
                     score_scale=score_scale)


_flash_core_mla.defvjp(_vjp_fwd_mla, _bwd_impl_mla)


_FINE_BAND = 2048  # widest window whose band runs in 512 x 512 blocks


def _default_blocks(T: int, block_q, block_k, window=None):
    """v5e-tuned defaults, sequence-length adaptive (measured fwd+bwd at
    B=4, H=16, D=128: bq=512 wins at T<=2k, bq=1024 wins at 4k/8k by
    ~10%).  Both directions compile within v5e's VMEM budget — the
    backward reuses the forward's resolved blocks.  On smaller-VMEM
    generations pass smaller blocks explicitly if Mosaic reports VMEM
    exhaustion.

    With a ``window`` (narrower than the row) of at most 2048 both
    blocks are 512: a band executes whole blocks, so at window 2048 the
    1024-blocks' 3 key blocks a query block hold 67% useful pairs and the
    512-blocks' 5 hold 80%, which outweighs what a smaller block loses.
    Measured on the banded grids at 2 x 8192 x 32/4 heads x 128, bf16, ms
    a sliding layer under selective remat (fwd + dq + dkv, each once
    since the forward's residuals are saved; per-kernel readings of
    ``tools/probe_flash_band.py``, PERF.md §6, PR 31, summed anew by
    PR 33 — no order changed from the sums with the forward twice):
    window 2048 — 1024 x 1024 23.71, **512 x 512 22.27**, 512 x 1024
    24.64, 1024 x 512 26.27, 256 x 512 27.53, 256 x 1024 29.18, 512 x 256
    33.96; window 1024 — 18.37, **15.25**, 256 x 256 26.14; window 4096 —
    **32.16** against 33.38, so wider windows keep the row's defaults."""
    fine = window is not None and window <= _FINE_BAND
    if block_q is None:
        block_q = 512 if T <= 2048 or fine else 1024
    if block_k is None:
        block_k = 512 if fine else 1024
    return block_q, block_k


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    kv_repeat: int = 1,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
    segment_ids: Optional[jax.Array] = None,
    window: Optional[int] = None,
    q_rope: Optional[jax.Array] = None,
    k_rope: Optional[jax.Array] = None,
    score_scale: float = 1.0,
) -> jax.Array:
    """Flash attention over (B, T, H, D) queries.

    k/v are compact GQA tensors of shape (B, T, H // kv_repeat, D).  Output
    matches ``parallel.ring_attention.attention_reference`` up to fp
    accumulation order; fully differentiable (flash backward kernels).
    Off-TPU the kernels run in Pallas interpret mode.  Default blocks are
    length-adaptive (see ``_default_blocks``).

    ``segment_ids`` (B, T) int32, values >= 0: packed-sequence masking —
    tokens attend only within their own segment (causality still applies
    on top).  The standard layout for LM pretraining feeds that pack
    multiple documents into one row.  Packed blocks always take the
    masked path, so packing trades the interior-block fast path for the
    mask; unpacked calls are entirely unaffected.

    ``window`` (static int, causal only, k/v of q's length): sliding-window
    attention — key ``j`` is visible to query ``i`` iff
    ``0 <= i - j < window``.  The kernels' inner grid axis runs over the
    most blocks the band covers, not over the row (``band_grid``: 5 key
    blocks a query block where the row has 16, at T = 8192, window 2048
    and the windowed default of 512 x 512), so the blocks outside it cost
    neither a grid step nor a DMA.  A step that falls before the row's
    head or past its tail skips its matmuls and refetches nothing, one
    just below a narrower stretch of the band skips its matmuls as blocks
    above the diagonal do, blocks wholly inside the band keep the unmasked
    fast path, and the kernels are named ``ddl_flash_swa_*`` on the
    trace.  A window that covers the sequence is plain causal attention
    and runs as such; ``None`` leaves every program exactly what it was
    before windows existed.

    ``q_rope`` (B, T, H, R) with ``k_rope`` (B, T, 1, R): latent attention
    (causal self-attention, one k/v head a query head) — the score is
    ``(q . k + q_rope . k_rope) / sqrt(D + R)`` with ONE rotary key a
    position for all heads, which the kernels read through their index
    map, so its H-fold broadcast is never written; v and the output keep
    width D.  The kernels are named ``ddl_flash_mla_*`` on the trace.
    Absent, every program is what it was before.  ``score_scale`` (a static
    float, the latent form only) multiplies that ``1/sqrt(D + R)`` inside
    the kernels' one scale - YaRN's ``mscale^2``, so that q is not rescaled
    in bfloat16; 1 is the same float, and program, as without it.
    """
    if (q_rope is None) != (k_rope is None):
        raise ValueError("q_rope and k_rope come together")
    if q_rope is not None:
        B, T, H, _ = q.shape
        R = q_rope.shape[-1]
        if (q_rope.shape != (B, T, H, R) or k_rope.shape != (B, T, 1, R)
                or k.shape != q.shape or v.shape != q.shape):
            raise ValueError(
                f"latent attention takes q, k, v {q.shape}, q_rope "
                f"(B, T, H, R) and the shared k_rope (B, T, 1, R); got "
                f"{k.shape}, {v.shape}, {q_rope.shape}, {k_rope.shape}"
            )
        if (not causal or kv_repeat != 1 or window is not None
                or segment_ids is not None):
            raise NotImplementedError(
                "flash_attention: the latent form (q_rope/k_rope) is causal "
                "self-attention with a key/value head a query head; grouped "
                "heads, a sliding window and packed rows have no kernel"
            )
        block_q, block_k = _default_blocks(T, block_q, block_k)
        out, _ = _flash_core_mla(
            q, k, v, q_rope, k_rope, _offsets_arr(0, 0), block_q, block_k,
            interpret, float(score_scale),
        )
        return out
    if window is not None:
        if not causal or window < 1:
            raise ValueError(
                f"window={window!r} needs causal attention and window >= 1"
            )
        if k.shape[1] != q.shape[1]:
            raise ValueError(
                f"window={window!r} is self-attention: {k.shape[1]} keys "
                f"for {q.shape[1]} queries"
            )
        if window >= q.shape[1]:
            window = None  # the band holds every causal pair
    block_q, block_k = _default_blocks(q.shape[1], block_q, block_k, window)
    if flash_tile.fits(q, k, v, kv_repeat, block_q, block_k, segment_ids,
                       window=window):
        # The one-block kernels keep no named residuals: under a policy
        # their output is saved and their forward re-run.
        return _tag(flash_tile.tile_attention(q, k, v, causal, interpret))
    if window is not None:
        if segment_ids is not None:
            raise NotImplementedError(
                "flash_attention: packed rows (segment_ids) inside a "
                "sliding window have no kernel"
            )
        out, _ = _flash_core_win(
            q, k, v, _offsets_arr(0, 0), kv_repeat, block_q, block_k,
            interpret, window,
        )
        return out
    if segment_ids is not None:
        out, _ = _flash_core_seg(
            q, k, v, _offsets_arr(0, 0), segment_ids, segment_ids, causal,
            kv_repeat, block_q, block_k, interpret,
        )
        return out
    out, _ = _flash_core(
        q, k, v, _offsets_arr(0, 0), causal, kv_repeat, block_q, block_k,
        interpret,
    )
    return out


def flash_attention_with_lse(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    q_offset=0,
    k_offset=0,
    causal: bool = True,
    kv_repeat: int = 1,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
    segment_ids: Optional[jax.Array] = None,
    kv_segment_ids: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Flash attention returning (out, logsumexp (B, H, T) fp32).

    ``q_offset`` / ``k_offset`` are GLOBAL token offsets (static or traced
    ints) added to the local positions for causal masking — ring attention
    passes its shard offsets here so each ring step masks against global
    positions.  Rows with every key masked return out == 0 and
    lse == -1e30; combine partial results with
    ``lse = logaddexp(lse_a, lse_b)`` and
    ``out = out_a·exp(lse_a-lse) + out_b·exp(lse_b-lse)``.

    ``segment_ids`` (B, Tq) / ``kv_segment_ids`` (B, Tk; defaults to
    ``segment_ids``): packed-sequence masking — ring attention passes its
    local query ids and the CURRENT rotating key-block ids.
    """
    block_q, block_k = _default_blocks(q.shape[1], block_q, block_k)
    if kv_segment_ids is not None and segment_ids is None:
        # Key-only ids have no sound default for the queries (mirroring
        # them silently mis-segments unpacked queries).
        raise ValueError(
            "kv_segment_ids requires segment_ids (the query-side ids)"
        )
    if segment_ids is not None:
        seg_k = kv_segment_ids if kv_segment_ids is not None else segment_ids
        return _flash_core_seg(
            q, k, v, _offsets_arr(q_offset, k_offset), segment_ids, seg_k,
            causal, kv_repeat, block_q, block_k, interpret,
        )
    return _flash_core(
        q, k, v, _offsets_arr(q_offset, k_offset), causal, kv_repeat,
        block_q, block_k, interpret,
    )
