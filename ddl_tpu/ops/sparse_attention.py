"""Block-sparse causal attention over a per-query SELECTION of key blocks
(InfLLM-V2, arXiv:2509.24663; MiniCPM4, arXiv:2506.07900): the selection
stage and flash kernels whose key blocks come from data.

A key-value head ``g`` serves ``R`` query heads (a group).  Keys are cut
into blocks of ``block`` positions; a query at position ``t`` of group
``g`` sees ``visible(t)``: the first ``init_blocks`` blocks, the
``local_blocks`` blocks up to its own, and the ``topk - init_blocks`` past
blocks outside both that score highest - one selection a GROUP, shared by
its ``R`` heads - and inside a visible block the keys ``j <= t``.

**Selection** (:func:`select_blocks`; no gradient: a ``stop_gradient`` on
its operands).  Compressed keys ``Kc_m = mean k[m stride : m stride +
kernel)``; per head ``p_h = softmax_m(q_h . Kc_m / sqrt(d))`` over the
``m`` wholly in the past; ``P = sum_{h in g} p_h``; a block's score is the
max of ``P`` over the ``m`` whose span meets it.  ``ddl_sparse_select`` is
one kernel from q and the compressed keys to the BLOCK scores (B, G, T,
T / block), float32: the per-head scores (T x T / stride x heads: 2.1 GiB
at 16,384 x 1,023 x 32) and their sum over a group never leave VMEM.  The
compressed keys reach it in PLANES - plane ``r`` holds ``Kc_{n b + r}`` at
column ``b`` (``n = block / stride``), and further planes the ``m = n b -
1, ...`` that reach into block ``b`` from before it - so the max over a
block's ``m`` is a max over lane-aligned slices, not a strided pool (the
repeated planes are left out of the softmax's sum).  ``lax.top_k`` (ties
to the lower block) and the lists are XLA's, under the caller's scope.

**Lists.**  What the kernels read is :class:`Selection`: the sizes it was
made with; ``visible`` (B, G, T, blocks), 0 / 1 in the operands' dtype; for
every tile of ``tile_q`` neighbouring positions the blocks ANY of them
sees, ascending, two to an int32 (``lists``, ``counts``); and transposed,
for every tile of ``tile_k`` keys the query tiles that see any of its
blocks (``lists_t``, ``counts_t`` - the inversion ``models/moe.py`` makes
for its row moves).  The arrays are tagged with the name
``remat="selective"`` saves.  The tiles are :func:`_tiles`': 128 positions,
more where the lists would not fit scalar memory.

**Kernels.**  A query tile is a group's ``R`` heads by ``tile_q``
positions: ``R tile_q`` rows on the MXU (2,048 at 16 x 128).

- ``ddl_flash_sparse_fwd`` / ``ddl_flash_sparse_bwd_dq``: grid (B, G,
  query tiles, list steps); a step takes TWO listed blocks (two block
  specs on k and on v whose index maps read the scalar-prefetched list:
  128 keys a step at blocks of 64, whole lanes and a whole MXU tile), so
  a block outside the tile's list costs no DMA, and steps behind the
  list's end repeat its last entry (no DMA) and skip their body.  A
  position of the tile that did not choose a listed block is masked: the
  step's (tile_q, keys) mask is ``visible[tile] @ E`` with ``E`` the 0 / 1
  matrix that picks the step's two block columns and spreads each over
  its keys - one small matmul, no gather - and ``j <= t`` inside.
- ``ddl_flash_sparse_bwd_dkv``: grid (B, G, key tiles, listed query
  tiles), ``dk`` and ``dv`` summed over the group's heads in the kernel
  (the contraction runs over all ``R tile_q`` rows), the same mask from
  ``visible``: exactly ``visible(t)``.

Merged lists of neighbouring positions are what fills the MXU; how much
of the causal triangle they cover is the data's: with near-uniform scores
(random weights) a block is chosen by ~29% of the later queries and a
tile's merged list approaches the whole triangle; with trained weights
neighbours choose alike.

Off the TPU the kernels run in Pallas' interpret mode;
:func:`attention_dense` is the same function as a masked softmax in XLA.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ddl_tpu.ops.flash_attention import (
    _LANES, _NEG_INF, _precision_for, _row_operand, _tag,
)
from ddl_tpu.ops.gated_delta import _NN, _NT, _TN
from ddl_tpu.ops.naming import named_pallas_call

#: Positions a query tile, keys a tile of the ``dkv`` pass.
_TILE_Q = 128
_TILE_K = 128
_VMEM_LIMIT = 64 * 2**20
#: Bytes of scalar memory a call's prefetched lists may take (a v5e's holds
#: 1 MiB: my chip run, PR 39).
_SMEM_BUDGET = 768 * 2**10


class SparseConfig(NamedTuple):
    """The selection's sizes (MiniCPM4's published values)."""

    block: int = 64
    kernel: int = 32
    stride: int = 16
    topk: int = 64
    init_blocks: int = 1
    #: ``window_size / block``: the blocks up to a query's own it always sees.
    local_blocks: int = 32


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=["visible", "lists", "counts", "lists_t", "counts_t"],
    meta_fields=["sizes"],
)
@dataclasses.dataclass(frozen=True)
class Selection:
    """What the sparse kernels read (the module's docstring)."""

    sizes: SparseConfig  # what it was selected with (static)
    visible: jax.Array  # (B, G, Tp, blocks) 0 / 1, the operands' dtype
    lists: jax.Array  # (B G tiles_q steps,) int32: two block ids a word
    counts: jax.Array  # (B G tiles_q,) int32: blocks a tile's list holds
    lists_t: jax.Array  # (B G tiles_k tiles_q,) int32: query tile ids
    counts_t: jax.Array  # (B G tiles_k,) int32


def _tiles(T: int, groups: int, sc: SparseConfig):
    """(tile_q, tile_k, padded length) for ``groups`` (row, key-value head)
    pairs of ``T`` positions: multiples of the block, the query tile
    doubled while the lists overflow :data:`_SMEM_BUDGET` (they are
    quadratic in the row's length: a selection a HEAD at 16,384 positions
    needs tiles of 512)."""
    whole = -(-T // sc.block) * sc.block
    fit = lambda want: max(sc.block, min(want, whole))
    tq, tk = fit(_TILE_Q), fit(_TILE_K)
    assert tq % sc.block == 0 and tk % sc.block == 0, (tq, tk, sc.block)

    def padded(tq):
        step = math.lcm(tq, tk)
        return -(-T // step) * step

    def list_bytes(tq):
        Tp = padded(tq)
        return 4 * groups * (Tp // tq) * max(-(-Tp // sc.block // 2), Tp // tk)

    while list_bytes(tq) > _SMEM_BUDGET and tq < whole:
        tq = fit(2 * tq)
    return tq, tk, padded(tq)


# -- selection ----------------------------------------------------------------


def _plane_keys(sc: SparseConfig, nb: int, M: int) -> np.ndarray:
    """(planes, nbp): the compressed key ``m`` at each plane's column, -1
    where none is (before the row, behind its last one, a padded column).
    The first ``block / stride`` planes hold every ``m`` once."""
    n = sc.block // sc.stride
    extra = (sc.kernel - 1) // sc.stride
    nbp = -(-nb // _LANES) * _LANES
    b = np.arange(nbp)
    m = np.stack(
        [b * n + r for r in range(n)] + [b * n - e for e in range(1, extra + 1)]
    )
    return np.where((m >= 0) & (m < M) & (b < nb), m, -1).astype(np.int32)


def _select_kernel(q_ref, kc_ref, m_ref, o_ref, *, heads, tile, main, nbp, scale,
                   reach, stride, precision):
    """Block scores of one tile of positions for one group."""
    t = pl.program_id(2) * tile + jax.lax.broadcasted_iota(
        jnp.int32, (tile, m_ref.shape[-1]), 0
    )
    m = m_ref[...]
    seen = (m >= 0) & (m * stride + reach <= t)  # wholly in the past
    col = jax.lax.broadcasted_iota(jnp.int32, seen.shape, 1)
    counted = seen & (col < main)  # each m once: the softmax's sum
    kc = kc_ref[0, 0]
    total = jnp.zeros(seen.shape, jnp.float32)
    for h in range(heads):
        s = scale * jax.lax.dot_general(
            q_ref[0, h], kc, _NT, precision=precision,
            preferred_element_type=jnp.float32,
        )
        top = jnp.max(jnp.where(counted, s, _NEG_INF), axis=-1, keepdims=True)
        e = jnp.where(seen, jnp.exp(s - jnp.where(top <= _NEG_INF / 2, 0.0, top)), 0.0)
        norm = jnp.sum(jnp.where(counted, e, 0.0), axis=-1, keepdims=True)
        total = total + e / jnp.where(norm > 0.0, norm, 1.0)
    best = total[:, :nbp]
    for lo in range(nbp, total.shape[-1], nbp):
        best = jnp.maximum(best, total[:, lo : lo + nbp])
    o_ref[0, 0] = best


def block_scores(q, k, sc: SparseConfig, interpret: Optional[bool] = None):
    """``s_{g,t,b}`` (B, G, T, blocks) float32 from q (B, T, H, D) and k
    (B, T, G, D): the selection's scores (the module's docstring)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    B, T, H, D = q.shape
    G = k.shape[2]
    nb = -(-T // sc.block)
    assert sc.block % sc.stride == 0 and T >= sc.kernel, (sc, T)
    M = (T - sc.kernel) // sc.stride + 1
    kc = jax.lax.reduce_window(
        k.astype(jnp.float32), 0.0, jax.lax.add, (1, sc.kernel, 1, 1),
        (1, sc.stride, 1, 1), "VALID",
    ) / sc.kernel  # (B, M, G, D)
    planes = _plane_keys(sc, nb, M)
    nbp, width = planes.shape[1], planes.size
    kc = jnp.moveaxis(kc, 2, 1)[:, :, np.maximum(planes.reshape(-1), 0)].astype(q.dtype)
    tile = min(_TILE_Q, -(-T // 8) * 8)
    Tp = -(-T // tile) * tile
    qt = jnp.moveaxis(jnp.pad(q, ((0, 0), (0, Tp - T), (0, 0), (0, 0))), 2, 1)
    R = H // G
    out = named_pallas_call(
        "ddl_sparse_select",
        functools.partial(
            _select_kernel, heads=R, tile=tile, main=(sc.block // sc.stride) * nbp,
            nbp=nbp, scale=D**-0.5, reach=sc.kernel - 1, stride=sc.stride,
            precision=_precision_for(q.dtype),
        ),
        grid=(B, G, Tp // tile),
        in_specs=[
            pl.BlockSpec((1, R, tile, D), lambda b, g, i: (b, g, i, 0)),
            pl.BlockSpec((1, 1, width, D), lambda b, g, i: (b, g, 0, 0)),
            pl.BlockSpec((1, width), lambda b, g, i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, tile, nbp), lambda b, g, i: (b, g, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, G, Tp, nbp), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
    )(qt, kc, jnp.asarray(planes.reshape(1, -1)))
    return out[:, :, :T, :nb]


def visible_blocks(scores, sc: SparseConfig):
    """(B, G, T, blocks) bool: ``visible(t)`` by block, from the block
    scores: initial and local blocks, and the ``topk - init_blocks`` best
    of the past blocks outside both (ties to the lower block)."""
    T, nb = scores.shape[2:]
    own = (jnp.arange(T) // sc.block)[:, None]
    b = jnp.arange(nb)[None, :]
    first = (b < sc.init_blocks) & (b <= own)
    local = (b <= own) & (own - b < sc.local_blocks)
    open_ = (b >= sc.init_blocks) & (own - b >= sc.local_blocks)
    picks = min(sc.topk - sc.init_blocks, nb)
    if picks <= 0:
        return jnp.broadcast_to(first | local, scores.shape)
    masked = jnp.where(open_, scores, -jnp.inf)
    least = jax.lax.top_k(masked, picks)[0][..., -1:]  # the last pick's score
    above = masked > least
    # what is left of the picks goes to the blocks AT that score, lowest first
    tied = masked == least
    left = picks - jnp.sum(above, axis=-1, keepdims=True)
    picked = open_ & (above | (tied & (jnp.cumsum(tied, axis=-1) <= left)))
    return first | local | picked


def _listed(live, pair: bool):
    """(..., rows, n) bool -> (the ``n`` live columns of each row,
    ascending, then the last of them again; their number)."""
    n = live.shape[-1]
    counts = jnp.sum(live, axis=-1, dtype=jnp.int32)
    order = jnp.argsort(jnp.logical_not(live), axis=-1, stable=True).astype(jnp.int32)
    last = jnp.take_along_axis(order, jnp.maximum(counts - 1, 0)[..., None], axis=-1)
    lists = jnp.where(jnp.arange(n) < counts[..., None], order, last)
    if pair:
        if n % 2:
            lists = jnp.concatenate([lists, last], axis=-1)
        lists = lists[..., 0::2] | (lists[..., 1::2] << 16)
    return lists.reshape(-1), counts.reshape(-1)


def make_selection(visible, sc: SparseConfig, dtype) -> Selection:
    """:class:`Selection` from ``visible`` (B, G, T, blocks) bool."""
    B, G, T, nb = visible.shape
    tq, tk, Tp = _tiles(T, B * G, sc)
    nbp = Tp // sc.block
    visible = jnp.pad(visible, ((0, 0), (0, 0), (0, Tp - T), (0, nbp - nb)))
    by_tile = jnp.any(visible.reshape(B, G, Tp // tq, tq, nbp), axis=3)
    lists, counts = _listed(by_tile, pair=True)
    by_key_tile = jnp.any(
        by_tile.reshape(B, G, Tp // tq, Tp // tk, tk // sc.block), axis=-1
    )
    lists_t, counts_t = _listed(jnp.swapaxes(by_key_tile, 2, 3), pair=False)
    return Selection(sc, *(_tag(x) for x in (
        visible.astype(dtype), lists, counts, lists_t, counts_t
    )))


def select_blocks(q, k, sc: SparseConfig, interpret: Optional[bool] = None) -> Selection:
    """The selection of q (B, T, H, D) over k (B, T, G, D), without a
    gradient."""
    q, k = jax.lax.stop_gradient(q), jax.lax.stop_gradient(k)
    scores = block_scores(q, k, sc, interpret)
    return make_selection(visible_blocks(scores, sc), sc, q.dtype)


# -- the flash kernels ----------------------------------------------------------


def _step_mask(vis, first_block, valid, tile_q, keys, block, q0):
    """(tile_q, keys) bool: the step's keys a position sees.  ``vis``
    (tile_q, blocks) 0 / 1; ``first_block`` (1, keys) int32: the block each
    key column belongs to; ``valid`` (1, keys) bool."""
    nb = vis.shape[-1]
    rows = jax.lax.broadcasted_iota(jnp.int32, (nb, keys), 0)
    pick = ((rows == first_block) & valid).astype(vis.dtype)
    chosen = jax.lax.dot_general(
        vis, pick, _NN, preferred_element_type=jnp.float32
    ) > 0.5
    col = jax.lax.broadcasted_iota(jnp.int32, (tile_q, keys), 1)
    k_pos = first_block * block + col % block
    q_pos = q0 + jax.lax.broadcasted_iota(jnp.int32, (tile_q, keys), 0)
    return chosen & (k_pos <= q_pos)


def _pair_of_step(lists_ref, counts_ref, steps, block):
    """For a forward / dq grid step: (live, the block of each key column
    (1, 2 block), which columns hold a listed block)."""
    b, g, i, j = (pl.program_id(a) for a in range(4))
    tile = (b * pl.num_programs(1) + g) * pl.num_programs(2) + i
    count = counts_ref[tile]
    word = lists_ref[tile * steps + j]
    col = jax.lax.broadcasted_iota(jnp.int32, (1, 2 * block), 1)
    second = col >= block
    blocks = jnp.where(second, word >> 16, word & 0xFFFF)
    valid = jnp.logical_not(second) | (2 * j + 1 < count)
    return 2 * j < count, blocks, valid


def _listed_pair(ka_ref, kb_ref, va_ref, vb_ref, vis_ref, blocks, valid, tile_q,
                 block, q0):
    """A forward / dq step's keys and values (its two listed blocks, one
    after the other) and the (tile_q, 2 block) mask of what a position sees
    of them."""
    k = jnp.concatenate([ka_ref[0, 0], kb_ref[0, 0]], axis=0)
    v = jnp.concatenate([va_ref[0, 0], vb_ref[0, 0]], axis=0)
    mask = _step_mask(vis_ref[0, 0], blocks, valid, tile_q, 2 * block, block, q0)
    return k, v, mask


def _rows(ref):
    """A (1, R, tile, D) block as (R tile, D) rows, head-major."""
    x = ref[0]
    return x.reshape(x.shape[0] * x.shape[1], x.shape[2])


def _masked_scores(q, k, mask, scale, precision):
    """(R, tile_q, keys) float32 scores of ``q`` (R tile_q, D) rows, the
    unseen ones at ``_NEG_INF``."""
    s = scale * jax.lax.dot_general(
        q, k, _NT, precision=precision, preferred_element_type=jnp.float32
    )
    R = s.shape[0] // mask.shape[0]
    return jnp.where(mask[None], s.reshape((R,) + mask.shape), _NEG_INF)


def _fwd_kernel(lists_ref, counts_ref, q_ref, ka_ref, kb_ref, va_ref, vb_ref,
                vis_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref, *, scale, block,
                steps, precision):
    j, last = pl.program_id(3), pl.num_programs(3) - 1
    tile_q = q_ref.shape[2]
    q0 = pl.program_id(2) * tile_q

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    live, blocks, valid = _pair_of_step(lists_ref, counts_ref, steps, block)

    @pl.when(live)
    def _attend():
        k, v, mask = _listed_pair(
            ka_ref, kb_ref, va_ref, vb_ref, vis_ref, blocks, valid, tile_q, block, q0
        )
        s = _masked_scores(_rows(q_ref), k, mask, scale, precision)
        s = s.reshape(-1, s.shape[-1])
        m_prev = jnp.max(m_ref[:], axis=-1)
        l_prev = jnp.max(l_ref[:], axis=-1)
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        safe_m = jnp.where(m_next <= _NEG_INF / 2, 0.0, m_next)
        alpha = jnp.exp(jnp.where(m_prev <= _NEG_INF / 2, _NEG_INF, m_prev - safe_m))
        p = jnp.exp(s - safe_m[:, None])
        acc_ref[:] = acc_ref[:] * alpha[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, _NN, preferred_element_type=jnp.float32,
            precision=precision,
        )
        l_next = alpha * l_prev + jnp.sum(p, axis=-1)
        m_ref[:] = jnp.broadcast_to(m_next[:, None], m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_next[:, None], l_ref.shape)

    @pl.when(j == last)
    def _finish():
        m = jnp.max(m_ref[:], axis=-1)
        l = jnp.max(l_ref[:], axis=-1)
        lse = jnp.where(
            l > 0.0, jnp.where(m <= _NEG_INF / 2, 0.0, m) + jnp.log(l), _NEG_INF
        )
        lse_ref[0] = lse[:, None].reshape(lse_ref.shape[1:])
        out = acc_ref[:] / jnp.where(l == 0.0, 1.0, l)[:, None]
        o_ref[0] = out.reshape(o_ref.shape[1:]).astype(o_ref.dtype)


def _p_and_ds(q, k, v, d_o, lse, delta, mask, scale, precision):
    """The probabilities (rows, keys) float32 from the saved logsumexp and
    the scores' cotangent."""
    s = _masked_scores(q, k, mask, scale, precision)
    s = s.reshape(-1, s.shape[-1])
    # an unseen pair sits at _NEG_INF: exp underflows to 0 whatever lse is,
    # but a row that saw nothing has lse = _NEG_INF too
    p = jnp.where(s <= _NEG_INF / 2, 0.0, jnp.exp(s - lse))
    d_p = jax.lax.dot_general(
        d_o, v, _NT, preferred_element_type=jnp.float32, precision=precision
    )
    return p, p * (d_p - delta) * scale


def _dq_kernel(lists_ref, counts_ref, q_ref, ka_ref, kb_ref, va_ref, vb_ref,
               vis_ref, do_ref, lse_ref, delta_ref, dq_ref, acc_ref, *, scale,
               block, steps, precision):
    j, last = pl.program_id(3), pl.num_programs(3) - 1
    tile_q = q_ref.shape[2]
    q0 = pl.program_id(2) * tile_q

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    live, blocks, valid = _pair_of_step(lists_ref, counts_ref, steps, block)

    @pl.when(live)
    def _accumulate():
        k, v, mask = _listed_pair(
            ka_ref, kb_ref, va_ref, vb_ref, vis_ref, blocks, valid, tile_q, block, q0
        )
        _, d_s = _p_and_ds(
            _rows(q_ref), k, v, _rows(do_ref), _rows(lse_ref), _rows(delta_ref),
            mask, scale, precision,
        )
        acc_ref[:] += jax.lax.dot_general(
            d_s.astype(k.dtype), k, _NN, preferred_element_type=jnp.float32,
            precision=precision,
        )

    @pl.when(j == last)
    def _finish():
        dq_ref[0] = acc_ref[:].reshape(dq_ref.shape[1:]).astype(dq_ref.dtype)


def _dkv_kernel(lists_ref, counts_ref, q_ref, k_ref, v_ref, vis_ref, do_ref,
                lse_ref, delta_ref, dk_ref, dv_ref, dk_acc, dv_acc, *, scale,
                block, precision):
    b, g, c, j = (pl.program_id(a) for a in range(4))
    tile_q, tile_k = q_ref.shape[2], k_ref.shape[2]
    steps = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    tile = (b * pl.num_programs(1) + g) * pl.num_programs(2) + c

    @pl.when(j < counts_ref[tile])
    def _accumulate():
        i = lists_ref[tile * steps + j]
        col = jax.lax.broadcasted_iota(jnp.int32, (1, tile_k), 1)
        mask = _step_mask(
            vis_ref[0, 0], c * (tile_k // block) + col // block, col >= 0,
            tile_q, tile_k, block, i * tile_q,
        )
        q, d_o = _rows(q_ref), _rows(do_ref)
        p, d_s = _p_and_ds(
            q, k_ref[0, 0], v_ref[0, 0], d_o, _rows(lse_ref), _rows(delta_ref),
            mask, scale, precision,
        )
        dv_acc[:] += jax.lax.dot_general(
            p.astype(d_o.dtype), d_o, _TN, preferred_element_type=jnp.float32,
            precision=precision,
        )
        dk_acc[:] += jax.lax.dot_general(
            d_s.astype(q.dtype), q, _TN, preferred_element_type=jnp.float32,
            precision=precision,
        )

    @pl.when(j == steps - 1)
    def _finish():
        dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


def _geometry(q, k, sel: Selection):
    sc = sel.sizes
    B, T, H, D = q.shape
    G = k.shape[2]
    tq, tk, Tp = _tiles(T, B * G, sc)
    assert sel.visible.shape == (B, G, Tp, Tp // sc.block), (sel.visible.shape, Tp)
    if 4 * max(sel.lists.size, sel.lists_t.size) > _SMEM_BUDGET:
        # rows x groups x tiles x steps words: quadratic in the row's length
        raise NotImplementedError(
            f"sparse_attention: the block lists of {B} x {G} groups x {Tp} "
            f"positions at tile_q {tq}, tile_k {tk} do not fit scalar memory "
            f"({4 * sel.lists.size} / {4 * sel.lists_t.size} bytes)"
        )
    return B, T, H, D, G, H // G, tq, tk, Tp


def _head_major(x, Tp):
    """(B, T, heads, D) -> (B, heads, Tp, D), zero rows behind the row."""
    x = jnp.pad(x, ((0, 0), (0, Tp - x.shape[1]), (0, 0), (0, 0)))
    return jnp.moveaxis(x, 2, 1)


def _list_specs(G, R, D, tq, tiles_q, nbp, block, steps):
    """Block specs of a forward / dq call: the query tile's, the two listed
    key blocks' (k and v each), ``visible``'s, a row operand's."""
    def listed(shift):
        def at(b, g, i, j, lists_ref, counts_ref):
            word = lists_ref[((b * G + g) * tiles_q + i) * steps + j]
            return b, g, (word >> shift) & 0xFFFF, 0

        return pl.BlockSpec((1, 1, block, D), at)

    tile = pl.BlockSpec((1, R, tq, D), lambda b, g, i, j, *_: (b, g, i, 0))
    vis = pl.BlockSpec((1, 1, tq, nbp), lambda b, g, i, j, *_: (b, g, i, 0))
    row = pl.BlockSpec((1, R, tq, 1), lambda b, g, i, j, *_: (b, g, i, 0))
    return tile, [listed(0), listed(16)] * 2, vis, row


def _compiler_params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT,
    )


def _forward(q, k, v, sel: Selection, interpret):
    sc = sel.sizes
    B, T, H, D, G, R, tq, tk, Tp = _geometry(q, k, sel)
    nbp = Tp // sc.block
    steps = -(-nbp // 2)
    qt, kt, vt = _head_major(q, Tp), _head_major(k, Tp), _head_major(v, Tp)
    tile, listed, vis, row = _list_specs(G, R, D, tq, Tp // tq, nbp, sc.block, steps)
    out, lse = named_pallas_call(
        "ddl_flash_sparse_fwd",
        functools.partial(
            _fwd_kernel, scale=D**-0.5, block=sc.block, steps=steps,
            precision=_precision_for(q.dtype),
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, G, Tp // tq, steps),
            in_specs=[tile] + listed + [vis],
            out_specs=[tile, row],
            scratch_shapes=[
                pltpu.VMEM((R * tq, _LANES), jnp.float32),
                pltpu.VMEM((R * tq, _LANES), jnp.float32),
                pltpu.VMEM((R * tq, D), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Tp, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, Tp, 1), jnp.float32),
        ],
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(sel.lists, sel.counts, qt, kt, kt, vt, vt, sel.visible)
    return jnp.moveaxis(out[:, :, :T], 1, 2), lse[:, :, :T, 0]


def _backward(q, k, v, sel: Selection, out, lse, d_out, interpret):
    sc = sel.sizes
    B, T, H, D, G, R, tq, tk, Tp = _geometry(q, k, sel)
    nbp = Tp // sc.block
    steps = -(-nbp // 2)
    precision = _precision_for(q.dtype)
    delta = jnp.sum(out.astype(jnp.float32) * d_out.astype(jnp.float32), axis=-1)
    delta = _row_operand(jnp.moveaxis(delta, 2, 1), Tp)
    lse = _row_operand(lse, Tp)
    qt, kt, vt, dot = (_head_major(x, Tp) for x in (q, k, v, d_out))
    tile, listed, vis, row = _list_specs(G, R, D, tq, Tp // tq, nbp, sc.block, steps)
    dq = named_pallas_call(
        "ddl_flash_sparse_bwd_dq",
        functools.partial(
            _dq_kernel, scale=D**-0.5, block=sc.block, steps=steps,
            precision=precision,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, G, Tp // tq, steps),
            in_specs=[tile] + listed + [vis, tile, row, row],
            out_specs=tile,
            scratch_shapes=[pltpu.VMEM((R * tq, D), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, Tp, D), q.dtype),
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(sel.lists, sel.counts, qt, kt, kt, vt, vt, sel.visible, dot, lse, delta)

    tiles_q, tiles_k = Tp // tq, Tp // tk

    def listed_tile(b, g, c, j, lists_ref, counts_ref):
        return b, g, lists_ref[((b * G + g) * tiles_k + c) * tiles_q + j], 0

    q_tile = pl.BlockSpec((1, R, tq, D), listed_tile)
    q_row = pl.BlockSpec((1, R, tq, 1), listed_tile)
    q_vis = pl.BlockSpec((1, 1, tq, nbp), listed_tile)
    key = pl.BlockSpec((1, 1, tk, D), lambda b, g, c, j, *_: (b, g, c, 0))
    dk, dv = named_pallas_call(
        "ddl_flash_sparse_bwd_dkv",
        functools.partial(
            _dkv_kernel, scale=D**-0.5, block=sc.block, precision=precision
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, G, tiles_k, tiles_q),
            in_specs=[q_tile, key, key, q_vis, q_tile, q_row, q_row],
            out_specs=[key, key],
            scratch_shapes=[pltpu.VMEM((tk, D), jnp.float32)] * 2,
        ),
        out_shape=[jax.ShapeDtypeStruct((B, G, Tp, D), k.dtype)] * 2,
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(sel.lists_t, sel.counts_t, qt, kt, vt, sel.visible, dot, lse, delta)
    back = lambda x: jnp.moveaxis(x[:, :, :T], 1, 2)
    return back(dq), back(dk), back(dv)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _sparse_core(q, k, v, sel, interpret):
    return _forward(q, k, v, sel, interpret)[0]


def _core_fwd(q, k, v, sel, interpret):
    out, lse = _forward(q, k, v, sel, interpret)
    out, lse = _tag(out), _tag(lse)
    return out, (q, k, v, sel, out, lse)


def _core_bwd(interpret, res, d_out):
    q, k, v, sel, out, lse = res
    grads = _backward(q, k, v, sel, out, lse, d_out, interpret)
    no_grad = lambda x: (
        jnp.zeros_like(x) if jnp.issubdtype(x.dtype, jnp.floating)
        else np.zeros(x.shape, jax.dtypes.float0)
    )
    return grads + (jax.tree.map(no_grad, sel),)


_sparse_core.defvjp(_core_fwd, _core_bwd)


def sparse_attention(q, k, v, sel: Selection, interpret: Optional[bool] = None):
    """``softmax_{j in visible(t)}(q_t . k_j / sqrt(D)) v_j`` for q (B, T, H,
    D) over k, v (B, T, G, D), ``sel`` from :func:`select_blocks`.
    Differentiable in q, k and v; the output (B, T, H, D) is tagged for
    ``remat="selective"`` beside its logsumexp."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _sparse_core(q, k, v, sel, interpret)


def attention_dense(q, k, v, sel: Selection):
    """The same function as one masked softmax in XLA (every pair's score
    written out: short rows, the CPU)."""
    sc = sel.sizes
    B, T, H, D = q.shape
    R = H // k.shape[2]
    seen = jnp.repeat(sel.visible[:, :, :T] > 0.5, sc.block, axis=-1)[..., :T]
    seen = seen & (jnp.arange(T)[None, :] <= jnp.arange(T)[:, None])
    s = jnp.einsum(
        "bqgrd,bkgd->bgrqk", q.reshape(B, T, -1, R, D), k,
        precision=_precision_for(q.dtype), preferred_element_type=jnp.float32,
    ) * D**-0.5
    p = jax.nn.softmax(jnp.where(seen[:, :, None], s, _NEG_INF), axis=-1)
    o = jnp.einsum(
        "bgrqk,bkgd->bqgrd", p.astype(v.dtype), v, precision=_precision_for(q.dtype),
        preferred_element_type=jnp.float32,
    )
    return o.reshape(B, T, H, D).astype(q.dtype)
