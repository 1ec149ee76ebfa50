"""The hyper-connected residual path's passes over the stream as kernels:
each reads a tile's four rows ``(n, tt, C)`` from HBM once, holds them in
VMEM, and writes its result rows straight into the stacked ``(B, n, T, C)``
array (``models/hyper_connections.py`` has the equations and the plain XLA
passes these are held to).

- ``ddl_hc_pre_fwd`` ``X -> (h, p, ss, Hpre)``: the ``2 n + n^2`` folded
  projections on the MXU (the float32 weights split three ways into bfloat16
  rows, ``cm,tc->mt``, so ``p`` comes out token-last), the stream's sum of
  squares, ``Hpre`` - a sigmoid of ``n`` numbers a token - and ``h = Hpre X``
  from the same tile.
- ``ddl_hc_pre_bwd`` ``(X, dh, ...) -> (dX, dHpre, X^T dp)``: ``dHpre_i =
  <dh, X_i>``, then what of ``dp`` and ``dss`` hangs on it (through the
  ``pre`` sigmoid and the norm's ``rsqrt`` alone; the rounds' backward is
  XLA's, before the kernel, on the small arrays), ``dX_i = Hpre_i dh + dp
  (norm phi)_i^T + 2 dss X_i`` plus the stream's other cotangent (the wrap's
  second half's: the merge autodiff would make in a pass of its own), and the
  weights' cotangent accumulated over
  the token grid in a resident output block.
- ``ddl_hc_post_fwd`` ``(X, y, Hpost, Hres) -> X'``.
- ``ddl_hc_post_bwd`` ``(X, y, Hpost, Hres, dX') -> (dX, dy, dHpost,
  dHres)``: the twenty inner products are lane reductions of the tile in
  VMEM.

A grid step is :data:`TILE` tokens of one batch row.  The elementwise work
walks the tile in slabs of :data:`SLAB` tokens and a slab in chunks of 128
lanes, so that its float32 intermediates stay in registers - both as loops:
a kernel body that unrolls a row's chunks is a program ``C / 128`` times as
long, traced a few times a process and lowered at every call site of a step
(PERF.md section 6, PR 50: +19.5% on a warm ``setup_s``); a token's coefficients (the
matrices' entries) reach it as columns ``(tokens, 1)`` of a token-major
``(TILE, 128)`` array that is the transpose, made once a step, of the
token-last rows the model keeps.  The small arrays cross the kernels'
boundary token-last, rows padded to the sublanes' 8.

Off the TPU the kernels run in Pallas' interpret mode, which is how the CPU
tests hold them to the plain form.
"""

from __future__ import annotations

import functools
import operator
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ddl_tpu.ops.naming import named_pallas_call

F32 = jnp.float32
BF16 = jnp.bfloat16
#: Tokens a grid step: the lanes of a token-last block.
TILE = 128
#: Tokens an inner step of the elementwise work: a bfloat16 tile's rows.
SLAB = 16
_LANES = 128
#: Chunks of 128 lanes an inner step of the elementwise work.
UNROLL = 4
#: ``post_bwd`` at 4 x 3,584 holds 12.9 MB of blocks a step, twice for the
#: pipeline's second buffer; the chip has 128 MiB.
_VMEM_LIMIT = 64 * 1024 * 1024


def takes(shape: Tuple[int, ...], dtype) -> bool:
    """Whether a stream ``(B, n, T, C)`` takes the kernels: whole lanes,
    whole tiles, the projections' ``m = 2 n + n^2`` rows whole sublanes and
    their three-way split within one matmul's 128 rows."""
    _, n, T, C = shape
    m = 2 * n + n * n
    return (C % _LANES == 0 and T % TILE == 0 and m % 8 == 0
            and 3 * m <= _LANES and dtype in (BF16, F32))


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _pad_rows(x: jax.Array, rows: int) -> jax.Array:
    """``x (k, tt)`` with zero rows below it, ``rows`` in all."""
    k, tt = x.shape
    if k == rows:
        return x
    return jnp.concatenate([x, jnp.zeros((rows - k, tt), x.dtype)], axis=0)


def _token_major(x: jax.Array) -> jax.Array:
    """Token-last float32 rows ``(k, tt)``, ``k`` a multiple of 8, as
    ``(tt, 128)``: a token's ``k`` numbers on its first lanes."""
    return _pad_rows(x, _LANES).T


def _column(cols: jax.Array, k: int) -> jax.Array:
    """Column ``k`` of a slab's token-major numbers, over a slab's lanes."""
    return jnp.broadcast_to(cols[:, k : k + 1], (cols.shape[0], _LANES))


def _slabs(body) -> None:
    """``body(rows)`` for each slab of a tile's tokens."""

    def step(s, carry):
        body(pl.ds(pl.multiple_of(s * SLAB, SLAB), SLAB))
        return carry

    lax.fori_loop(0, TILE // SLAB, step, 0)


def _chunks(C: int, body, carry=None):
    """``carry = body(lanes, carry)`` for each 128 lanes of a row of ``C``: a
    loop (a kernel's program, and what tracing and lowering it cost, do not
    grow with ``C``) whose step is :data:`UNROLL` chunks, for the scheduler to
    interleave."""
    chunks = C // _LANES
    unroll = next(u for u in (UNROLL, 2, 1) if chunks % u == 0)

    def step(c, carry):
        for k in range(unroll):
            start = pl.multiple_of((c * unroll + k) * _LANES, _LANES)
            carry = body(pl.ds(start, _LANES), carry)
        return carry

    return lax.fori_loop(0, chunks // unroll, step, carry)


def _lane_masks(count: int):
    lane = lax.broadcasted_iota(jnp.int32, (SLAB, _LANES), 1)
    return [lane == k for k in range(count)]


def _gather_sums(parts, masks) -> jax.Array:
    """``(SLAB, 128)`` with the lane sums of ``parts[k]`` on lane ``k``."""
    out = jnp.zeros((SLAB, _LANES), F32)
    for part, mask in zip(parts, masks):
        total = jnp.sum(part, axis=-1, keepdims=True)
        out = lax.select(mask, jnp.broadcast_to(total, out.shape), out)
    return out


def _call(name, kernel, grid, in_specs, out_specs, out_shape, scratch=(),
          semantics=("parallel", "parallel")):
    """The kernel's call; XLA's cost analysis is told the bytes it moves -
    each operand and result once."""
    call = functools.partial(
        named_pallas_call, name, kernel, grid=grid, in_specs=in_specs,
        out_specs=out_specs, out_shape=out_shape, scratch_shapes=list(scratch),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=semantics, vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_interpret(),
    )
    size = lambda x: x.size * x.dtype.itemsize

    def run(*args):
        moved = sum(map(size, args)) + sum(map(size, jax.tree.leaves(out_shape)))
        return call(cost_estimate=pl.CostEstimate(
            flops=0, transcendentals=0, bytes_accessed=moved))(*args)

    return run


def _stream_spec(n, C):
    return pl.BlockSpec((1, n, TILE, C), lambda b, t: (b, 0, t, 0))


def _row_spec(C):
    return pl.BlockSpec((1, TILE, C), lambda b, t: (b, t, 0))


def _small_spec(rows):
    return pl.BlockSpec((1, rows, TILE), lambda b, t: (b, 0, t))


def _whole_spec(shape):
    return pl.BlockSpec(shape, lambda b, t: (0,) * len(shape))


# -- hc_post ---------------------------------------------------------------------------


def _coefficients(post: jax.Array, res: jax.Array) -> jax.Array:
    """``[Hres (n^2 rows, i major) | Hpost (n) | 0]`` (B, rows, T) float32,
    the rows a multiple of 8."""
    B, n, T = post.shape
    rows = -(-(n * n + n) // 8) * 8
    return jnp.concatenate([
        res.reshape(B, n * n, T).astype(F32), post.astype(F32),
        jnp.zeros((B, rows - n * n - n, T), F32)], axis=1)


def _post_fwd_kernel(x_ref, y_ref, coef_ref, out_ref, cols_ref, *, n, C):
    cols_ref[...] = _token_major(coef_ref[0])

    def slab(rows):
        cols = cols_ref[rows, :]
        res = [[_column(cols, i * n + j) for j in range(n)] for i in range(n)]
        post = [_column(cols, n * n + i) for i in range(n)]

        def chunk(lanes, _):
            xs = [x_ref[0, j, rows, lanes].astype(F32) for j in range(n)]
            yf = y_ref[0, rows, lanes].astype(F32)
            for i in range(n):
                acc = res[i][0] * xs[0]
                for j in range(1, n):
                    acc = acc + res[i][j] * xs[j]
                out_ref[0, i, rows, lanes] = (acc + post[i] * yf).astype(out_ref.dtype)

        _chunks(C, chunk)

    _slabs(slab)


def post_fwd(X: jax.Array, y: jax.Array, post: jax.Array, res: jax.Array) -> jax.Array:
    """``X'_i = sum_j Hres[i, j] X_j + Hpost_i y`` (B, n, T, C)."""
    B, n, T, C = X.shape
    coef = _coefficients(post, res)
    return _call(
        "ddl_hc_post_fwd", functools.partial(_post_fwd_kernel, n=n, C=C),
        (B, T // TILE),
        [_stream_spec(n, C), _row_spec(C), _small_spec(coef.shape[1])],
        _stream_spec(n, C), jax.ShapeDtypeStruct(X.shape, X.dtype),
        scratch=[pltpu.VMEM((TILE, _LANES), F32)],
    )(X, y, coef)


def _post_bwd_kernel(x_ref, y_ref, coef_ref, dxn_ref, dx_ref, dy_ref, dcoef_ref,
                     cols_ref, sums_ref, *, n, C):
    cols_ref[...] = _token_major(coef_ref[0])
    masks = _lane_masks(n * n + n)

    def slab(rows):
        cols = cols_ref[rows, :]
        res = [[_column(cols, i * n + j) for j in range(n)] for i in range(n)]
        post = [_column(cols, n * n + i) for i in range(n)]

        def chunk(lanes, dots):
            xs = [x_ref[0, j, rows, lanes].astype(F32) for j in range(n)]
            ds = [dxn_ref[0, i, rows, lanes].astype(F32) for i in range(n)]
            yf = y_ref[0, rows, lanes].astype(F32)
            for j in range(n):
                acc = res[0][j] * ds[0]
                for i in range(1, n):
                    acc = acc + res[i][j] * ds[i]
                dx_ref[0, j, rows, lanes] = acc.astype(dx_ref.dtype)
            acc = post[0] * ds[0]
            for i in range(1, n):
                acc = acc + post[i] * ds[i]
            dy_ref[0, rows, lanes] = acc.astype(dy_ref.dtype)
            return tuple(
                dots[i * n + j] + ds[i] * xs[j] for i in range(n) for j in range(n)
            ) + tuple(dots[n * n + i] + ds[i] * yf for i in range(n))

        dots = _chunks(C, chunk, (jnp.zeros((SLAB, _LANES), F32),) * (n * n + n))
        sums_ref[rows, :] = _gather_sums(dots, masks)

    _slabs(slab)
    dcoef_ref[0] = sums_ref[...].T[: dcoef_ref.shape[1]]


def post_bwd(X, y, post, res, dXn):
    """``(dX, dy, dHpost (B, n, T), dHres (B, n, n, T))``, the small ones
    float32: ``dX_j = sum_i Hres[i, j] dX'_i``, ``dy = sum_i Hpost_i dX'_i``,
    ``dHres[i, j] = <dX'_i, X_j>``, ``dHpost_i = <dX'_i, y>``."""
    B, n, T, C = X.shape
    coef = _coefficients(post, res)
    rows = coef.shape[1]
    dX, dy, dcoef = _call(
        "ddl_hc_post_bwd", functools.partial(_post_bwd_kernel, n=n, C=C),
        (B, T // TILE),
        [_stream_spec(n, C), _row_spec(C), _small_spec(rows), _stream_spec(n, C)],
        [_stream_spec(n, C), _row_spec(C), _small_spec(rows)],
        [jax.ShapeDtypeStruct(X.shape, X.dtype), jax.ShapeDtypeStruct(y.shape, y.dtype),
         jax.ShapeDtypeStruct((B, rows, T), F32)],
        scratch=[pltpu.VMEM((TILE, _LANES), F32), pltpu.VMEM((TILE, _LANES), F32)],
    )(X, y, coef, dXn)
    return (dX, dy, dcoef[:, n * n : n * n + n],
            dcoef[:, : n * n].reshape(B, n, n, T))


# -- hc_pre ----------------------------------------------------------------------------


def _split3(w: jax.Array):
    """A float32 array as three whose sum is it to 24 bits, each a bfloat16
    number (kept float32: a kernel's converts are not folded away)."""
    parts = []
    for _ in range(3):
        part = w.astype(BF16).astype(F32)
        parts.append(part)
        w = w - part
    return parts


def _mxu_rows(x: jax.Array, dtype) -> jax.Array:
    """Float32 rows ``(k, tt)`` as a matmul's operand: 128 rows of ``dtype``."""
    return _pad_rows(x, _LANES).astype(dtype)


def _dot(a, b, dims):
    precision = lax.Precision.HIGHEST if a.dtype == F32 else None
    return lax.dot_general(a, b, (dims, ((), ())), precision=precision,
                           preferred_element_type=F32)


def _unsplit(y: jax.Array, m: int, split: bool) -> jax.Array:
    """Rows ``[:m]`` of a product with unsplit weights, or the sum of the
    three parts' rows."""
    return y[:m] + y[m : 2 * m] + y[2 * m : 3 * m] if split else y[:m]


def _norm_factor(ss, width, norm_eps):
    """What the norm multiplies a token's projections by."""
    return lax.rsqrt(ss / width + norm_eps)


def _pre_fwd_kernel(x_ref, w_ref, gate_ref, h_ref, p_ref, ss_ref, pre_ref,
                    cols_ref, *, n, C, norm_eps):
    m = p_ref.shape[1]
    split = x_ref.dtype == BF16
    p = _unsplit(functools.reduce(operator.add, (  # (rows, tt) a stream's row
        _dot(w_ref[i], x_ref[0, i], ((1,), (1,))) for i in range(n))), m, split)

    def squares(rows):
        def chunk(lanes, total):
            for i in range(n):
                xf = x_ref[0, i, rows, lanes].astype(F32)
                total = total + xf * xf
            return total

        cols_ref[rows, :] = _chunks(C, chunk, jnp.zeros((SLAB, _LANES), F32))

    _slabs(squares)
    ss = jnp.sum(cols_ref[...].T, axis=0, keepdims=True)  # (1, tt)
    gate = gate_ref[:8]
    z = p[:8] * _norm_factor(ss, n * C, norm_eps)
    pre = jax.nn.sigmoid(gate[:, 1:2] * z + gate[:, 0:1])  # rows n.. unused
    p_ref[0] = p
    ss_ref[0] = ss
    pre_ref[0] = pre[:n]
    cols_ref[...] = _token_major(pre)

    def read(rows):
        cols = cols_ref[rows, :]
        pre_i = [_column(cols, i) for i in range(n)]

        def chunk(lanes, _):
            acc = pre_i[0] * x_ref[0, 0, rows, lanes].astype(F32)
            for i in range(1, n):
                acc = acc + pre_i[i] * x_ref[0, i, rows, lanes].astype(F32)
            h_ref[0, rows, lanes] = acc.astype(h_ref.dtype)

        _chunks(C, chunk)

    _slabs(read)


def _weight_rows(W: jax.Array) -> jax.Array:
    """Weights ``(n, C, k)`` in the stream's dtype as a kernel's matmul
    operand ``(n, 128, C)``: ``k`` rows, zero rows below."""
    Wt = jnp.swapaxes(W, 1, 2)
    return jnp.pad(Wt, ((0, 0), (0, _LANES - Wt.shape[1]), (0, 0)))


def _gate_rows(alpha_pre: jax.Array, b_pre: jax.Array, m: int) -> jax.Array:
    """``[b_pre | alpha_pre]`` on the first two lanes of ``m`` rows (float32)."""
    n = b_pre.shape[0]
    out = jnp.zeros((m, _LANES), F32)
    out = out.at[:n, 0].set(b_pre.astype(F32))
    return out.at[:, 1].set(alpha_pre.astype(F32))


def pre_fwd(X: jax.Array, W: jax.Array, alpha_pre: jax.Array, b_pre: jax.Array,
            norm_eps: float):
    """``(h (B, T, C), p (B, m, T), ss (B, T), Hpre (B, n, T))`` of the
    stream and the folded weights ``W`` in its dtype - float32 ``(n, C, m)``,
    or bfloat16 ``(n, C, 3 m)``, the three parts of their split side by side
    -: ``p = vec(X) W``, ``ss = |vec(X)|^2``, ``Hpre = sigmoid(alpha_pre p[:n]
    rsqrt(ss / (n C) + norm_eps) + b_pre)`` and ``h = Hpre X``, the small ones
    float32."""
    B, n, T, C = X.shape
    m = 2 * n + n * n
    small = lambda rows: jax.ShapeDtypeStruct((B, rows, T), F32)
    h, p, ss, pre = _call(
        "ddl_hc_pre_fwd",
        functools.partial(_pre_fwd_kernel, n=n, C=C, norm_eps=norm_eps),
        (B, T // TILE),
        [_stream_spec(n, C), _whole_spec((n, _LANES, C)), _whole_spec((m, _LANES))],
        [_row_spec(C), _small_spec(m), _small_spec(1), _small_spec(n)],
        [jax.ShapeDtypeStruct((B, T, C), X.dtype), small(m), small(1), small(n)],
        scratch=[pltpu.VMEM((TILE, _LANES), F32)],
    )(X, _weight_rows(W), _gate_rows(alpha_pre, b_pre, m))
    return h, p, ss[:, 0], pre


def _pre_bwd_kernel(x_ref, dh_ref, small_ref, w_ref, gate_ref, add_ref,
                    dx_ref, dpre_ref, dw_ref, cols_ref, through_ref, *,
                    n, C, m, norm_eps):
    split = x_ref.dtype == BF16
    masks = _lane_masks(n)
    row = lax.broadcasted_iota(jnp.int32, (m, TILE), 0)

    def inner(rows):
        def chunk(lanes, dots):
            dhf = dh_ref[0, rows, lanes].astype(F32)
            return tuple(dots[i] + dhf * x_ref[0, i, rows, lanes].astype(F32)
                         for i in range(n))

        dots = _chunks(C, chunk, (jnp.zeros((SLAB, _LANES), F32),) * n)
        cols_ref[rows, :] = _gather_sums(dots, masks)

    _slabs(inner)
    dpre = cols_ref[...].T[:m]  # rows n.. are zero
    dpre_ref[0] = dpre[:n]
    small = small_ref[0]
    pre, p, dp_rest = small[:m], small[m : 2 * m], small[2 * m : 3 * m]
    ss, dss_rest = small[3 * m : 3 * m + 1], small[3 * m + 1 : 3 * m + 2]
    alpha = gate_ref[:, 1:2]
    r = _norm_factor(ss, n * C, norm_eps)
    dz = dpre * pre * (1.0 - pre) * alpha  # the ``pre`` rows; zero below
    dp = dz * r + dp_rest
    dss = dss_rest - (0.5 / (n * C)) * (r * r * r) * jnp.sum(
        dz * p, axis=0, keepdims=True)
    cols_ref[...] = _token_major(
        lax.select(row == n, jnp.broadcast_to(2.0 * dss, pre.shape), pre))
    if split:
        hi, mid, lo = _split3(dp)
        # (dp_hi + dp_mid + dp_lo) (W_hi + W_mid + W_lo)^T to 16 bits: the
        # three products a bfloat16 cotangent can tell apart (``w_ref``'s
        # rows are W_hi | W_mid | W_hi).
        lead = _mxu_rows(jnp.concatenate([hi, hi, mid], axis=0), BF16).T
        dp_rows = _mxu_rows(jnp.concatenate([hi, mid, lo], axis=0), BF16)
    else:
        lead, dp_rows = _mxu_rows(dp, F32).T, _mxu_rows(dp, F32)

    @pl.when((pl.program_id(0) == 0) & (pl.program_id(1) == 0))
    def _():
        dw_ref[...] = jnp.zeros(dw_ref.shape, F32)

    for i in range(n):
        through_ref[...] = _dot(lead, w_ref[i], ((1,), (0,)))  # (tt, C)
        dw_ref[i] += _unsplit(_dot(dp_rows, x_ref[0, i], ((1,), (0,))), m, split)

        def outer(rows, i=i):
            cols = cols_ref[rows, :]
            pre_i, dss2 = _column(cols, i), _column(cols, n)

            def chunk(lanes, _):
                dx = (pre_i * dh_ref[0, rows, lanes].astype(F32)
                      + through_ref[rows, lanes]
                      + dss2 * x_ref[0, i, rows, lanes].astype(F32)
                      + add_ref[0, i, rows, lanes].astype(F32))
                dx_ref[0, i, rows, lanes] = dx.astype(dx_ref.dtype)

            _chunks(C, chunk)

        _slabs(outer)


def pre_bwd(X, dh, W, alpha_pre, pre, p, ss, dp_rest, dss_rest, dX_add,
            norm_eps: float):
    """``(dX (B, n, T, C), dHpre (B, n, T), dW (n, C, m))``: ``dHpre_i = <dh,
    X_i>``; with ``dz = dHpre Hpre (1 - Hpre) alpha_pre`` and ``r = rsqrt(ss
    / (n C) + norm_eps)``, ``dp = [dz r | 0] + dp_rest`` and ``dss = dss_rest
    - r^3 <dz, p[:n]> / (2 n C)`` - ``dp_rest (B, m, T)`` (zero on its first
    ``n`` rows) and ``dss_rest (B, T)`` are what reaches ``p`` and ``ss``
    from ``Hpost`` and ``Hres`` -; then ``dX_i = Hpre_i dh + dp W_i^T + 2 dss
    X_i`` (``W`` in the stream's dtype: float32 ``(n, C, m)``, or bfloat16
    ``(n, C, 3 m)``, the split's parts ``hi | mid | hi`` that meet ``dp``'s
    ``hi | hi | mid``) ``+ dX_add_i`` (the stream's other cotangent, summed
    in float32 before the one rounding) and ``dW_i = X_i^T dp`` over all
    tokens."""
    B, n, T, C = X.shape
    m = 2 * n + n * n
    rows = 3 * m + 8
    small = jnp.concatenate([
        jnp.pad(pre.astype(F32), ((0, 0), (0, m - n), (0, 0))), p, dp_rest,
        ss[:, None], dss_rest[:, None], jnp.zeros((B, 6, T), F32)], axis=1)
    dX, dpre, dW = _call(
        "ddl_hc_pre_bwd",
        functools.partial(_pre_bwd_kernel, n=n, C=C, m=m, norm_eps=norm_eps),
        (B, T // TILE),
        [_stream_spec(n, C), _row_spec(C), _small_spec(rows),
         _whole_spec((n, _LANES, C)), _whole_spec((m, _LANES)), _stream_spec(n, C)],
        [_stream_spec(n, C), _small_spec(n), _whole_spec((n, m, C))],
        [jax.ShapeDtypeStruct(X.shape, X.dtype),
         jax.ShapeDtypeStruct((B, n, T), F32),
         jax.ShapeDtypeStruct((n, m, C), F32)],
        scratch=[pltpu.VMEM((TILE, _LANES), F32), pltpu.VMEM((TILE, C), F32)],
        semantics=("arbitrary", "arbitrary"),
    )(X, dh, small, _weight_rows(W),
      _gate_rows(alpha_pre, jnp.zeros((n,), F32), m), dX_add)
    return dX, dpre, jnp.swapaxes(dW, 1, 2)
