"""Manifold-constrained hyper-connections (mHC): a residual path ``n``
streams wide, read and written by every sub-block through learned,
input-dependent mixing matrices, the stream-to-stream one projected onto the
doubly stochastic matrices by Sinkhorn-Knopp.

Per token, with the stream ``X`` (n x C) and a sub-block ``F``::

    xb    = RMSNorm_nC(vec(X))                               float32 from here
    Hpre  = sigmoid(alpha_pre * (xb phi_pre) + b_pre)        (n,)
    Hpost = 2 sigmoid(alpha_post * (xb phi_post) + b_post)   (n,)
    Z     = clip(alpha_res * mat(xb phi_res) + b_res, lo, hi)
    M     = exp(Z);  iters x:  M /= colsum(M) + eps;  M /= rowsum(M) + eps
    Hres  = M                                                (n, n)
    h = Hpre X;   y = F(h);   X' = Hres X + Hpost^T y

One WRAP is the two routines around ``F``, each ONE ``custom_vjp`` whose
passes are jitted by name (a traced step's passes can be counted).  A pass
over the stream is one kernel of ``ops/hyper_connections.py`` where the
stream's shape takes it (:func:`_takes_kernels`: ``C`` whole lanes, ``T``
whole token tiles) and XLA's fusions anywhere else - the plain form below,
which the kernels are held to; the shape decides, nothing else does:

- :func:`hc_pre` ``(X, wrap, hc) -> (h, Hpost, Hres, X)``: the stream is
  handed on as the fourth result for :func:`hc_post` to take, so that it has
  ONE consumer and its two cotangents - the second half's and this half's
  own - meet inside ``_hc_pre_bwd``'s one pass, where autodiff would add
  them in a pass of its own over three streams.  The pass over the
  stream makes the small outputs - the norm's sum of squares and the ``2 n +
  n^2`` projections, 25 float32 numbers a token - and ``h = Hpre X``:
  ``_hc_pre_fwd`` (the kernel ``ddl_hc_pre_fwd``: one read, ``Hpre`` - a
  sigmoid of ``n`` of those numbers - made inside it) or ``_hc_project`` and
  ``_hc_read`` (two reads).  ``_hc_matrices`` makes the matrices of the 25
  numbers on either path (no pass over the stream: the Sinkhorn rounds on
  lanes full of tokens; beside the kernel its ``Hpre`` is not used, so that
  forward and backward agree on the kernel's own).  The 25 numbers are tagged
  :data:`ddl_tpu.models.remat.HC` where they are made: kept, a
  rematerialised wrap reads the stream once, for ``h`` - the kernel again, or
  ``_hc_read`` alone - and what the kind saves beside the kernel is the
  matrices' input (the matrices are made again from them; keeping the
  MATRICES by name sends XLA's compile of the step past 30 GiB of host memory
  - PERF.md section 7, PR 46 - so they are not what is tagged).
  ``_hc_pre_bwd`` differentiates the matrices' arithmetic (the rounds again,
  XLA's on the small arrays on either path) and writes the stream's
  cotangent in one pass: the kernel ``ddl_hc_pre_bwd`` takes what reaches
  the 25 numbers from ``Hpost`` and ``Hres``, makes ``dHpre = <dh, X_i>``
  and what hangs on it itself, and sums the weights' cotangent over the
  token grid in a resident block.
- :func:`hc_post` ``(X, y, Hpost, Hres) -> X'``: ``_hc_post_fwd`` and
  ``_hc_post_bwd``, one pass each: ``ddl_hc_post_fwd`` / ``ddl_hc_post_bwd``
  or XLA's fusions.

Layouts, chosen for the chip's (8, 128) tiles: the stream is ``(B, n, T,
C)`` - a stream's rows are whole ``(T, C)`` planes; ``(B, T, n, C)`` would
pad 4 rows to a tile's 16 - and the matrices are TOKEN-LAST, ``Hpost (B, n,
T)`` and ``Hres (B, n, n, T)``: the Sinkhorn rounds are then elementwise
over lanes full of tokens, where ``(B, T, n, n)`` would fill 16 of a tile's
1,024 places.

No float32 copy of the stream is written: the stream stays in its dtype in
HBM and is widened inside the fusions that read it.  The float32
projections ``xb phi`` are matmuls over the bfloat16 stream itself with the
float32 weights split three ways (:func:`_split3`: three bfloat16 matrices
whose sum is the weight to 24 bits, side by side in one matmul of ``3 (2 n
+ n^2)`` = 72 columns, which the MXU pads to 128 either way): the products
are exact and accumulate in float32, at a sixth of the passes a float32
matmul at ``highest`` takes.  The cotangents' matmuls (24 deep, or over the
tokens) take bfloat16 operands likewise, the cotangent split.

The kernels keep all of that - the float32 arithmetic, the split products,
the token-last small arrays, the two ``custom_vjp``s and their residuals -
and read a tile's four rows from HBM once where XLA's fusions read the
stream two to four times a pass and copy ``jnp.stack``'s rows (PERF.md
section 6, PR 50).
"""

from __future__ import annotations

import functools
import math
import operator
from typing import Any, Dict, List, NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ddl_tpu.models import decoder as _decoder
from ddl_tpu.models import remat as _remat
from ddl_tpu.ops import hyper_connections as _kernels

Params = Dict[str, Any]
F32 = jnp.float32


class HyperConnections(NamedTuple):
    """What a published config states of the path (``hc_mult``,
    ``hc_sinkhorn_iters``, ``hc_eps``, ``mhc_h_res_clamp_min`` / ``_max``)
    and the norm's ``rms_norm_eps``."""

    n: int = 4
    iters: int = 20
    eps: float = 1e-6
    clamp: Tuple[float, float] = (-30.0, 30.0)
    norm_eps: float = 1e-6


def wrap_rows(prefix: str, n: int, d: int) -> List[_decoder.Row]:
    """One wrap's parameters under ``prefix`` (float32 storage: 0.36 M
    numbers at 4 x 3,584, and the equations are float32): seeded ``phi``,
    ``alpha`` 0.01, ``b_pre = -ln(n - 1)`` (``Hpre = 1/n``), ``b_post = 0``
    (``Hpost = 1``), ``b_res = 4 I`` - a fresh wrap is near the plain
    residual ``x + F(x / n ...)`` (each stream a quarter of the read)."""
    rep, nd = P(None), n * d

    def row(name, shape, **kw):
        return _decoder.Row(f"{prefix}.{name}", shape, P(*(None,) * len(shape)),
                            dtype=F32, **kw)

    return [
        _decoder.Row(f"{prefix}.norm", (nd,), rep, fill=1.0, dtype=F32),
        row("phi_pre", (nd, n)), row("phi_post", (nd, n)), row("phi_res", (nd, n * n)),
        row("alpha_pre", (), fill=0.01), row("alpha_post", (), fill=0.01),
        row("alpha_res", (), fill=0.01),
        row("b_pre", (n,), fill=-math.log(n - 1.0)), row("b_post", (n,), fill=0.0),
        row("b_res", (n, n), draw=lambda key, shape: 4.0 * jnp.eye(n, dtype=F32)),
    ]


# -- the residual path's two ends ------------------------------------------------------


def open_stream(x: jax.Array, n: int) -> jax.Array:
    """(B, T, C) -> (B, n, T, C): the embedded row replicated."""
    return jnp.broadcast_to(x[:, None], (x.shape[0], n) + x.shape[1:])


def close_stream(X: jax.Array) -> jax.Array:
    """(B, n, T, C) -> (B, T, C): the streams' sum (in float32)."""
    return jnp.sum(X, axis=1, dtype=F32).astype(X.dtype)


# -- the matrices' arithmetic: 2 n + n^2 + 1 numbers a token, token-last -------------


def _add(parts):
    return functools.reduce(operator.add, parts)


def sinkhorn(M: jax.Array, iters: int, eps: float) -> jax.Array:
    """``iters`` rounds of column then row normalisation of ``M (B, n, n,
    T)`` (rows on axis 1, columns on axis 2), float32.  The sums are written
    out as adds, so a round is elementwise and the rounds fuse."""
    n = M.shape[1]
    for _ in range(iters):
        M = M / (_add([M[:, i : i + 1] for i in range(n)]) + eps)
        M = M / (_add([M[:, :, j : j + 1] for j in range(n)]) + eps)
    return M


def _gate(z_pre: jax.Array, wrap: Params) -> jax.Array:
    """``Hpre (B, n, T)`` of its ``n`` normed projections."""
    return jax.nn.sigmoid(
        wrap["alpha_pre"] * z_pre + wrap["b_pre"].astype(F32)[None, :, None])


def matrices(z: jax.Array, wrap: Params, hc: HyperConnections):
    """``(Hpre (B, n, T), Hpost (B, n, T), Hres (B, n, n, T))`` float32 from
    the normed stream's projections ``z = xb [phi_pre | phi_post | phi_res]``
    (B, 2 n + n^2, T)."""
    n = hc.n
    B, _, T = z.shape
    col = lambda b: b.astype(F32)[None, :, None]
    pre = _gate(z[:, :n], wrap)
    post = 2.0 * jax.nn.sigmoid(
        wrap["alpha_post"] * z[:, n : 2 * n] + col(wrap["b_post"]))
    Z = wrap["alpha_res"] * z[:, 2 * n :].reshape(B, n, n, T) + (
        wrap["b_res"].astype(F32)[None, :, :, None])
    res = sinkhorn(jnp.exp(jnp.clip(Z, *hc.clamp)), hc.iters, hc.eps)
    return pre, post, res


def _normed(p: jax.Array, ss: jax.Array, hc: HyperConnections, width: int):
    """``z = xb phi`` from the FOLDED projections ``p = vec(X) (norm * phi)``
    (B, m, T) and the stream's sum of squares ``ss`` (B, T), ``width = n C``
    numbers a token: the norm's division happens here, on 24 numbers a token."""
    return p * jax.lax.rsqrt(ss / width + hc.norm_eps)[:, None]


def _mix(p: jax.Array, ss: jax.Array, wrap: Params, hc: HyperConnections, width: int):
    """:func:`matrices` of :func:`_normed`."""
    return matrices(_normed(p, ss, hc, width), wrap, hc)


# -- the passes over the stream ----------------------------------------------------------


def _split3(w: jax.Array, axis: int) -> jax.Array:
    """A float32 array as three bfloat16 ones whose sum is it to 24 bits,
    side by side on ``axis``.  ``reduce_precision`` and not a pair of
    converts, which XLA removes on a TPU."""
    parts = []
    for _ in range(3):
        part = jax.lax.reduce_precision(w, 8, 7)
        parts.append(part.astype(jnp.bfloat16))
        w = w - part
    return jnp.concatenate(parts, axis=axis)


def _unsplit3(y: jax.Array, axis: int) -> jax.Array:
    return _add(jnp.split(y, 3, axis=axis))


def _dot16(spec: str, a: jax.Array, b: jax.Array) -> jax.Array:
    """``einsum(spec, a, b)`` of bfloat16 operands accumulated in float32.
    XLA:CPU has no such matmul: there the operands are widened (exactly)
    and multiplied at ``highest`` - the same products, the same sums."""
    if jax.default_backend() == "cpu":
        return jnp.einsum(spec, a.astype(F32), b.astype(F32),
                          precision=jax.lax.Precision.HIGHEST)
    return jnp.einsum(spec, a, b, preferred_element_type=F32)


def _phi(wrap: Params) -> jax.Array:
    """``[phi_pre | phi_post | phi_res]`` (n C, 2 n + n^2) float32."""
    return jnp.concatenate(
        [wrap["phi_pre"], wrap["phi_post"], wrap["phi_res"]], axis=-1).astype(F32)


def _folded(wrap: Params, n: int, C: int) -> jax.Array:
    """``norm * phi`` as (n, C, 2 n + n^2) float32: the norm's weight rides
    the projections."""
    return (wrap["norm"].astype(F32)[:, None] * _phi(wrap)).reshape(n, C, -1)


def _project(X: jax.Array, W: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """``(p (B, m, T), ss (B, T))`` float32 of the stream ``X (B, n, T, C)``
    and the folded weights ``W (n, C, m)``: a matmul a stream (a contraction
    over n and C at once would transpose the stream)."""
    n = X.shape[1]
    if X.dtype == jnp.bfloat16:
        W3 = _split3(W, axis=-1)
        p = _unsplit3(_add([
            _dot16("btc,cm->bmt", X[:, i], W3[i]) for i in range(n)]), axis=1)
    else:
        p = _add([
            jnp.einsum("btc,cm->bmt", X[:, i].astype(F32), W[i],
                       precision=jax.lax.Precision.HIGHEST) for i in range(n)])
    ss = _add([jnp.sum(jnp.square(X[:, i].astype(F32)), axis=-1) for i in range(n)])
    return p, ss


def _takes_kernels(X: jax.Array) -> bool:
    """The shape rule: a stream of whole lanes and whole token tiles takes
    the ``ddl_hc_*`` kernels, any other XLA's passes below - the plain form
    the kernels are held to."""
    return _kernels.takes(X.shape, X.dtype)


def _per_token(a: jax.Array) -> jax.Array:
    """(B, T) -> (B, T, 1): a token's number against its row of C."""
    return a[..., None]


@jax.jit
def _hc_project(X: jax.Array, wrap: Params):
    """The pass over the stream in front of the matrices: ``(p (B, 2 n + n^2,
    T), ss (B, T))``, the folded projections and the sum of squares - the 25
    float32 numbers a token the matrices are a function of."""
    B, n, T, C = X.shape
    return _project(X, _folded(wrap, n, C))


@functools.partial(jax.jit, static_argnames=("hc", "width"))
def _hc_matrices(p: jax.Array, ss: jax.Array, wrap: Params,
                 hc: HyperConnections, width: int):
    """``(Hpre, Hpost, Hres)`` from 25 numbers a token: no pass over the
    stream."""
    return _mix(p, ss, wrap, hc, width)


@functools.partial(jax.jit, static_argnames=("hc",))
def _hc_pre_fwd(X: jax.Array, wrap: Params, hc: HyperConnections):
    """``ddl_hc_pre_fwd``: ``(h, p, ss, Hpre)`` in one read of the stream."""
    B, n, T, C = X.shape
    W = _folded(wrap, n, C)
    if X.dtype == jnp.bfloat16:
        W = _split3(W, axis=-1)
    return _kernels.pre_fwd(X, W, wrap["alpha_pre"], wrap["b_pre"], hc.norm_eps)


@jax.jit
def _hc_read(X: jax.Array, pre: jax.Array) -> jax.Array:
    """``h = Hpre X`` (B, T, C): one read of the stream."""
    n = X.shape[1]
    return _add([
        _per_token(pre[:, i]) * X[:, i].astype(F32) for i in range(n)
    ]).astype(X.dtype)


@functools.partial(jax.jit, static_argnames=("hc",))
def _hc_pre_bwd(X, wrap, pre, p, ss, dh, dpost, dres, dthrough,
                hc: HyperConnections):
    """Cotangents of :func:`hc_pre`'s operands: ``dHpre = <dh, X_i>``, the
    matrices' arithmetic differentiated on its 25 numbers a token, then ``dX_i
    = Hpre_i dh + dp (norm phi)_i^T + 2 dss X_i`` in one pass and the weights'
    cotangent ``X_i^T dp`` over the tokens.  ``dthrough``: the cotangent of
    the stream :func:`hc_pre` handed on, added to ``dX`` in the same pass."""
    B, n, T, C = X.shape
    W = _folded(wrap, n, C)
    small = _small(wrap)
    _, pull = jax.vjp(lambda p_, ss_, w_: _mix(p_, ss_, w_, hc, n * C), p, ss, small)
    if _takes_kernels(X):
        # ``ddl_hc_pre_bwd``.  The rounds' backward, which hangs on dHpost and
        # dHres alone, in front of it; dHpre reaches ``p`` and ``ss`` inside it
        # and ``alpha_pre`` / ``b_pre`` behind it, through the gate alone.
        dp, dss, dsmall = pull((jnp.zeros_like(pre), dpost.astype(F32), dres.astype(F32)))
        back = _back3(W) if X.dtype == jnp.bfloat16 else W
        dX, dpre, dW = _kernels.pre_bwd(
            X, dh, back, wrap["alpha_pre"], pre, p, ss, dp, dss, dthrough, hc.norm_eps)
        _, pull_gate = jax.vjp(
            lambda w_: _gate(_normed(p, ss, hc, n * C)[:, :n], w_), small)
        dsmall = jax.tree.map(operator.add, dsmall, pull_gate(dpre)[0])
    else:
        dX, dW, dsmall = _pre_bwd_passes(X, W, pre, dh, pull, dpost, dres, dthrough)
    # W = norm[:, None] * phi
    dW = dW.reshape(n * C, -1)
    dphi = wrap["norm"].astype(F32)[:, None] * dW
    dwrap = dict(dsmall)
    dwrap["norm"] = jnp.sum(dW * _phi(wrap), axis=-1)
    dwrap["phi_pre"], dwrap["phi_post"], dwrap["phi_res"] = (
        dphi[:, :n], dphi[:, n : 2 * n], dphi[:, 2 * n :])
    return dX, {k: dwrap[k].astype(wrap[k].dtype) for k in wrap}


def _back3(W: jax.Array) -> jax.Array:
    """The split weights ``hi | mid | hi`` (n, C, 3 m) bfloat16 that meet a
    cotangent's ``hi | hi | mid``: ``(dp_hi + dp_mid + dp_lo) (W_hi + W_mid +
    W_lo)^T`` to 16 bits, the three products a bfloat16 cotangent can tell
    apart."""
    m = W.shape[-1]
    W3 = _split3(W, axis=-1)
    return jnp.concatenate([W3[..., : 2 * m], W3[..., :m]], axis=-1)


def _pre_bwd_passes(X, W, pre, dh, pull, dpost, dres, dthrough):
    """XLA's passes of :func:`_hc_pre_bwd`: ``(dX, dW (n, C, m), the small
    leaves' cotangents)``."""
    n = X.shape[1]
    dhf = dh.astype(F32)
    dpre = jnp.stack(
        [jnp.sum(dhf * X[:, i].astype(F32), axis=-1) for i in range(n)], axis=1)
    dp, dss, dsmall = pull((dpre, dpost.astype(F32), dres.astype(F32)))
    if X.dtype == jnp.bfloat16:
        # dp W_i^T, 24 deep, and X_i^T dp over the tokens: bfloat16 operands,
        # the float32 side split three ways.
        dp3 = _split3(dp, axis=1)
        m = W.shape[-1]
        lead = jnp.concatenate([dp3[:, :m], dp3[:, :m], dp3[:, m : 2 * m]], axis=1)
        back = _back3(W)
        through = [_dot16("bmt,cm->btc", lead, back[i]) for i in range(n)]
        dW = jnp.stack([
            _unsplit3(_dot16("btc,bmt->cm", X[:, i], dp3), axis=-1)
            for i in range(n)])
    else:
        hi = jax.lax.Precision.HIGHEST
        through = [jnp.einsum("bmt,cm->btc", dp, W[i], precision=hi) for i in range(n)]
        dW = jnp.stack([
            jnp.einsum("btc,bmt->cm", X[:, i].astype(F32), dp, precision=hi)
            for i in range(n)])
    dX = jnp.stack([
        (_per_token(pre[:, i]) * dhf + through[i]
         + _per_token(2.0 * dss) * X[:, i].astype(F32)
         + dthrough[:, i].astype(F32)).astype(X.dtype)
        for i in range(n)], axis=1)
    return dX, dW, dsmall


def _small(wrap: Params) -> Params:
    """What the matrices' arithmetic reads of a wrap: ``alpha`` and ``b``."""
    return {k: v for k, v in wrap.items() if not k.startswith(("phi", "norm"))}


def _pre(X, wrap, hc, tag):
    B, n, T, C = X.shape
    if _takes_kernels(X):
        # Hpre is the kernel's own, here and in the backward's residuals
        h, p, ss, pre = _hc_pre_fwd(X, wrap, hc)
        p, ss = tag(p), tag(ss)
        _, post, res = _hc_matrices(p, ss, _small(wrap), hc, n * C)
    else:
        p, ss = (tag(v) for v in _hc_project(X, wrap))
        pre, post, res = _hc_matrices(p, ss, _small(wrap), hc, n * C)
        h = _hc_read(X, pre)
    return (h, post, res, X), (X, wrap, pre, p, ss)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def hc_pre(X: jax.Array, wrap: Params, hc: HyperConnections):
    """The wrap's first half: ``(h (B, T, C), Hpost (B, n, T) float32, Hres
    (B, n, n, T) float32, X)`` of the stream ``X (B, n, T, C)`` - the stream
    handed on for :func:`hc_post` to take in its place."""
    return _pre(X, wrap, hc, lambda v: v)[0]


def _hc_pre_fwd_rule(X, wrap, hc):
    return _pre(X, wrap, hc, lambda v: _remat.tag(v, _remat.HC))


def _hc_pre_bwd_rule(hc, res, cts):
    return _hc_pre_bwd(*res, *cts, hc)


hc_pre.defvjp(_hc_pre_fwd_rule, _hc_pre_bwd_rule)


@jax.jit
def _hc_post_fwd(X, y, post, res):
    """``X'_i = sum_j Hres[i, j] X_j + Hpost_i y``: one read of the stream
    and of ``y``, one write."""
    if _takes_kernels(X):
        return _kernels.post_fwd(X, y, post, res)
    n = X.shape[1]
    yf = y.astype(F32)
    rows = [X[:, j].astype(F32) for j in range(n)]
    return jnp.stack([
        (_add([_per_token(res[:, i, j]) * rows[j] for j in range(n)])
         + _per_token(post[:, i]) * yf).astype(X.dtype)
        for i in range(n)], axis=1)


@jax.jit
def _hc_post_bwd(X, y, post, res, dXn):
    """``dX_j = sum_i Hres[i, j] dX'_i``, ``dy = sum_i Hpost_i dX'_i``,
    ``dHres[i, j] = <dX'_i, X_j>``, ``dHpost_i = <dX'_i, y>``."""
    if _takes_kernels(X):
        return _kernels.post_bwd(X, y, post, res, dXn)
    n = X.shape[1]
    yf = y.astype(F32)
    rows = [X[:, j].astype(F32) for j in range(n)]
    drows = [dXn[:, i].astype(F32) for i in range(n)]
    dX = jnp.stack([
        _add([_per_token(res[:, i, j]) * drows[i] for i in range(n)]).astype(X.dtype)
        for j in range(n)], axis=1)
    dy = _add([_per_token(post[:, i]) * drows[i] for i in range(n)]).astype(y.dtype)
    dpost = jnp.stack([jnp.sum(drows[i] * yf, axis=-1) for i in range(n)], axis=1)
    dres = jnp.stack([
        jnp.stack([jnp.sum(drows[i] * rows[j], axis=-1) for j in range(n)], axis=1)
        for i in range(n)], axis=1)
    return dX, dy, dpost, dres


@jax.custom_vjp
def hc_post(X: jax.Array, y: jax.Array, post: jax.Array, res: jax.Array) -> jax.Array:
    """The wrap's second half: ``X' = Hres X + Hpost^T y`` (B, n, T, C)."""
    return _hc_post_fwd(X, y, post, res)


def _hc_post_fwd_rule(X, y, post, res):
    return _hc_post_fwd(X, y, post, res), (X, y, post, res)


def _hc_post_bwd_rule(res, dXn):
    return _hc_post_bwd(*res, dXn)


hc_post.defvjp(_hc_post_fwd_rule, _hc_post_bwd_rule)
