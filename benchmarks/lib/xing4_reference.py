"""Plain float32 reference of the Xing4.0 decoder (``model_type: xing4_0``,
``XingChen-AGI/Xing4.0-29B-A4B``): forward, the two-term train loss and
gradients in ``jax.numpy`` under ``jax.default_matmul_precision("highest")``,
importing nothing from ``ddl_tpu``: no kernel, no ``custom_vjp``, no remat but
the ``checkpoint_layers`` a caller asks for.  ``tests/reference_xing4.py`` and
``benchmarks/lib/xing4_reference.py`` are one file twice (a tier-1 test holds
them byte-equal): the benchmark carries its own copy.

On a TPU run :func:`forward` EAGERLY, not under ``jax.jit`` (PERF.md section
7, found by PR 36 (a): a float32 ``jax.numpy`` program of a whole layer at
16,384 positions came out wrong on the chip as ONE XLA program and right a
stage a program).  So every stage of a layer is jitted here: called eagerly
each is a program of its own whose result is its output; under a caller's
``jit`` or ``grad`` they are inlined and change nothing.

The equations (the catalog row's ``config.json`` keys, the hyper-connections
papers and the ``deepseek_v3`` family's module as remembered: there is no
network here; what the keys do not state is an ASSUMED comment below and an
``assumed`` entry of the benchmark's configuration file).  Per token, ``n =
hc_mult`` streams ``X`` (n x C):

- open / close: ``X_0[i] = E[token]`` for every ``i`` (ASSUMED: the embedded
  row replicated); behind the last layer ``x_L = sum_i X_L[i]`` (ASSUMED);
  ``logits = RMSNorm(x_L; final_norm) W_head``, untied.
- a wrap around a sub-block ``F`` (two a layer, parameters of its own each),
  in float32: ``xb = RMSNorm(vec(X); norm)`` over all ``n C`` numbers;
  ``Hpre = sigmoid(alpha_pre (xb phi_pre) + b_pre)`` (n);
  ``Hpost = 2 sigmoid(alpha_post (xb phi_post) + b_post)`` (n);
  ``Z = clip(alpha_res mat(xb phi_res) + b_res, clamp)`` (n x n, row-major);
  ``M = exp(Z)``, then ``hc_sinkhorn_iters`` rounds of ``M <- M / (colsum(M) +
  hc_eps)``, ``M <- M / (rowsum(M) + hc_eps)`` (ASSUMED: the clamp on ``Z``,
  ``hc_eps`` in both denominators, column before row); ``Hres = M``.
  ``h = Hpre X``; ``y = F(h)``; ``X' = Hres X + Hpost^T y``.
- ``F`` of the first wrap: latent attention on ``RMSNorm(h; attn_norm)``:
  ``q = RMSNorm(h Wq_a; q_a_norm) Wq_b`` per head ``[q_nope | q_rope]``;
  ``[c | k_r] = h Wkv_a``, ``c = RMSNorm(c; kv_a_norm)``, ``[k_nope | v] = c
  Wkv_b`` per head; RoPE on adjacent pairs (ASSUMED: ``rope_interleave``) of
  ``q_rope`` and of the one ``k_r`` a position all heads share, at YaRN's
  frequencies (:func:`yarn_inv_freq`), cos and sin times ``m(mscale) /
  m(mscale_all_dim)``; scores ``(q_nope . k_nope + q_rope . k_r) / sqrt(nope
  + rope) * m(mscale_all_dim)^2``, ``m(x) = 0.1 x ln(factor) + 1``; causal
  softmax; ``concat(softmax v) Wo``.
- ``F`` of the second wrap on ``RMSNorm(h; mlp_norm)``: a dense SwiGLU
  (``layer < n_dense_layers``) or ``s = sigmoid(h Wr)``, ``sel = top_k(s +
  expert_bias)``, ``w = s[sel] / (sum + 1e-20) * routed_scaling_factor``,
  ``sum_k w_k Expert_sel_k(h) + Shared(h)``, the shared expert ungated.
- the multi-token-prediction module (DeepSeek-V3's section 2.2): ``h'_i =
  [RMSNorm(E[t_(i+1)]; enorm) | RMSNorm(x_L,i; hnorm)] W_eh`` (ASSUMED: that
  order; the closed stream BEFORE the final norm), one routed layer of the
  model's kind on a stream of its own (opened by replication, closed by the
  sum), ``logits'_i = RMSNorm(.; mtp.norm) W_head`` with the model's own
  embedding and head, target ``t_(i+2)``, ``i = 0 .. T-3``.  Train loss
  ``CE_main + mtp_weight CE_mtp`` (ASSUMED: 0.1).

The share: ``held = (first, count)`` of the router's ``n_experts``, as
``reference_deepseek_v3.py`` has it.

Parameter layout (``ddl_tpu/models/xing4.py``'s): ``embed`` (V, C),
``final_norm``, ``lm_head`` (C, V); a layer: ``attn_norm``, ``mlp_norm``,
``wq_a`` (C, r), ``q_a_norm``, ``wq_b`` (r, H (nope + rope)), ``wkv_a`` (C, rank +
rope), ``kv_a_norm``, ``wkv_b`` (rank, H (nope + v)), ``wo``; a dense layer
``w_gate``, ``w_up``, ``w_down``; a routed one ``w_router`` (C, E),
``expert_bias``, ``shared`` and ``experts`` (SwiGLU stacks with a leading
``count`` axis); ``hc_attn`` and ``hc_mlp``, each ``norm`` (n C), ``phi_pre``,
``phi_post`` (n C, n), ``phi_res`` (n C, n n), ``alpha_pre``, ``alpha_post``,
``alpha_res`` (), ``b_pre``, ``b_post`` (n), ``b_res`` (n, n), a token's ``vec(X)``
stream after stream; ``mtp``: ``enorm``, ``hnorm``, ``norm``, ``w_eh`` (2 C, C),
``layer`` (a routed layer's).  The stream here is ``(B, T, n, C)``.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Params = Dict[str, Any]


class Config(NamedTuple):
    n_heads: int
    qk_nope_dim: int
    qk_rope_dim: int
    v_head_dim: int
    kv_lora_rank: int
    n_experts: int  # the router's width
    topk: int
    n_dense_layers: int
    held: Tuple[int, int]  # (first, count) of the experts in the parameters
    #: (factor, original_max_position_embeddings, beta_fast, beta_slow,
    #: mscale, mscale_all_dim), or ``None``: plain RoPE.
    yarn: Optional[Tuple[float, int, float, float, float, float]] = None
    route_norm: bool = True
    route_scale: float = 2.0
    rope_theta: float = 1e4
    norm_eps: float = 1e-6
    hc_mult: int = 4
    hc_iters: int = 20
    hc_eps: float = 1e-6
    hc_clamp: Tuple[float, float] = (-30.0, 30.0)
    n_mtp: int = 1
    mtp_weight: float = 0.1
    #: Queries per block of the masked-softmax attention.
    query_block: int = 256
    #: ``jax.checkpoint`` around each layer and around each query block of
    #: its attention: the same arithmetic, with one layer's intermediates
    #: and one block's scores alive at a time in a backward pass.
    checkpoint_layers: bool = False


def _same(a):
    return a


@functools.lru_cache(maxsize=None)
def _rounder(compute_dtype: Optional[Any]):
    """Identity for the float32 reference.  With a ``compute_dtype`` every
    matmul operand and every block's result is rounded to it and brought
    back to float32: the reference "computed in" that precision, for
    finding out whether a tolerance would let a lower precision pass.  One
    function a precision: the jitted stages take it as a static argument."""
    if compute_dtype is None:
        return _same

    def rounded(a):
        return a.astype(compute_dtype).astype(jnp.float32)

    return rounded


def _norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


# -- YaRN ------------------------------------------------------------------------------


def yarn_mscale(factor: float, x: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * x * math.log(factor) + 1.0


def yarn_inv_freq(theta: float, R: int, yarn) -> np.ndarray:
    """The ``R / 2`` frequencies: ``f_i = theta^(-2i/R)``; ``r_i = clip((i -
    low) / (high - low), 0, 1)``, ``low = floor(c(beta_fast))``, ``high =
    ceil(c(beta_slow))`` clamped to ``[0, R - 1]``, ``c(b) = R ln(original / (2
    pi b)) / (2 ln theta)``; ``inv_freq_i = f_i ((1 - r_i) + r_i / factor)``."""
    factor, original, beta_fast, beta_slow = yarn[:4]
    c = lambda b: R * math.log(original / (2 * math.pi * b)) / (2 * math.log(theta))
    low, high = max(math.floor(c(beta_fast)), 0), min(math.ceil(c(beta_slow)), R - 1)
    out = []
    for i in range(R // 2):
        f = theta ** (-2.0 * i / R)
        ramp = min(max((i - low) / max(high - low, 1e-3), 0.0), 1.0)
        out.append(f * ((1.0 - ramp) + ramp / factor))
    return np.asarray(out, np.float32)


def score_scale(c: Config) -> float:
    """``1 / sqrt(nope + rope)``, times ``m(mscale_all_dim)^2`` under YaRN
    where ``mscale_all_dim`` is set."""
    scale = 1.0 / math.sqrt(c.qk_nope_dim + c.qk_rope_dim)
    if c.yarn is not None and c.yarn[5]:
        scale *= yarn_mscale(c.yarn[0], c.yarn[5]) ** 2
    return scale


def _rope(x, c: Config):
    """Rotary embedding on adjacent pairs (ASSUMED: ``rope_interleave``):
    pair ``i`` = ``(x[2i], x[2i+1])`` turns by ``pos * inv_freq_i``; ``x``:
    (B, T, H, R), positions 0..T-1."""
    R = x.shape[-1]
    if c.yarn is None:
        inv_freq = c.rope_theta ** (-np.arange(0, R, 2, dtype=np.float32) / R)
        factor = 1.0
    else:
        inv_freq = yarn_inv_freq(c.rope_theta, R, c.yarn)
        factor = yarn_mscale(c.yarn[0], c.yarn[4]) / yarn_mscale(c.yarn[0], c.yarn[5])
    angles = np.arange(x.shape[1], dtype=np.float32)[:, None] * inv_freq[None, :]
    cos = jnp.asarray(np.cos(angles) * factor)[None, :, None, :]
    sin = jnp.asarray(np.sin(angles) * factor)[None, :, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    turned = jnp.stack([even * cos - odd * sin, even * sin + odd * cos], axis=-1)
    return turned.reshape(x.shape)


# -- a wrap ------------------------------------------------------------------------------


def sinkhorn(M, iters: int, eps: float):
    """``M`` (..., n, n): column then row normalisation, ``iters`` rounds."""
    for _ in range(iters):
        M = M / (jnp.sum(M, axis=-2, keepdims=True) + eps)  # a column's sum
        M = M / (jnp.sum(M, axis=-1, keepdims=True) + eps)  # a row's sum
    return M


@functools.partial(jax.jit, static_argnames=("c",))
def wrap_matrices(X, wrap, c: Config):
    """``(Hpre (B, T, n), Hpost (B, T, n), Hres (B, T, n, n))`` of the stream
    ``X (B, T, n, C)``, float32 whatever else is rounded."""
    B, T, n, C = X.shape
    f32 = lambda a: a.astype(jnp.float32)
    xb = _norm(X.reshape(B, T, n * C), f32(wrap["norm"]), c.norm_eps)
    pre = jax.nn.sigmoid(
        f32(wrap["alpha_pre"]) * (xb @ f32(wrap["phi_pre"])) + f32(wrap["b_pre"]))
    post = 2.0 * jax.nn.sigmoid(
        f32(wrap["alpha_post"]) * (xb @ f32(wrap["phi_post"])) + f32(wrap["b_post"]))
    Z = f32(wrap["alpha_res"]) * (xb @ f32(wrap["phi_res"])).reshape(B, T, n, n) + (
        f32(wrap["b_res"]))
    # ASSUMED: the clamp sits on Z, hc_eps in both denominators.
    M = jnp.exp(jnp.clip(Z, c.hc_clamp[0], c.hc_clamp[1]))
    return pre, post, sinkhorn(M, c.hc_iters, c.hc_eps)


@functools.partial(jax.jit, static_argnames=("r",))
def wrap_read(X, pre, r):
    """``h = Hpre X`` (B, T, C)."""
    return r(jnp.einsum("bti,btic->btc", pre, X))


@functools.partial(jax.jit, static_argnames=("r",))
def wrap_write(X, y, post, res, r):
    """``X' = Hres X + Hpost^T y`` (B, T, n, C)."""
    return r(jnp.einsum("btij,btjc->btic", res, X) + post[..., None] * r(y)[:, :, None])


# -- the sub-blocks ------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("eps", "r"))
def _pre_norm(x, w, eps, r):
    return r(_norm(x, w, eps))


@functools.partial(jax.jit, static_argnames=("c", "r"))
def latent_qkv(h, layer, c: Config, r=_same):
    """``(q_nope, q_rope (B, T, H, .), k_nope, k_rope (B, T, 1, R), v)`` of
    latent attention on normalised hidden states ``h`` (B, T, C)."""
    B, T, _ = h.shape
    H, nope, rank = c.n_heads, c.qk_nope_dim, c.kv_lora_rank
    q_a = r(_norm(h @ r(layer["wq_a"]), layer["q_a_norm"], c.norm_eps))
    q = (q_a @ r(layer["wq_b"])).reshape(B, T, H, nope + c.qk_rope_dim)
    kv_a = h @ r(layer["wkv_a"])
    latent = r(_norm(kv_a[..., :rank], layer["kv_a_norm"], c.norm_eps))
    kv = (latent @ r(layer["wkv_b"])).reshape(B, T, H, nope + c.v_head_dim)
    # One rotary key a position, for every head.
    return (r(q[..., :nope]), r(_rope(q[..., nope:], c)), r(kv[..., :nope]),
            r(_rope(kv_a[:, :, None, rank:], c)), r(kv[..., nope:]))


@functools.partial(jax.jit, static_argnames=("scale", "block", "checkpoint_blocks"))
def _attention(q, q_r, k, k_r, v, scale, block, checkpoint_blocks=False):
    """Causal softmax attention with the score's two products, a block of
    queries at a time against every key (``jax.lax.map`` over the blocks:
    one block's scores alive at a time)."""
    B, T, H, _ = q.shape

    def one_block(q_block, qr_block, first, k, k_r, v):
        i = first + jnp.arange(q_block.shape[1])[:, None]
        j = jnp.arange(T)[None, :]
        s = (jnp.einsum("bqhd,bkhd->bhqk", q_block, k)
             + jnp.einsum("bqhr,bkr->bhqk", qr_block, k_r[:, :, 0])) * scale
        s = jnp.where((j <= i)[None, None], s, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)

    if checkpoint_blocks:
        one_block = jax.checkpoint(
            one_block, policy=jax.checkpoint_policies.nothing_saveable
        )
    if T % block:  # a ragged last block: one block after another, unrolled
        out = [
            one_block(q[:, lo : lo + block], q_r[:, lo : lo + block], lo, k, k_r, v)
            for lo in range(0, T, block)
        ]
        return jnp.concatenate(out, axis=1)
    split = lambda a: jnp.moveaxis(
        a.reshape(B, T // block, block, H, a.shape[-1]), 1, 0)
    out = jax.lax.map(
        lambda b: one_block(b[0], b[1], b[2], k, k_r, v),
        (split(q), split(q_r), jnp.arange(0, T, block)),
    )
    return jnp.moveaxis(out, 0, 1).reshape(B, T, H, v.shape[-1])


@functools.partial(jax.jit, static_argnames=("r",))
def _attn_out(attn, layer, r):
    return r(attn.reshape(attn.shape[:2] + (-1,))) @ r(layer["wo"])


def _swiglu_of(h, w, r):
    return r(jax.nn.silu(h @ r(w["w_gate"])) * (h @ r(w["w_up"]))) @ r(w["w_down"])


@functools.partial(jax.jit, static_argnames=("r",))
def _swiglu(h, w, r):
    return _swiglu_of(h, w, r)


def _experts(h, experts, gates, r):
    """``sum_e gates[:, e] * expert_e(h)`` over the held experts: every
    token through every one of them, one expert at a time."""

    def one(acc, expert):
        w, gate = expert
        return acc + gate[:, None] * _swiglu_of(h, w, r), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(h), (experts, gates.T))
    return out


@functools.partial(jax.jit, static_argnames=("c", "r"))
def expert_mlp(h, layer, c: Config, r=_same):
    """A routed layer's FFN on flat tokens ``h`` (N, C): (the shared expert's
    output + the held experts' part of the routed sum, the router's choices
    (N, k) out of all ``n_experts``)."""
    # DEPARTURE: the published module may round the router's logits to the
    # model's dtype before the float32 sigmoid; here both are float32.
    scores = jax.nn.sigmoid(h @ r(layer["w_router"]))
    # ASSUMED: expert_bias stays at its initial zeros (noaux_tc moves it
    # outside the gradient and config.json gives no rule).  It enters the
    # selection only, so its gradient is zero.
    _, top_e = jax.lax.top_k(
        scores + jax.lax.stop_gradient(layer["expert_bias"]), c.topk
    )
    top_w = jnp.take_along_axis(scores, top_e, axis=-1)
    if c.route_norm:
        top_w = top_w / (jnp.sum(top_w, axis=-1, keepdims=True) + 1e-20)
    top_w = top_w * c.route_scale
    first, count = c.held
    if (first, count) != (0, c.n_experts):
        # DEPARTURE: a share's router is not trained (reference_deepseek_v3.py).
        top_w = jax.lax.stop_gradient(top_w)
    chosen = jax.nn.one_hot(top_e, c.n_experts, dtype=jnp.float32)  # (N, k, E)
    gates = jnp.einsum("nk,nke->ne", top_w, chosen)
    routed = _experts(h, layer["experts"], gates[:, first : first + count], r)
    return _swiglu_of(h, layer["shared"], r) + routed, top_e  # shared: ungated


def _layer(X, layer, c: Config, r, dense: bool):
    """One layer on the stream ``X (B, T, n, C)`` -> (X, the router's choices
    (B, T, k) or ``None``)."""
    B, T, n, C = X.shape
    pre, post, res = wrap_matrices(X, layer["hc_attn"], c)
    h = _pre_norm(wrap_read(X, pre, r), layer["attn_norm"], c.norm_eps, r)
    attn = _attention(
        *latent_qkv(h, layer, c, r), score_scale(c), c.query_block, c.checkpoint_layers)
    X = wrap_write(X, _attn_out(attn, layer, r), post, res, r)

    pre, post, res = wrap_matrices(X, layer["hc_mlp"], c)
    h = _pre_norm(wrap_read(X, pre, r), layer["mlp_norm"], c.norm_eps, r)
    h = h.reshape(B * T, C)
    if dense:
        out, top_e = _swiglu(h, layer, r), None
    else:
        out, top_e = expert_mlp(h, layer, c, r)
        top_e = top_e.reshape(B, T, c.topk)
    return wrap_write(X, out.reshape(B, T, C), post, res, r), top_e


@functools.partial(jax.jit, static_argnames=("eps", "r"))
def _head(x, norm, head, eps, r):
    return r(_norm(x, norm, eps)) @ r(head).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("eps", "r"))
def _mtp_input(ahead, x, mtp, eps, r):
    # ASSUMED: the embedding's half first; the order only permutes W_eh's rows.
    both = jnp.concatenate(
        [_norm(ahead, mtp["enorm"], eps), _norm(x, mtp["hnorm"], eps)], axis=-1)
    return r(r(both) @ r(mtp["w_eh"]))


def _open(x, n):
    # ASSUMED: every stream starts as the embedded row.
    return jnp.broadcast_to(x[:, :, None], x.shape[:2] + (n,) + x.shape[2:])


def _tail(x, params, tokens, c: Config, r, own):
    """The head, and the multi-token-prediction module, on the closed
    un-normed stream ``x`` (B, T, C): (logits, the module's logits (B, T - 1,
    V) or ``None``, the module's layer's choices (B, T, k) - its missing last
    position -1 - or ``None``).  ``own`` runs the module's layer."""
    logits = _head(x, params["final_norm"], params["lm_head"], c.norm_eps, r)
    if not c.n_mtp:
        return logits, None, None
    mtp = params["mtp"]
    embed = r(params["embed"]).astype(jnp.float32)
    # positions 0 .. T-2: the next token's embedding beside this position's
    # closed, un-normed stream
    h = _mtp_input(embed[tokens[:, 1:]], x[:, :-1], mtp, c.norm_eps, r)
    X, top_e = own(_open(h, c.hc_mult), mtp["layer"], c, r, False)
    mtp_logits = _head(
        jnp.sum(X, axis=2), mtp["norm"], params["lm_head"], c.norm_eps, r)
    return logits, mtp_logits, jnp.pad(
        top_e, ((0, 0), (0, 1), (0, 0)), constant_values=-1)


def forward_all(params: Params, tokens, c: Config,
                compute_dtype: Optional[Any] = None, layer_fn=None):
    """(logits (B, T, V), the module's logits (B, T - 1, V) - position ``i``
    predicts ``t_(i+2)``; ``None`` without a module -, the routers' choices
    (L_routed, B, T, k), out of all ``n_experts``: the stack's, and with a
    module its layer's last, its missing last position filled with -1).
    ``layer_fn`` stands in for :func:`_layer` in the STACK (its arguments, its
    results): a caller's way to order one layer's part of a backward pass;
    whatever it is given as a layer's parameters is handed on as it stands.
    The module's layer is always :func:`_layer`."""
    r = _rounder(compute_dtype)
    own = _layer
    with jax.default_matmul_precision("highest"):
        # float32 from here on, whatever dtype the weights are stored in
        embed = r(params["embed"]).astype(jnp.float32)
        layer_fn = layer_fn or _layer
        if c.checkpoint_layers:
            wrapped = lambda f: jax.checkpoint(
                f, static_argnums=(2, 3, 4),
                policy=jax.checkpoint_policies.nothing_saveable)
            layer_fn, own = wrapped(layer_fn), wrapped(own)
        X = _open(embed[tokens], c.hc_mult)
        picks = []
        for li, layer in enumerate(params["layers"]):
            X, top_e = layer_fn(X, layer, c, r, li < c.n_dense_layers)
            if top_e is not None:
                picks.append(top_e)
        x = jnp.sum(X, axis=2)  # ASSUMED: the streams' sum closes the path
        logits, mtp_logits, top_e = _tail(x, params, tokens, c, r, own)
        if top_e is not None:
            picks.append(top_e)
    picks = jnp.stack(picks) if picks else jnp.zeros(
        (0,) + tokens.shape + (c.topk,), jnp.int32
    )
    return logits, mtp_logits, picks


def forward(params: Params, tokens, c: Config,
            compute_dtype: Optional[Any] = None, layer_fn=None):
    """(logits, the routers' choices): :func:`forward_all` without the
    module's logits."""
    logits, _, picks = forward_all(params, tokens, c, compute_dtype, layer_fn)
    return logits, picks


def cross_entropy(logits, tokens):
    """Mean next-token cross-entropy: position t predicts token t+1; the
    last position predicts nothing."""
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    return -jnp.mean(picked)


def mtp_cross_entropy(mtp_logits, tokens):
    """Mean cross-entropy of the module: its position ``i`` (of ``T - 1``)
    predicts token ``i + 2``; positions ``0 .. T-3`` have one."""
    logp = jax.nn.log_softmax(mtp_logits[:, :-1], axis=-1)
    picked = jnp.take_along_axis(logp, tokens[:, 2:, None], axis=-1)[..., 0]
    return -jnp.mean(picked)


def losses(params: Params, tokens, c: Config,
           compute_dtype: Optional[Any] = None, layer_fn=None):
    """(``CE_main``, ``CE_mtp``; the second 0 without a module)."""
    logits, mtp_logits, _ = forward_all(params, tokens, c, compute_dtype, layer_fn)
    main = cross_entropy(logits, tokens)
    if mtp_logits is None:
        return main, jnp.zeros((), jnp.float32)
    return main, mtp_cross_entropy(mtp_logits, tokens)


def loss(params: Params, tokens, c: Config,
         compute_dtype: Optional[Any] = None, layer_fn=None) -> jax.Array:
    # ASSUMED: the module's weight (Config.mtp_weight); no auxiliary router
    # loss (noaux_tc balances through the selection bias).
    main, mtp = losses(params, tokens, c, compute_dtype, layer_fn)
    return main + c.mtp_weight * mtp


def loss_and_grads(params: Params, tokens, c: Config):
    return jax.value_and_grad(loss)(params, tokens, c)


# -- the same gradients, a layer a program -------------------------------------------


@functools.partial(jax.jit, static_argnames=("c", "dense"))
def _layer_forward(X, layer, c: Config, dense: bool):
    with jax.default_matmul_precision("highest"):
        return _layer(X, layer, c, _same, dense)[0]


@functools.partial(jax.jit, static_argnames=("c", "dense"))
def _layer_pullback(X, layer, dX, c: Config, dense: bool):
    """(d loss / d the layer's input, d loss / d its parameters) from the
    cotangent of its output: the layer's forward pass again, then back."""
    with jax.default_matmul_precision("highest"):
        _, pull = jax.vjp(lambda X_, w: _layer(X_, w, c, _same, dense)[0], X, layer)
        return pull(dX)


@functools.partial(jax.jit, static_argnames=("c",))
def _tail_pullback(x, top, tokens, c: Config):
    """(loss, d loss / d the closed stream, d loss / d every parameter beside
    the stack) of the head and the module."""

    def of(x, top):
        logits, mtp_logits, _ = _tail(x, top, tokens, c, _same, _layer)
        main = cross_entropy(logits, tokens)
        if mtp_logits is None:
            return main
        return main + c.mtp_weight * mtp_cross_entropy(mtp_logits, tokens)

    with jax.default_matmul_precision("highest"):
        value, (dx, dtop) = jax.value_and_grad(of, argnums=(0, 1))(x, top)
    return value, dx, dtop


@jax.jit
def _lookup_pullback(dX, tokens, like):
    """The embedding's gradient through the stack's lookup: the opened
    stream's cotangent summed over the streams, added at the tokens' rows."""
    return jnp.zeros(like.shape, jnp.float32).at[tokens].add(jnp.sum(dX, axis=2))


def loss_and_grads_by_layer(params: Params, tokens, c: Config, consume):
    """:func:`loss_and_grads`' loss, and its gradients handed out a part at a
    time: ``consume(("layers", i), grads)`` for every layer, last to first, and
    ``consume(("top",), grads)`` for everything beside the stack (the module
    among it) - the same numbers as ``jax.grad(loss)``, a tier-1 test holds
    them equal.  Run EAGERLY: a layer's forward pass, its pullback and the
    tail are a program each, so no program is the whole model's backward pass
    (whose compile took the chip's host past its memory at the benchmark's
    size) and no more than one part's gradients are alive."""
    dense = lambda li: li < c.n_dense_layers
    top = {k: v for k, v in params.items() if k != "layers"}
    embed = params["embed"].astype(jnp.float32)
    X, inputs = _open(embed[tokens], c.hc_mult), []
    for li, layer in enumerate(params["layers"]):
        inputs.append(X)
        X = _layer_forward(X, layer, c, dense(li))
    value, dx, dtop = _tail_pullback(jnp.sum(X, axis=2), top, tokens, c)
    dX = _open(dx, c.hc_mult)  # the closing sum hands every stream the same
    for li in reversed(range(len(inputs))):
        dX, grads = _layer_pullback(inputs.pop(), params["layers"][li], dX, c, dense(li))
        consume(("layers", li), grads)
    dtop["embed"] = dtop["embed"] + _lookup_pullback(dX, tokens, embed)
    consume(("top",), dtop)
    return value
