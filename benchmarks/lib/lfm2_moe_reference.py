"""Plain float32 reference of the LFM2-MoE decoder (``model_type:
lfm2_moe``, ``LiquidAI/LFM2-24B-A2B``): forward, train loss and gradients in
``jax.numpy`` under ``jax.default_matmul_precision("highest")``, importing
nothing from ``ddl_tpu``: no kernel, no ``custom_vjp``, no remat but the
``checkpoint_layers`` a caller asks for.  ``tests/reference_lfm2_moe.py`` and
``benchmarks/lib/lfm2_moe_reference.py`` are one file twice (a tier-1 test
holds them byte-equal): the benchmark carries its own copy.

On a TPU run :func:`forward` EAGERLY, not under ``jax.jit`` (PERF.md section
7, found by PR 36 (a): a float32 ``jax.numpy`` program of a whole layer at
16,384 positions came out wrong on the chip as ONE XLA program and right a
stage a program).  So every stage of a layer (:func:`conv_mixer`,
:func:`attn_qkv`, :func:`_attention`, :func:`_attn_out`, :func:`_swiglu`,
:func:`expert_mlp`, :func:`_add`, :func:`_pre_norm`, :func:`_head`) is jitted
here: called eagerly each is a program of its own whose result is its
output; under a caller's ``jit`` or ``grad`` they are inlined and change
nothing.

The equations (the catalog row's ``config.json`` keys and the ``lfm2_moe``
family's module as remembered: there is no network here; what the keys do
not state is an ASSUMED comment below and an ``assumed`` entry of the
benchmark's configuration file):

- ``x = E[tokens]`` (no embedding scale); block: ``x = x + Mix(RMSNorm(x;
  operator_norm))``; ``x = x + FFN(RMSNorm(x; ffn_norm))``: pre-norm
  residuals, no sandwich norm.  ``RMSNorm(x; w) = x / sqrt(mean(x^2) +
  norm_eps) * w``.
- ``conv`` mixer (the gated short convolution): ``[B | C | u] = h W_in``
  (ASSUMED: that order); ``a = B * u``; ``c_t = sum_j w_j a_{t-(K-1)+j}`` per
  channel, ``K = conv_L_cache``, ``a`` before the row's start zero,
  ``conv_bias`` false, no activation (ASSUMED) - written as shifted adds;
  ``out = (C * c) W_out``.  No positions.
- ``full_attention`` mixer: ``q = h Wq`` as ``n_heads`` x ``head_dim``,
  ``k, v = h Wk, h Wv`` as ``n_kv_heads`` x ``head_dim`` (``head_dim =
  hidden / n_heads``); ``q = RMSNorm(q; q_norm)``, ``k = RMSNorm(k;
  k_norm)`` over a head's width, one learned weight each, BEFORE the
  rotation (ASSUMED); rotate-half RoPE, ``theta`` 1e6, on q and k over the
  whole head; scores ``q . k / sqrt(head_dim)``, key ``j`` visible to query
  ``i`` iff ``j <= i``, each key head serving ``n_heads / n_kv_heads`` query
  heads; ``out = concat(softmax(s) v) Wo``; a dense masked softmax a block
  of query rows at a time.
- dense FFN (``layer < n_dense_layers``): ``(silu(h Wgate) * (h Wup)) Wdown``.
- expert FFN: ``s = sigmoid(h Wr)``; ``sel = top_k(s + expert_bias)`` (the
  bias in the selection only); ``w = s[sel] / (sum(s[sel]) + route_eps) *
  route_scale`` (``norm_topk_prob``; ASSUMED: 1e-6); ``sum_k w_k
  Expert_sel_k(h)``, each expert a SwiGLU.  No shared expert.
- final RMSNorm, TIED head (ASSUMED): ``logits = RMSNorm(x; final_norm)
  E^T`` over the embedding's own rows; next-token cross-entropy.

The share: ``held = (first, count)`` of the router's ``n_experts``.  The
parameters hold those experts only; every token goes through every HELD
expert under a mask of the router's choices, and a choice of an expert held
elsewhere adds nothing.  With ``(0, n_experts)`` it is the uncut layer.  A
sliced vocabulary is a smaller vocabulary: the embedding has the slice's
rows, and so has the head.

Parameter layout (``ddl_tpu/models/lfm2_moe.py``'s): ``embed`` (V, D),
``final_norm`` (D,), NO ``lm_head``; per layer ``operator_norm``,
``ffn_norm`` (D,); a conv layer ``w_in`` (D, 3 D), ``conv`` (K, D), ``w_out``
(D, D); an attention layer ``wq`` (D, H d), ``wk``, ``wv`` (D, Hkv d),
``wo`` (H d, D), ``q_norm``, ``k_norm`` (d,); a dense layer ``w_gate``,
``w_up`` (D, F), ``w_down`` (F, D); an expert layer ``w_router`` (D, E),
``expert_bias`` (E,) and ``experts``, SwiGLU stacks with a leading ``count``
axis.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Params = Dict[str, Any]


class Config(NamedTuple):
    n_heads: int
    n_kv_heads: int
    n_experts: int  # the router's width
    topk: int
    #: One flag a layer: True for ``conv``.
    conv_layers: Tuple[bool, ...]
    n_dense_layers: int
    held: Tuple[int, int]  # (first, count) of the experts in the parameters
    route_norm: bool = True
    route_scale: float = 1.0
    route_eps: float = 1e-6
    rope_theta: float = 1e6
    norm_eps: float = 1e-5
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


def _conv(a, taps):
    """Causal depthwise convolution as shifted adds: ``c_t = sum_j taps[K -
    1 - j] a_{t-j}``; ``a`` (B, T, C), ``taps`` (K, C), zeros before the row."""
    K = taps.shape[0]
    c = a * taps[K - 1]
    for j in range(1, K):
        shifted = jnp.concatenate(
            [jnp.zeros_like(a[:, :j]), a[:, : a.shape[1] - j]], axis=1
        )
        c = c + shifted * taps[K - 1 - j]
    return c


def _rope(x, theta):
    """Rotate-half rotary embedding over the whole head: ``x`` (B, T, H, d),
    positions 0..T-1; pair ``i`` = ``(x[i], x[i + d/2])`` turns by ``pos *
    theta^(-2i/d)``."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-np.arange(half, dtype=np.float32) / half)
    angles = np.arange(x.shape[1], dtype=np.float32)[:, None] * inv_freq[None, :]
    cos = jnp.asarray(np.cos(angles))[None, :, None, :]
    sin = jnp.asarray(np.sin(angles))[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


@functools.partial(jax.jit, static_argnames=("eps", "r"))
def _pre_norm(x, w, eps, r):
    return r(_norm(x, w, eps))


@functools.partial(jax.jit, static_argnames=("r",))
def _add(x, out, r):
    return r(x + r(out))


@functools.partial(jax.jit, static_argnames=("r",))
def conv_mixer(h, layer, r=_same):
    """The gated short convolution on normalised hidden states ``h`` (B, T,
    D): ``(C * conv(B * u)) W_out``."""
    D = h.shape[-1]
    bcx = r(h @ r(layer["w_in"]))
    # ASSUMED: the projection's thirds are B, C, u in that order.
    b, c_gate, u = bcx[..., :D], bcx[..., D : 2 * D], bcx[..., 2 * D :]
    # ASSUMED: no activation on the convolution; conv_bias false.
    y = r(c_gate * _conv(b * u, layer["conv"].astype(jnp.float32)))
    return y @ r(layer["w_out"])


@functools.partial(jax.jit, static_argnames=("c", "r"))
def attn_qkv(h, layer, c: Config, r=_same):
    """q (B, T, H, d) and k, v (B, T, Hkv, d) of an attention layer."""
    B, T, D = h.shape
    d = D // c.n_heads
    heads = lambda y, n: y.reshape(B, T, n, d)
    # ASSUMED: per-head QK RMSNorm (one d-long weight each) before RoPE.
    q = _norm(heads(h @ r(layer["wq"]), c.n_heads), layer["q_norm"], c.norm_eps)
    k = _norm(heads(h @ r(layer["wk"]), c.n_kv_heads), layer["k_norm"], c.norm_eps)
    v = heads(h @ r(layer["wv"]), c.n_kv_heads)
    return r(_rope(q, c.rope_theta)), r(_rope(k, c.rope_theta)), r(v)


@functools.partial(jax.jit, static_argnames=("block", "checkpoint_blocks"))
def _attention(q, k, v, block, checkpoint_blocks=False):
    """Causal softmax attention of q (B, T, H, d) over k, v (B, T, Hkv, d),
    each key head serving ``H / Hkv`` query heads, a block of queries at a
    time against every key (``jax.lax.map`` over the blocks: one block's
    scores alive at a time)."""
    B, T, H, d = q.shape
    rep = H // k.shape[2]
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)

    def one_block(q_block, first, k, v):
        i = first + jnp.arange(q_block.shape[1])[:, None]
        j = jnp.arange(T)[None, :]
        s = jnp.einsum("bqhd,bkhd->bhqk", q_block, k) / np.sqrt(d)
        s = jnp.where((j <= i)[None, None], s, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)

    if checkpoint_blocks:
        one_block = jax.checkpoint(
            one_block, policy=jax.checkpoint_policies.nothing_saveable
        )
    if T % block:  # a ragged last block: one block after another, unrolled
        out = [
            one_block(q[:, lo : lo + block], lo, k, v) for lo in range(0, T, block)
        ]
        return jnp.concatenate(out, axis=1)
    blocks = (
        jnp.moveaxis(q.reshape(B, T // block, block, H, d), 1, 0),
        jnp.arange(0, T, block),
    )
    out = jax.lax.map(lambda b: one_block(b[0], b[1], k, v), blocks)
    return jnp.moveaxis(out, 0, 1).reshape(B, T, H, d)


@functools.partial(jax.jit, static_argnames=("r",))
def _attn_out(attn, layer, r):
    return r(attn.reshape(attn.shape[:2] + (-1,))) @ r(layer["wo"])


def _attn_mixer(h, layer, c: Config, r):
    q, k, v = attn_qkv(h, layer, c, r)
    return _attn_out(_attention(q, k, v, c.query_block, c.checkpoint_layers), layer, r)


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
    """An expert layer's FFN on flat tokens ``h`` (N, D): (the held
    experts' part of the routed sum, the router's choices (N, k) out of all
    ``n_experts``).  No shared expert."""
    # DEPARTURE: the published module may round the router's logits to the
    # model's dtype before the float32 sigmoid; here both are float32.
    scores = jax.nn.sigmoid(h @ r(layer["w_router"]))
    # ASSUMED: expert_bias stays at its initial zeros (use_expert_bias moves
    # it outside the gradient and config.json gives no rule).  It enters
    # the selection only, so its gradient is zero.
    _, top_e = jax.lax.top_k(
        scores + jax.lax.stop_gradient(layer["expert_bias"]), c.topk
    )
    top_w = jnp.take_along_axis(scores, top_e, axis=-1)
    if c.route_norm:
        top_w = top_w / (jnp.sum(top_w, axis=-1, keepdims=True) + c.route_eps)
    top_w = top_w * c.route_scale
    first, count = c.held
    if (first, count) != (0, c.n_experts):
        # DEPARTURE: a share's router is not trained.  The experts held
        # elsewhere add nothing here, so the share's part of the router's
        # gradient only says "route to them"; the deployment's other chips
        # balance it, one chip's share cannot.  The weights are constants
        # of the backward pass, as expert_bias is.
        top_w = jax.lax.stop_gradient(top_w)
    chosen = jax.nn.one_hot(top_e, c.n_experts, dtype=jnp.float32)  # (N, k, E)
    gates = jnp.einsum("nk,nke->ne", top_w, chosen)
    # The share: the held experts' columns; a choice of an expert held
    # elsewhere adds nothing here.
    return _experts(h, layer["experts"], gates[:, first : first + count], r), top_e


def _layer(x, layer, c: Config, r, conv: bool, dense: bool):
    B, T, D = x.shape
    h = _pre_norm(x, layer["operator_norm"], c.norm_eps, r)
    x = _add(x, conv_mixer(h, layer, r) if conv else _attn_mixer(h, layer, c, r), r)
    h = _pre_norm(x, layer["ffn_norm"], c.norm_eps, r).reshape(B * T, D)
    if dense:
        out, top_e = _swiglu(h, layer, r), None
    else:
        out, top_e = expert_mlp(h, layer, c, r)
        top_e = top_e.reshape(B, T, c.topk)
    return _add(x, out.reshape(B, T, D), r), top_e


@functools.partial(jax.jit, static_argnames=("eps", "r"))
def _head(x, final_norm, embed, eps, r):
    # ASSUMED: tied embeddings - the head is the embedding's own rows.
    return r(_norm(x, final_norm, eps)) @ r(embed).astype(jnp.float32).T


def forward(params: Params, tokens, c: Config,
            compute_dtype: Optional[Any] = None,
            layer_fn=None) -> Tuple[jax.Array, jax.Array]:
    """(logits (B, T, V), the routers' choices (L_expert, B, T, k) out of
    all ``n_experts``).  ``layer_fn`` stands in for :func:`_layer` (its
    arguments, its results): a caller's way to order one layer's part of
    a backward pass; whatever it is given as a layer's parameters is handed
    on as it stands."""
    r = _rounder(compute_dtype)
    with jax.default_matmul_precision("highest"):
        # float32 from here on, whatever dtype the weights are stored in
        x = r(params["embed"])[tokens].astype(jnp.float32)
        picks = []
        layer_fn = layer_fn or _layer
        if c.checkpoint_layers:
            layer_fn = jax.checkpoint(
                layer_fn, static_argnums=(2, 3, 4, 5),
                policy=jax.checkpoint_policies.nothing_saveable,
            )
        for li, (layer, conv) in enumerate(zip(params["layers"], c.conv_layers)):
            x, top_e = layer_fn(x, layer, c, r, conv, li < c.n_dense_layers)
            if top_e is not None:
                picks.append(top_e)
        logits = _head(x, params["final_norm"], params["embed"], c.norm_eps, r)
    picks = jnp.stack(picks) if picks else jnp.zeros(
        (0,) + tokens.shape + (c.topk,), jnp.int32
    )
    return logits, picks


def cross_entropy(logits, tokens):
    """Mean next-token cross-entropy: position t predicts token t+1; the
    last position predicts nothing."""
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    return -jnp.mean(picked)


def loss(params: Params, tokens, c: Config,
         compute_dtype: Optional[Any] = None, layer_fn=None) -> jax.Array:
    # ASSUMED: no auxiliary router loss (the published recipe balances
    # through the selection bias, not through the loss).
    logits, _ = forward(params, tokens, c, compute_dtype, layer_fn)
    return cross_entropy(logits, tokens)


def loss_and_grads(params: Params, tokens, c: Config):
    return jax.value_and_grad(loss)(params, tokens, c)
