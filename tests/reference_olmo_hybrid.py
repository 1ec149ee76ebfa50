"""Plain float32 reference of the Olmo-Hybrid decoder (``model_type:
olmo_hybrid``, ``allenai/Olmo-Hybrid-7B``): forward, train loss and
gradients in ``jax.numpy`` under ``jax.default_matmul_precision("highest")``,
importing nothing from ``ddl_tpu``.  ``tests/reference_olmo_hybrid.py`` and
``benchmarks/lib/olmo_hybrid_reference.py`` are one file twice (a tier-1
test holds them byte-equal): the benchmark carries its own copy.

On a TPU run :func:`forward` EAGERLY, not under ``jax.jit``: as one XLA
program of 16,384 positions (jax 0.9.0's libtpu, a v5e) a linear layer's
mixer comes out 30-60% off for some twenty positions behind every multiple
of 1,024 - against numpy on the host - unless the scan's output is also an
output of the program; the scan as a program of its own, and what follows
it as another, are right to 3e-5 (found on the chip, PR 36: PERF.md section
6).  So every stage of a layer (:func:`linear_inputs`, :func:`recurrence`,
:func:`_linear_out`, :func:`_full_qkv`, :func:`_attention`,
:func:`_full_out`, :func:`_swiglu`, :func:`_add_norm`, :func:`_head`) is
jitted here: called eagerly each is a program of its own whose result is
its output, and its intermediates are the program's, not arrays of their
own; under a caller's ``jit`` or ``grad`` they are inlined and change
nothing.

The equations (the catalog row's ``config.json`` keys, Gated DeltaNet as
arXiv:2412.06464 states it; what ``config.json`` does not state is an
ASSUMED comment below and an ``assumed`` entry of the benchmark's
configuration file):

- ``x = E[tokens]``; block (ASSUMED: OLMo-2/3's norm placement, no norm in
  front of a block, one on its output): ``x = x + RMSNorm(Mixer(x))``;
  ``x = x + RMSNorm(SwiGLU(x))``; ``h = x`` below.
- ``linear_attention``, ``H`` heads of ``d_k`` keys and ``d_v`` values:
  ``q~ = SiLU(conv(h Wq))``, ``k~ = SiLU(conv(h Wk))``, ``v = SiLU(conv(h
  Wv))``, ``conv`` causal and depthwise over ``conv_kernel`` positions
  (ASSUMED: no bias), written as shifted adds; ``q = q~ / ||q~|| /
  sqrt(d_k)``, ``k = k~ / ||k~||`` per head (``||.||^2 + 1e-6`` under the
  root); ``beta_t = 2 sigmoid(h_t Wb)`` (the 2 is
  ``linear_allow_neg_eigval``); ``g_t = -exp(A_log) softplus(h_t Wa +
  dt_bias)``, ``alpha_t = exp(g_t)``; then position by position, a plain
  ``lax.scan`` with the state ``S`` (d_v, d_k) from zero::

      S_t = alpha_t S_{t-1} + beta_t (v_t - alpha_t S_{t-1} k_t) k_t^T
      o_t = S_t q_t

  ``y_t = RMSNorm_{d_v}(o_t) * SiLU(h_t Wg)``; ``out = y Wo``.
- ``full_attention``: ``q = RMSNorm(h Wq)``, ``k = RMSNorm(h Wk)`` over the
  whole projection with a learned weight (ASSUMED: the OLMo-2/3
  convention), ``v = h Wv``, heads of ``d_model / n_heads``; scores ``q . k
  / sqrt(head_dim)``, key ``j`` visible to query ``i`` iff ``j <= i``, NO
  position encoding (``rope_theta`` null; ASSUMED); ``out = concat(softmax(s)
  v) Wo``; a dense masked softmax a block of query rows at a time.
- final RMSNorm, untied head, next-token cross-entropy.  A sliced
  vocabulary is a smaller vocabulary: embedding and head have its rows.

Parameter layout (``ddl_tpu/models/olmo_hybrid.py``'s): ``embed`` (V, D),
``final_norm`` (D,), ``lm_head`` (D, V); per layer ``post_attn_norm``,
``post_mlp_norm`` (D,), ``w_gate``, ``w_up`` (D, F), ``w_down`` (F, D); a
linear layer ``wq``, ``wk`` (D, H d_k), ``wv``, ``wg`` (D, H d_v), ``wa``,
``wb`` (D, H), ``wo`` (H d_v, D), ``conv_q``, ``conv_k`` (K, H d_k),
``conv_v`` (K, H d_v), ``A_log``, ``dt_bias`` (H,), ``o_norm`` (d_v,); a
full layer ``wq``, ``wk``, ``wv``, ``wo`` (D, D), ``q_norm``, ``k_norm``
(D,).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Params = Dict[str, Any]


class Config(NamedTuple):
    n_heads: int  # the full-attention layers'
    n_linear_heads: int
    key_dim: int
    value_dim: int
    #: One flag a layer: True for ``linear_attention``.
    linear_layers: Tuple[bool, ...]
    allow_neg_eigval: bool = True
    norm_eps: float = 1e-6
    #: Queries per block of the masked-softmax attention.
    query_block: int = 256
    #: Positions per block of the recurrence's scan (the arithmetic is one
    #: scan over positions whatever this is).
    scan_block: int = 64
    #: ``jax.checkpoint`` around each layer, each query block of attention
    #: and each block of the recurrence's positions: the same arithmetic
    #: with one layer's intermediates, one block's scores and one block's
    #: states alive at a time in a backward pass.
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


def _conv(x, taps):
    """Causal depthwise convolution as shifted adds: ``y_t = sum_j taps[K -
    1 - j] x_{t-j}``; ``x`` (B, T, C), ``taps`` (K, C), zeros before the row."""
    K = taps.shape[0]
    y = x * taps[K - 1]
    for j in range(1, K):
        shifted = jnp.concatenate(
            [jnp.zeros_like(x[:, :j]), x[:, : x.shape[1] - j]], axis=1
        )
        y = y + shifted * taps[K - 1 - j]
    return y


def _unit(x):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


@functools.partial(jax.jit, static_argnames=("block", "checkpoint_blocks"))
def recurrence(q, k, v, g, beta, block=64, checkpoint_blocks=False):
    """The gated delta rule position by position: ``q``, ``k`` (B, T, H,
    d_k), ``v`` (B, T, H, d_v), ``g``, ``beta`` (B, T, H) -> (B, T, H,
    d_v).  One ``lax.scan`` over positions inside one over blocks of them
    (a backward pass then keeps a state a block, not a state a position)."""
    B, T, H, dk = q.shape
    dv = v.shape[-1]

    def step(S, x):
        q, k, v, g, b = x  # (B, H, ...)
        S = jnp.exp(g)[..., None, None] * S
        err = v - jnp.einsum("bhvk,bhk->bhv", S, k)
        S = S + b[..., None, None] * err[..., :, None] * k[..., None, :]
        return S, jnp.einsum("bhvk,bhk->bhv", S, q)

    def run_block(S, xs):
        return jax.lax.scan(step, S, xs)

    if checkpoint_blocks:
        run_block = jax.checkpoint(
            run_block, policy=jax.checkpoint_policies.nothing_saveable
        )
    pad = -T % block
    xs = []
    for x in (q, k, v, g, beta):
        # a padded step has beta = 0 and g = 0: it leaves the state alone
        x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        x = jnp.moveaxis(x, 1, 0)
        xs.append(x.reshape(((T + pad) // block, block) + x.shape[1:]))
    _, o = jax.lax.scan(run_block, jnp.zeros((B, H, dv, dk), jnp.float32), tuple(xs))
    return jnp.moveaxis(o.reshape((T + pad,) + o.shape[2:]), 0, 1)[:, :T]


@functools.partial(jax.jit, static_argnames=("block", "checkpoint_blocks"))
def _attention(q, k, v, block, checkpoint_blocks=False):
    """Causal softmax attention (B, T, H, D), a block of queries at a time
    against every key (``jax.lax.map`` over the blocks: one block's scores
    alive at a time)."""
    B, T, H, width = q.shape

    def one_block(q_block, first, k, v):
        i = first + jnp.arange(q_block.shape[1])[:, None]
        j = jnp.arange(T)[None, :]
        s = jnp.einsum("bqhd,bkhd->bhqk", q_block, k) / np.sqrt(width)
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
        jnp.moveaxis(q.reshape(B, T // block, block, H, width), 1, 0),
        jnp.arange(0, T, block),
    )
    out = jax.lax.map(lambda b: one_block(b[0], b[1], k, v), blocks)
    return jnp.moveaxis(out, 0, 1).reshape(B, T, H, width)


@functools.partial(jax.jit, static_argnames=("r",))
def _swiglu(h, w, r):
    return r(jax.nn.silu(h @ r(w["w_gate"])) * (h @ r(w["w_up"]))) @ r(w["w_down"])


@functools.partial(jax.jit, static_argnames=("c", "r"))
def linear_inputs(h, layer, c: Config, r=_same):
    """What the recurrence takes, from hidden states ``h`` (B, T, D):
    (q, k (B, T, H, d_k), v (B, T, H, d_v), g, beta (B, T, H))."""
    B, T, _ = h.shape
    H, dk, dv = c.n_linear_heads, c.key_dim, c.value_dim

    def mixed(w, taps, width):
        y = jax.nn.silu(_conv(r(h @ r(layer[w])), layer[taps]))
        return y.reshape(B, T, H, width)

    q = _unit(mixed("wq", "conv_q", dk)) / np.sqrt(dk)
    k = _unit(mixed("wk", "conv_k", dk))
    v = mixed("wv", "conv_v", dv)
    beta = jax.nn.sigmoid(h @ r(layer["wb"]))
    if c.allow_neg_eigval:
        beta = 2.0 * beta
    g = -jnp.exp(layer["A_log"]) * jax.nn.softplus(h @ r(layer["wa"]) + layer["dt_bias"])
    return r(q), r(k), r(v), g, beta


@functools.partial(jax.jit, static_argnames=("c", "r"))
def _linear_out(h, o, layer, c: Config, r):
    gate = jax.nn.silu(h @ r(layer["wg"])).reshape(o.shape)
    y = r(_norm(o, layer["o_norm"], c.norm_eps) * gate)
    return y.reshape(h.shape[:2] + (-1,)) @ r(layer["wo"])


def _linear_mixer(h, layer, c: Config, r):
    q, k, v, g, beta = linear_inputs(h, layer, c, r)
    o = r(recurrence(q, k, v, g, beta, c.scan_block, c.checkpoint_layers))
    return _linear_out(h, o, layer, c, r)


@functools.partial(jax.jit, static_argnames=("c", "r"))
def _full_qkv(h, layer, c: Config, r):
    B, T, D = h.shape
    heads = lambda y: y.reshape(B, T, c.n_heads, D // c.n_heads)
    q = heads(r(_norm(h @ r(layer["wq"]), layer["q_norm"], c.norm_eps)))
    k = heads(r(_norm(h @ r(layer["wk"]), layer["k_norm"], c.norm_eps)))
    return q, k, heads(r(h @ r(layer["wv"])))


@functools.partial(jax.jit, static_argnames=("r",))
def _full_out(attn, layer, r):
    return r(attn.reshape(attn.shape[:2] + (-1,))) @ r(layer["wo"])


def _full_mixer(h, layer, c: Config, r):
    q, k, v = _full_qkv(h, layer, c, r)
    return _full_out(_attention(q, k, v, c.query_block, c.checkpoint_layers), layer, r)


@functools.partial(jax.jit, static_argnames=("eps", "r"))
def _add_norm(x, out, w, eps, r):
    """The block's residual: ``x + RMSNorm(out)``."""
    return r(x + _norm(r(out), w, eps))


def _layer(x, layer, c: Config, r, linear: bool):
    mixer = _linear_mixer if linear else _full_mixer
    x = _add_norm(x, mixer(x, layer, c, r), layer["post_attn_norm"], c.norm_eps, r)
    return _add_norm(x, _swiglu(x, layer, r), layer["post_mlp_norm"], c.norm_eps, r)


@functools.partial(jax.jit, static_argnames=("eps", "r"))
def _head(x, final_norm, lm_head, eps, r):
    return r(_norm(x, final_norm, eps)) @ r(lm_head)


def forward(params: Params, tokens, c: Config,
            compute_dtype: Optional[Any] = None, layer_fn=None) -> jax.Array:
    """Logits (B, T, V).  ``layer_fn`` stands in for :func:`_layer` (its
    arguments, its result): a caller's way to order one layer's part of a
    backward pass; whatever it is given as a layer's parameters is handed
    on as it stands."""
    r = _rounder(compute_dtype)
    with jax.default_matmul_precision("highest"):
        # float32 from here on, whatever dtype the weights are stored in
        x = r(params["embed"])[tokens].astype(jnp.float32)
        layer_fn = layer_fn or _layer
        if c.checkpoint_layers:
            layer_fn = jax.checkpoint(
                layer_fn, static_argnums=(2, 3, 4),
                policy=jax.checkpoint_policies.nothing_saveable,
            )
        for layer, linear in zip(params["layers"], c.linear_layers):
            x = layer_fn(x, layer, c, r, linear)
        return _head(x, params["final_norm"], params["lm_head"], c.norm_eps, r)


def cross_entropy(logits, tokens):
    """Mean next-token cross-entropy: position t predicts token t+1; the
    last position predicts nothing."""
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    return -jnp.mean(picked)


def loss(params: Params, tokens, c: Config,
         compute_dtype: Optional[Any] = None, layer_fn=None) -> jax.Array:
    return cross_entropy(forward(params, tokens, c, compute_dtype, layer_fn), tokens)


def loss_and_grads(params: Params, tokens, c: Config):
    return jax.value_and_grad(loss)(params, tokens, c)
