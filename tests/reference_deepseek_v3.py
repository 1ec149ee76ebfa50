"""Plain float32 reference of the DeepSeek-V3-shaped decoder
(``model_type: deepseek_v3``, Kakao Kanana-2-30B-A3B): forward, train loss
and gradients in ``jax.numpy`` under
``jax.default_matmul_precision("highest")``, importing nothing from
``ddl_tpu``.  ``benchmarks/lib/deepseek_v3_reference.py`` is the
benchmark's copy (a tier-1 test holds the two bodies identical).

The equations (the catalog row's ``config.json`` keys and the
``deepseek_v3`` family's module as remembered: there is no network here;
what departs is a DEPARTURE/ASSUMED comment below):

- ``x = E[tokens]``; block: ``x = x + Attn(attn_norm(x))``;
  ``x = x + MLP(mlp_norm(x))`` (pre-norm residuals).
- latent attention on ``h`` (``q_lora_rank`` null: no query low-rank
  step): ``q = h Wq`` -> per head ``[q_nope | q_rope]``; ``[c | k_r] = h
  Wkv_a``; ``c = RMSNorm(c)``; ``[k_nope | v] = c Wkv_b`` per head; RoPE on
  ``q_rope`` and on the ONE ``k_r`` a position only, on adjacent pairs
  ``(x[2i], x[2i+1])`` by ``pos * theta^(-2i/R)`` (``rope_interleave``);
  ``k = [k_nope | k_r]`` with ``k_r`` repeated to every head — the 192-wide
  q and k are materialised here; scores ``q . k / sqrt(nope + rope)``, key
  ``j`` visible to query ``i`` iff ``j <= i``; ``out = concat(softmax(s) v)
  Wo``, v and the heads' outputs ``v_head_dim`` wide.
- dense MLP (``layer < n_dense_layers``): ``(silu(h Wgate) * (h Wup)) Wdown``.
- expert MLP: ``sc = sigmoid(h Wr)``; ``sel = top_k(sc + expert_bias)``
  (``n_group = topk_group = 1``: no group limit); ``w = sc[sel] /
  (sum(sc[sel]) + 1e-20) * route_scale``; ``Shared(h) + sum_k w_k
  Expert_sel_k(h)``, ``Shared`` one SwiGLU of ``n_shared * d_expert``.
- final RMSNorm, untied head, next-token cross-entropy.

The share: ``held = (first, count)`` of the router's ``n_experts``.  The
parameters hold those experts only; every token goes through every HELD
expert under a mask of the router's choices, and a choice of an expert
held elsewhere adds nothing.  With ``(0, n_experts)`` it is the uncut
layer.  A sliced vocabulary is a smaller vocabulary: the embedding and
the head have the slice's rows.

Parameter layout (``ddl_tpu/models/deepseek_v3.py``'s): ``embed`` (V, D),
``final_norm`` (D,), ``lm_head`` (D, V), and per layer ``attn_norm``,
``mlp_norm`` (D,), ``wq`` (D, H*(nope+rope)), ``wkv_a`` (D, rank+rope),
``kv_a_norm`` (rank,), ``wkv_b`` (rank, H*(nope+v)), ``wo`` (H*v, D); a
dense layer ``w_gate``, ``w_up`` (D, F), ``w_down`` (F, D); an expert layer
``w_router`` (D, E), ``expert_bias`` (E,), ``shared`` and ``experts``
SwiGLU stacks (``experts`` with a leading ``count`` axis).
"""

from __future__ import annotations

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
    route_norm: bool = True
    route_scale: float = 2.448
    rope_theta: float = 1e6
    norm_eps: float = 1e-6
    #: Queries per block of the masked-softmax attention.
    query_block: int = 256
    #: ``jax.checkpoint`` around each layer and around each query block of
    #: its attention: the same arithmetic, with one layer's intermediates
    #: and one block's scores alive at a time in a backward pass - for
    #: gradients at a size whose attention probabilities would not fit.
    checkpoint_layers: bool = False


def _rounder(compute_dtype: Optional[Any]):
    """Identity for the float32 reference.  With a ``compute_dtype`` every
    matmul operand and every block's result is rounded to it and brought
    back to float32: the reference "computed in" that precision, for
    finding out whether a tolerance would let a lower precision pass."""
    if compute_dtype is None:
        return lambda a: a
    return lambda a: a.astype(compute_dtype).astype(jnp.float32)


def _norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """Rotary embedding on adjacent pairs (``rope_interleave``): pair ``i``
    = ``(x[2i], x[2i+1])`` turns by ``pos * theta^(-2i/R)``; ``x``:
    (B, T, H, R), positions 0..T-1."""
    R = x.shape[-1]
    inv_freq = theta ** (-np.arange(0, R, 2, dtype=np.float32) / R)
    angles = np.arange(x.shape[1], dtype=np.float32)[:, None] * inv_freq[None, :]
    cos = jnp.asarray(np.cos(angles))[None, :, None, :]
    sin = jnp.asarray(np.sin(angles))[None, :, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    turned = jnp.stack([even * cos - odd * sin, even * sin + odd * cos], axis=-1)
    return turned.reshape(x.shape)


def _attention(q, k, v, block, checkpoint_blocks=False):
    """Causal softmax attention from materialised q, k (B, T, H, nope+rope)
    and v (B, T, H, v), a block of queries at a time against every key
    (``jax.lax.map`` over the blocks: one block's scores alive at a time)."""
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
    return jnp.moveaxis(out, 0, 1).reshape(B, T, H, v.shape[-1])


def _swiglu(h, w, r):
    return r(jax.nn.silu(h @ r(w["w_gate"])) * (h @ r(w["w_up"]))) @ r(w["w_down"])


def _experts(h, experts, gates, r):
    """``sum_e gates[:, e] * expert_e(h)`` over the held experts: every
    token through every one of them, one expert at a time."""

    def one(acc, expert):
        w, gate = expert
        return acc + gate[:, None] * _swiglu(h, w, r), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(h), (experts, gates.T))
    return out


def latent_qkv(h, layer, c: Config, r=lambda a: a):
    """The materialised q, k (B, T, H, nope + rope) and v (B, T, H, v) of
    latent attention on normalised hidden states ``h`` (B, T, D)."""
    B, T, _ = h.shape
    H, nope, rank = c.n_heads, c.qk_nope_dim, c.kv_lora_rank
    q = (h @ r(layer["wq"])).reshape(B, T, H, nope + c.qk_rope_dim)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], c.rope_theta)], -1)
    kv_a = h @ r(layer["wkv_a"])
    latent = r(_norm(kv_a[..., :rank], layer["kv_a_norm"], c.norm_eps))
    kv = (latent @ r(layer["wkv_b"])).reshape(B, T, H, nope + c.v_head_dim)
    # One rotary key a position, repeated to every head.
    k_r = _rope(kv_a[:, :, None, rank:], c.rope_theta)
    k_r = jnp.broadcast_to(k_r, (B, T, H, c.qk_rope_dim))
    k = jnp.concatenate([kv[..., :nope], k_r], -1)
    return q, k, kv[..., nope:]


def _layer(x, layer, c: Config, r, dense: bool):
    B, T, D = x.shape
    h = r(_norm(x, layer["attn_norm"], c.norm_eps))
    q, k, v = latent_qkv(h, layer, c, r)
    attn = _attention(r(q), r(k), r(v), c.query_block, c.checkpoint_layers)
    x = r(x + r(attn.reshape(B, T, -1)) @ r(layer["wo"]))

    h = r(_norm(x, layer["mlp_norm"], c.norm_eps)).reshape(B * T, D)
    if dense:
        out, top_e = _swiglu(h, layer, r), None
    else:
        out, top_e = expert_mlp(h, layer, c, r)
        top_e = top_e.reshape(B, T, c.topk)
    return r(x + r(out).reshape(B, T, D)), top_e


def expert_mlp(h, layer, c: Config, r=lambda a: a):
    """An expert layer's MLP on flat tokens ``h`` (N, D): (the shared
    experts' output + the held experts' part of the routed sum, the
    router's choices (N, k) out of all ``n_experts``)."""
    # DEPARTURE: the published module rounds the router's logits to the
    # model's dtype before the float32 sigmoid; here both are float32.
    scores = jax.nn.sigmoid(h @ r(layer["w_router"]))
    # ASSUMED: e_score_correction_bias (``expert_bias``) stays at its
    # initial zeros (``noaux_tc`` moves it outside the gradient and
    # config.json gives no rule).  It enters the selection only, so its
    # gradient is zero.
    _, top_e = jax.lax.top_k(
        scores + jax.lax.stop_gradient(layer["expert_bias"]), c.topk
    )
    top_w = jnp.take_along_axis(scores, top_e, axis=-1)
    if c.route_norm:
        top_w = top_w / (jnp.sum(top_w, axis=-1, keepdims=True) + 1e-20)
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
    routed = _experts(h, layer["experts"], gates[:, first : first + count], r)
    return _swiglu(h, layer["shared"], r) + routed, top_e  # shared: ungated


def forward(params: Params, tokens, c: Config,
            compute_dtype: Optional[Any] = None,
            layer_fn=None) -> Tuple[jax.Array, jax.Array]:
    """(logits (B, T, V), the routers' choices (L_expert, B, T, k) out of
    all ``n_experts``).  ``layer_fn`` stands in for :func:`_layer` (its
    arguments, its results): a caller's way to order one layer's part of
    a backward pass; whatever it is given as a layer's parameters is
    handed on as it stands."""
    r = _rounder(compute_dtype)
    with jax.default_matmul_precision("highest"):
        x = r(params["embed"])[tokens]
        picks = []
        layer_fn = layer_fn or _layer
        if c.checkpoint_layers:
            layer_fn = jax.checkpoint(
                layer_fn, static_argnums=(2, 3, 4),
                policy=jax.checkpoint_policies.nothing_saveable,
            )
        for li, layer in enumerate(params["layers"]):
            x, top_e = layer_fn(x, layer, c, r, li < c.n_dense_layers)
            if top_e is not None:
                picks.append(top_e)
        x = r(_norm(x, params["final_norm"], c.norm_eps))
        logits = x @ r(params["lm_head"])
    return logits, jnp.stack(picks)


def cross_entropy(logits, tokens):
    """Mean next-token cross-entropy: position t predicts token t+1; the
    last position predicts nothing."""
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    return -jnp.mean(picked)


def loss(params: Params, tokens, c: Config,
         compute_dtype: Optional[Any] = None, layer_fn=None) -> jax.Array:
    # ASSUMED: no auxiliary router loss (``noaux_tc`` balances through the
    # selection bias, not through the loss).
    logits, _ = forward(params, tokens, c, compute_dtype, layer_fn)
    return cross_entropy(logits, tokens)


def loss_and_grads(params: Params, tokens, c: Config):
    return jax.value_and_grad(loss)(params, tokens, c)
