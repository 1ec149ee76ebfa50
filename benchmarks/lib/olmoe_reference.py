"""The benchmark's own copy of the plain OLMoE reference
(``tests/reference_olmoe.py``; a tier-1 test holds the two bodies
identical): forward, train loss and gradients in ``jax.numpy`` float32
under ``jax.default_matmul_precision("highest")``, importing nothing from
``ddl_tpu``.  The layer's equations, the parameter layout and every
departure from the published module are in that file's docstring and in
the ``DEPARTURE`` comments below.
"""

from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Params = Dict[str, Any]


class Config(NamedTuple):
    n_heads: int
    n_kv_heads: int
    head_dim: int
    n_experts: int
    topk: int
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    norm_topk_prob: bool = False
    router_aux_weight: float = 0.01
    router_z_weight: float = 0.001
    #: Queries per block of the masked-softmax attention.
    query_block: int = 512


def _rounder(compute_dtype: Optional[Any]):
    """Identity for the float32 reference.  With a ``compute_dtype`` every
    matmul operand and every block's result is rounded to it and brought
    back to float32: the reference "computed in" that precision, for
    finding out whether a tolerance would let a lower precision pass."""
    if compute_dtype is None:
        return lambda a: a
    return lambda a: a.astype(compute_dtype).astype(jnp.float32)


def _norm(x, w, eps):
    # DEPARTURE: HF casts the normalised value back to the input's dtype
    # before multiplying by the weight; in float32 that cast is nothing.
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """Rotate-half rotary embedding; ``x``: (B, T, H, hd), positions 0..T-1
    (HF ``apply_rotary_pos_emb``: ``x cos + rotate_half(x) sin`` with the
    frequencies repeated over the two halves)."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-np.arange(0, half, dtype=np.float32) / half)
    angles = np.arange(x.shape[1], dtype=np.float32)[:, None] * inv_freq[None, :]
    cos = jnp.asarray(np.cos(angles))[None, :, None, :]
    sin = jnp.asarray(np.sin(angles))[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def _attention(q, k, v, block):
    """Causal softmax attention, (B, T, H, hd) each, a block of queries at
    a time against every key (masked above the diagonal)."""
    B, T, H, hd = q.shape
    if k.shape[2] != H:  # grouped-query: each key/value head serves H/KV queries
        k = jnp.repeat(k, H // k.shape[2], axis=2)
        v = jnp.repeat(v, H // v.shape[2], axis=2)
    key_pos = np.arange(T)
    out = []
    for lo in range(0, T, block):
        hi = min(lo + block, T)
        s = jnp.einsum("bqhd,bkhd->bhqk", q[:, lo:hi], k) / np.sqrt(hd)
        masked = key_pos[None, :] > np.arange(lo, hi)[:, None]
        s = jnp.where(jnp.asarray(masked)[None, None], -jnp.inf, s)
        out.append(jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v))
    return jnp.concatenate(out, axis=1)


def _experts(h, layer, gates, r):
    """``sum_e gates[:, e] * expert_e(h)``: every token through every
    expert, one expert at a time."""

    def one(acc, expert):
        w_gate, w_up, w_down, gate = expert
        hidden = r(jax.nn.silu(h @ r(w_gate)) * (h @ r(w_up)))
        return acc + gate[:, None] * (hidden @ r(w_down)), None

    out, _ = jax.lax.scan(
        one, jnp.zeros_like(h),
        (layer["w_gate"], layer["w_up"], layer["w_down"], gates.T),
    )
    return out


def _layer(x, layer, c: Config, r):
    B, T, D = x.shape
    h = r(_norm(x, layer["attn_norm"], c.norm_eps))
    q = _norm(h @ r(layer["wq"]), layer["q_norm"], c.norm_eps)
    k = _norm(h @ r(layer["wk"]), layer["k_norm"], c.norm_eps)
    v = h @ r(layer["wv"])
    q = r(_rope(q.reshape(B, T, c.n_heads, c.head_dim), c.rope_theta))
    k = r(_rope(k.reshape(B, T, c.n_kv_heads, c.head_dim), c.rope_theta))
    v = r(v.reshape(B, T, c.n_kv_heads, c.head_dim))
    attn = r(_attention(q, k, v, c.query_block).reshape(B, T, -1))
    x = r(x + attn @ r(layer["wo"]))

    h = r(_norm(x, layer["mlp_norm"], c.norm_eps)).reshape(B * T, D)
    # DEPARTURE: HF computes the router's logits in the model's dtype and
    # the softmax in float32; here both are float32.
    logits = h @ r(layer["w_router"])
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, c.topk)
    if c.norm_topk_prob:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    chosen = jax.nn.one_hot(top_e, c.n_experts, dtype=jnp.float32)  # (N, k, E)
    gates = jnp.einsum("nk,nke->ne", top_p, chosen)
    x = r(x + _experts(h, layer, gates, r).reshape(B, T, D))

    # DEPARTURE: HF's load_balancing_loss_func concatenates the router
    # logits of all layers and takes f and P over layers and tokens at
    # once; the recipe (and this repo) take the term per layer and average
    # the layers.  Both read ``topk`` at perfect balance.
    f = jnp.sum(chosen, axis=(0, 1)) / (B * T)
    balance = c.n_experts * jnp.sum(f * jnp.mean(probs, axis=0))
    z = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    return x, balance, z, top_e.reshape(B, T, c.topk)


def forward(params: Params, tokens, c: Config,
            compute_dtype: Optional[Any] = None
            ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """(logits (B, T, V), load-balance term and z-loss as means over the
    layers, the routers' choices (L, B, T, k))."""
    r = _rounder(compute_dtype)
    with jax.default_matmul_precision("highest"):
        x = r(params["embed"])[tokens]
        balance, z, picks = 0.0, 0.0, []
        for layer in params["layers"]:
            x, b, zz, top_e = _layer(x, layer, c, r)
            balance, z = balance + b, z + zz
            picks.append(top_e)
        x = r(_norm(x, params["final_norm"], c.norm_eps))
        logits = x @ r(params["lm_head"])
    n = len(params["layers"])
    return logits, balance / n, z / n, jnp.stack(picks)


def cross_entropy(logits, tokens):
    """Mean next-token cross-entropy: position t predicts token t+1; the
    last position predicts nothing."""
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    return -jnp.mean(picked)


def loss(params: Params, tokens, c: Config,
         compute_dtype: Optional[Any] = None) -> jax.Array:
    logits, balance, z, _ = forward(params, tokens, c, compute_dtype)
    return (
        cross_entropy(logits, tokens)
        + c.router_aux_weight * balance
        + c.router_z_weight * z
    )


def loss_and_grads(params: Params, tokens, c: Config):
    return jax.value_and_grad(loss)(params, tokens, c)
