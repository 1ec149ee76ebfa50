"""Plain float32 reference of the AFMoE decoder (``model_type: afmoe``,
Arcee Trinity-Mini): forward, train loss and gradients in ``jax.numpy``
under ``jax.default_matmul_precision("highest")``, importing nothing from
``ddl_tpu``.  ``benchmarks/lib/afmoe_reference.py`` is the benchmark's
copy (a tier-1 test holds the two bodies identical).

The equations (the catalog row's ``config.json`` keys, and
``modeling_afmoe.py`` as remembered: there is no network here; what the
``config.json`` has no key for is a DEPARTURE/ASSUMED comment below):

- ``x = E[tokens] * sqrt(hidden)`` (``mup_enabled``).
- block: ``x = x + post_attn_norm(Attn(input_norm(x)))``;
  ``x = x + post_mlp_norm(MLP(pre_mlp_norm(x)))``.
- attention on ``h``: ``q, k, v, g = h Wq, h Wk, h Wv, h Wg``; RMSNorm over
  ``head_dim`` with one learned weight on every head of ``q`` and ``k``;
  RoPE on ``q``, ``k`` in ``sliding_attention`` layers only; scores scaled
  by ``1/sqrt(head_dim)``; key ``j`` visible to query ``i`` iff ``j <= i``
  and, in a sliding layer, ``i - j < sliding_window``;
  ``out = (softmax(scores) v * sigmoid(g)) Wo``.
- dense MLP (``layer < n_dense_layers``): ``(silu(h Wgate) * (h Wup)) Wdown``.
- expert MLP: ``s = sigmoid(h Wr)``; ``sel = top_k(s + expert_bias)``;
  ``w = s[sel] / (sum(s[sel]) + 1e-20) * route_scale``;
  ``Shared(h) + sum_k w_k Expert_sel_k(h)``.
- final RMSNorm, untied head, next-token cross-entropy.

The share: ``held = (first, count)`` of the router's ``n_experts``.  The
parameters hold those experts only; every token goes through every HELD
expert under a mask of the router's choices, and a choice of an expert
held elsewhere adds nothing.  With ``(0, n_experts)`` it is the uncut
layer.  A sliced vocabulary is a smaller vocabulary: the embedding and
the head have the slice's rows.

Parameter layout (``ddl_tpu/models/afmoe.py``'s): ``embed`` (V, D),
``final_norm`` (D,), ``lm_head`` (D, V), and per layer ``input_norm``,
``post_attn_norm``, ``pre_mlp_norm``, ``post_mlp_norm`` (D,), ``wq``,
``wg`` (D, H*hd), ``wk``, ``wv`` (D, KV*hd), ``wo`` (H*hd, D), ``q_norm``,
``k_norm`` (hd,); a dense layer ``w_gate``, ``w_up`` (D, F), ``w_down``
(F, D); an expert layer ``w_router`` (D, E), ``expert_bias`` (E,),
``shared`` and ``experts`` SwiGLU stacks (``experts`` with a leading
``count`` axis).
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
    n_experts: int  # the router's width
    topk: int
    layer_types: Tuple[str, ...]  # "sliding_attention" | "full_attention"
    n_dense_layers: int
    sliding_window: int
    held: Tuple[int, int]  # (first, count) of the experts in the parameters
    route_norm: bool = True
    route_scale: float = 2.826
    mup_enabled: bool = True
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
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
    """Rotate-half rotary embedding; ``x``: (B, T, H, hd), positions 0..T-1."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-np.arange(0, half, dtype=np.float32) / half)
    angles = np.arange(x.shape[1], dtype=np.float32)[:, None] * inv_freq[None, :]
    cos = jnp.asarray(np.cos(angles))[None, :, None, :]
    sin = jnp.asarray(np.sin(angles))[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def visible(T: int, window: Optional[int]) -> np.ndarray:
    """(T, T) bool: key j (column) is visible to query i (row)."""
    i, j = np.arange(T)[:, None], np.arange(T)[None, :]
    seen = j <= i
    if window is not None:
        seen &= i - j < window
    return seen


def _attention(q, k, v, block, window, checkpoint_blocks=False):
    """Masked softmax attention, (B, T, H, hd) each, a block of queries at
    a time against every key (``jax.lax.map`` over the blocks: one block's
    scores alive at a time, and a program that compiles in a fraction of
    the unrolled one's time)."""
    B, T, H, hd = q.shape
    if k.shape[2] != H:  # grouped-query: each key/value head serves H/KV queries
        k = jnp.repeat(k, H // k.shape[2], axis=2)
        v = jnp.repeat(v, H // v.shape[2], axis=2)

    def one_block(q_block, first, k, v):
        # ``visible``'s rows of this block, from the positions and not as
        # a (T, T) constant in the program: 64 MiB a mask at T = 8192.
        i = first + jnp.arange(q_block.shape[1])[:, None]
        j = jnp.arange(T)[None, :]
        seen = j <= i
        if window is not None:
            seen &= i - j < window
        s = jnp.einsum("bqhd,bkhd->bhqk", q_block, k) / np.sqrt(hd)
        s = jnp.where(seen[None, None], s, -jnp.inf)
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
        jnp.moveaxis(q.reshape(B, T // block, block, H, hd), 1, 0),
        jnp.arange(0, T, block),
    )
    out = jax.lax.map(lambda b: one_block(b[0], b[1], k, v), blocks)
    return jnp.moveaxis(out, 0, 1).reshape(B, T, H, hd)


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


def _layer(x, layer, c: Config, r, sliding: bool, dense: bool):
    B, T, D = x.shape
    h = r(_norm(x, layer["input_norm"], c.norm_eps))
    q = (h @ r(layer["wq"])).reshape(B, T, c.n_heads, c.head_dim)
    k = (h @ r(layer["wk"])).reshape(B, T, c.n_kv_heads, c.head_dim)
    v = (h @ r(layer["wv"])).reshape(B, T, c.n_kv_heads, c.head_dim)
    # One head_dim-long weight for all heads (AfmoeRMSNorm(head_dim)).
    q = _norm(q, layer["q_norm"], c.norm_eps)
    k = _norm(k, layer["k_norm"], c.norm_eps)
    if sliding:  # full_attention layers carry no position encoding
        q, k = _rope(q, c.rope_theta), _rope(k, c.rope_theta)
    attn = _attention(
        r(q), r(k), r(v), c.query_block, c.sliding_window if sliding else None,
        c.checkpoint_layers,
    )
    gated = r(r(attn.reshape(B, T, -1)) * r(jax.nn.sigmoid(h @ r(layer["wg"]))))
    x = r(x + _norm(r(gated @ r(layer["wo"])), layer["post_attn_norm"], c.norm_eps))

    h = r(_norm(x, layer["pre_mlp_norm"], c.norm_eps)).reshape(B * T, D)
    if dense:
        out, top_e = _swiglu(h, layer, r), None
    else:
        out, top_e = expert_mlp(h, layer, c, r)
        top_e = top_e.reshape(B, T, c.topk)
    out = _norm(r(out).reshape(B, T, D), layer["post_mlp_norm"], c.norm_eps)
    return r(x + out), top_e


def expert_mlp(h, layer, c: Config, r=lambda a: a):
    """An expert layer's MLP on flat tokens ``h`` (N, D): (the shared
    expert's output + the held experts' part of the routed sum, the
    router's choices (N, k) out of all ``n_experts``)."""
    # DEPARTURE: the published module rounds the router's logits to the
    # model's dtype before the float32 sigmoid; here both are float32.
    scores = jax.nn.sigmoid(h @ r(layer["w_router"]))
    # ASSUMED: expert_bias stays at its initial zeros (the published
    # recipe moves it outside the gradient, load_balance_coeff 0.001;
    # config.json gives the coefficient and not the rule).  It enters the
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
        # DEPARTURE: a share's router is not trained.  The experts held
        # elsewhere add nothing here, so the share's part of the router's
        # gradient only says "route to them"; the deployment's other chips
        # balance it, one chip's share cannot.  The weights are constants
        # of the backward pass, as expert_bias is.
        top_w = jax.lax.stop_gradient(top_w)
    chosen = jax.nn.one_hot(top_e, c.n_experts, dtype=jnp.float32)  # (N, k, E)
    gates = jnp.einsum("nk,nke->ne", top_w, chosen)
    # The share: the held experts' columns; a choice of an expert held
    # elsewhere adds nothing here.  n_group = topk_group = 1: no
    # group-limited selection.
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
        if c.mup_enabled:
            x = r(x * np.float32(np.sqrt(x.shape[-1])))
        picks = []
        layer_fn = layer_fn or _layer
        if c.checkpoint_layers:
            layer_fn = jax.checkpoint(
                layer_fn, static_argnums=(2, 3, 4, 5),
                policy=jax.checkpoint_policies.nothing_saveable,
            )
        for li, (layer, kind) in enumerate(zip(params["layers"], c.layer_types)):
            x, top_e = layer_fn(
                x, layer, c, r, kind == "sliding_attention", li < c.n_dense_layers
            )
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
    # ASSUMED: no auxiliary router loss (the recipe balances through
    # expert_bias, not through the loss).
    logits, _ = forward(params, tokens, c, compute_dtype, layer_fn)
    return cross_entropy(logits, tokens)


def loss_and_grads(params: Params, tokens, c: Config):
    return jax.value_and_grad(loss)(params, tokens, c)
