"""Plain float32 reference of the MiniCPM-SALA decoder (``model_type:
minicpm_sala``, ``openbmb/MiniCPM-SALA``): forward, train loss and
gradients in ``jax.numpy`` under ``jax.default_matmul_precision("highest")``,
importing nothing from ``ddl_tpu``.  ``tests/reference_minicpm_sala.py`` and
``benchmarks/lib/minicpm_sala_reference.py`` are one file twice (a tier-1
test holds them byte-equal): the benchmark carries its own copy.

On a TPU run :func:`forward` EAGERLY, not under ``jax.jit``: one float32
XLA program over 16,384 positions was wrong on the chip (PR 36, PERF.md
section 6), so every stage of a layer is jitted here - called eagerly each
is a program of its own - and under a caller's ``jit`` or ``grad`` they are
inlined and change nothing.

The equations (what ``config.json`` does not carry is an ASSUMED comment
here and an ``assumed`` entry of the benchmark's configuration file):

- MiniCPM's muP, pre-norm: ``x = scale_emb E[ids]``; ``x = x + a
  Mixer(RMSNorm(x))``; ``x = x + a SwiGLU(RMSNorm(x))`` with ``a =
  scale_depth / sqrt(mup_denominator)`` (ASSUMED: the PUBLISHED depth, not
  the cut's); ``logits = (RMSNorm(x) / logit_div) W_head``, ``logit_div =
  hidden / dim_model_base``, untied.
- ``lightning-attn`` (Lightning Attention-2, arXiv:2401.04658), ``H`` heads
  of ``d``: ``q = RoPE(RMSNorm_d(h Wq) w_q)``, ``k`` likewise, ``v = h Wv``
  (ASSUMED: no SiLU; per-head norms with learned ``d``-long weights; RoPE
  over the whole head in the half-split form); position by position, a
  plain ``lax.scan`` with the state ``S`` (d, d) from zero::

      S_t = lam_h S_{t-1} + k_t^T v_t,      o_t = q_t S_t / sqrt(d)

  ``lam_h = exp(-2^(-8 (h + 1) / H))`` (ASSUMED: Lightning Attention's
  slopes, not learned); ``y = RMSNorm_d(o) w_o * sigmoid(h Wg)``; ``out = y
  Wo``.
- ``minicpm4`` (InfLLM-V2, arXiv:2509.24663), ``H`` query heads, ``G``
  key-value heads, NO positions: ``q = RMSNorm_d(h Wq) w_q``, ``k =
  RMSNorm_d(h Wk) w_k``, ``v = h Wv``.  Rows up to ``dense_len``: causal
  softmax attention.  Longer rows: compressed keys ``Kc_m = mean k[m
  stride : m stride + kernel)``; ``p_h = softmax`` over the ``m`` wholly in
  the past of ``q_h . Kc_m / sqrt(d)``; ``P = sum_{h in g} p_h``; a block's
  score the max of ``P`` over the ``m`` whose span meets it; a query sees
  the first ``init_blocks`` blocks, the ``local_blocks`` up to its own, and
  the ``topk - init_blocks`` best-scored past blocks outside both (ties to
  the lower block), and in a visible block the keys ``j <= t``.  No
  gradient through the selection.  ``out = (o * sigmoid(h Wg)) Wo``.
- next-token cross-entropy.  A sliced vocabulary is a smaller vocabulary.

Parameter layout (``ddl_tpu/models/minicpm_sala.py``'s): ``embed`` (V, D),
``final_norm`` (D,), ``lm_head`` (D, V); per layer ``input_norm``,
``pre_mlp_norm`` (D,), ``w_gate``, ``w_up`` (D, F), ``w_down`` (F, D),
``wq``, ``wg`` (D, H d), ``wo`` (H d, D), ``q_norm``, ``k_norm`` (d,); a
lightning layer ``wk``, ``wv`` (D, H d), ``o_norm`` (d,); a sparse layer
``wk``, ``wv`` (D, G d).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Params = Dict[str, Any]


class Config(NamedTuple):
    n_heads: int
    n_kv_heads: int
    head_dim: int
    lightning_heads: int
    lightning_head_dim: int
    #: One flag a layer: True for ``minicpm4`` (sparse attention).
    sparse_layers: Tuple[bool, ...]
    rope_theta: float = 10000.0
    scale_emb: float = 12.0
    #: ``scale_depth / sqrt(mup_denominator)``.
    residual_scale: float = 1.4 / 32**0.5
    #: ``hidden_size / dim_model_base``.
    logit_div: float = 16.0
    norm_eps: float = 1e-6
    block: int = 64
    kernel: int = 32
    stride: int = 16
    topk: int = 64
    init_blocks: int = 1
    local_blocks: int = 32
    dense_len: int = 8192
    #: Queries per block of the masked-softmax attention and the selection.
    query_block: int = 256
    #: Positions per block of the recurrence's scan.
    scan_block: int = 64
    #: ``jax.checkpoint`` around each layer, each query block and each block
    #: of the recurrence: one block's intermediates alive in a backward pass.
    checkpoint_layers: bool = False


def _same(a):
    return a


@functools.lru_cache(maxsize=None)
def _rounder(compute_dtype: Optional[Any]):
    """Identity for the float32 reference.  With a ``compute_dtype`` every
    matmul operand and every block's result is rounded to it and brought
    back to float32: the reference "computed in" that precision."""
    if compute_dtype is None:
        return _same

    def rounded(a):
        return a.astype(compute_dtype).astype(jnp.float32)

    return rounded


def _norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """Rotary embedding in the half-split form; ``x`` (B, T, H, d)."""
    T, half = x.shape[1], x.shape[-1] // 2
    freqs = theta ** (-np.arange(half, dtype=np.float64) / half)
    angles = np.arange(T, dtype=np.float64)[:, None] * freqs[None, :]
    cos = jnp.asarray(np.cos(angles), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(angles), jnp.float32)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def slopes(n_heads: int) -> np.ndarray:
    """``-log lam_h = 2^(-8 (h + 1) / H)``."""
    return 2.0 ** (-8.0 * np.arange(1, n_heads + 1) / n_heads)


def _blocked(f, checkpoint_blocks):
    if not checkpoint_blocks:
        return f
    return jax.checkpoint(f, policy=jax.checkpoint_policies.nothing_saveable)


@functools.partial(jax.jit, static_argnames=("log_decay", "block", "checkpoint_blocks"))
def _lightning(q, k, v, log_decay, block, checkpoint_blocks):
    B, T, H, d = q.shape
    lam = jnp.asarray(np.exp(-np.asarray(log_decay, np.float64)), jnp.float32)

    def step(S, x):
        q, k, v = x  # (B, H, d)
        S = lam[None, :, None, None] * S + k[..., :, None] * v[..., None, :]
        return S, jnp.einsum("bhk,bhkv->bhv", q, S) / np.sqrt(d)

    run_block = _blocked(lambda S, xs: jax.lax.scan(step, S, xs), checkpoint_blocks)
    pad = -T % block
    xs = []
    for x in (q, k, v):  # a padded step has k = v = 0: it only decays
        x = jnp.moveaxis(jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0))), 1, 0)
        xs.append(x.reshape(((T + pad) // block, block) + x.shape[1:]))
    _, o = jax.lax.scan(run_block, jnp.zeros((B, H, d, d), jnp.float32), tuple(xs))
    return jnp.moveaxis(o.reshape((T + pad,) + o.shape[2:]), 0, 1)[:, :T]


def lightning(q, k, v, log_decay: Sequence[float], block=64, checkpoint_blocks=False):
    """The fixed-decay recurrence position by position: ``q``, ``k``, ``v``
    (B, T, H, d) -> (B, T, H, d).  One ``lax.scan`` over positions inside
    one over blocks of them."""
    return _lightning(
        q, k, v, tuple(float(s) for s in log_decay), block, checkpoint_blocks
    )


def _query_blocks(f, T, block, *rows):
    """``f(first position, a block of each of rows)`` over blocks of
    ``block`` positions of ``rows`` (B, T, ...), joined along positions."""
    if T % block:  # a ragged last block: one block after another, unrolled
        out = [f(lo, *(x[:, lo : lo + block] for x in rows)) for lo in range(0, T, block)]
        return jnp.concatenate(out, axis=1)
    cut = lambda x: jnp.moveaxis(
        x.reshape((x.shape[0], T // block, block) + x.shape[2:]), 1, 0
    )
    out = jax.lax.map(
        lambda b: f(b[0], *b[1:]), (jnp.arange(0, T, block),) + tuple(cut(x) for x in rows)
    )
    out = jnp.moveaxis(out, 0, 1)
    return out.reshape((out.shape[0], T) + out.shape[3:])


@functools.partial(jax.jit, static_argnames=("c",))
def block_scores(q, k, c: Config):
    """The selection's scores ``s_{g,t,b}`` (B, T, G, blocks) from q (B, T,
    H, d) and k (B, T, G, d), a block of queries at a time."""
    B, T, H, d = q.shape
    G = k.shape[2]
    M = (T - c.kernel) // c.stride + 1
    nb = -(-T // c.block)
    kc = jax.lax.reduce_window(
        k, 0.0, jax.lax.add, (1, c.kernel, 1, 1), (1, c.stride, 1, 1), "VALID"
    ) / c.kernel  # (B, M, G, d)
    m = np.arange(M)
    # block b meets the m in [n b - reach, n b + n): a window over m
    n, reach = c.block // c.stride, (c.kernel - 1) // c.stride

    def one_block(first, q_block):
        t = first + jnp.arange(q_block.shape[1])
        past = (m[None, :] * c.stride + c.kernel - 1) <= t[:, None]  # (queries, M)
        s = jnp.einsum(
            "bqgrd,bmgd->bqgrm", q_block.reshape(q_block.shape[:2] + (G, H // G, d)), kc
        ) / np.sqrt(d)
        s = jnp.where(past[None, :, None, None, :], s, -jnp.inf)
        top = jnp.max(s, axis=-1, keepdims=True)
        e = jnp.where(past[None, :, None, None, :], jnp.exp(s - jnp.where(
            jnp.isfinite(top), top, 0.0)), 0.0)
        total = jnp.sum(e, axis=-1, keepdims=True)
        P = jnp.sum(e / jnp.where(total > 0, total, 1.0), axis=3)  # (B, q, G, M)
        return jax.lax.reduce_window(
            P, 0.0, jax.lax.max, (1, 1, 1, n + reach), (1, 1, 1, n),
            ((0, 0), (0, 0), (0, 0), (reach, nb * n - M)),
        )

    return _query_blocks(one_block, T, min(c.query_block, T), q)


@functools.partial(jax.jit, static_argnames=("c",))
def visible_blocks(scores, c: Config):
    """(B, T, G, blocks) bool from the scores: the blocks a query sees."""
    T, nb = scores.shape[1], scores.shape[-1]
    own = (np.arange(T) // c.block)[:, None]
    b = np.arange(nb)[None, :]
    first = (b < c.init_blocks) & (b <= own)
    local = (b <= own) & (own - b < c.local_blocks)
    open_ = (b >= c.init_blocks) & (own - b >= c.local_blocks)
    picks = min(c.topk - c.init_blocks, nb)
    fixed = jnp.asarray(first | local)[None, :, None, :]
    if picks <= 0:
        return jnp.broadcast_to(fixed, scores.shape)
    masked = jnp.where(jnp.asarray(open_)[None, :, None, :], scores, -jnp.inf)
    order = jnp.argsort(-masked, axis=-1, stable=True)  # ties: the lower block
    rank = jnp.argsort(order, axis=-1, stable=True)
    return fixed | ((rank < picks) & jnp.isfinite(masked))


@functools.partial(jax.jit, static_argnames=("block", "query_block", "checkpoint_blocks"))
def sparse_attention(q, k, v, seen, block, query_block, checkpoint_blocks=False):
    """``softmax`` over the keys ``j <= t`` of the blocks ``seen`` (B, T, G,
    blocks) marks: q (B, T, H, d), k, v (B, T, G, d), a block of queries at
    a time against every key."""
    B, T, H, d = q.shape
    G = k.shape[2]

    def one_block(first, q_block, seen_block):
        n = q_block.shape[1]
        t = first + jnp.arange(n)
        keys = jnp.repeat(seen_block, block, axis=-1)[..., :T]  # (B, n, G, T)
        keys = keys & (jnp.arange(T)[None, :] <= t[:, None])[None, :, None, :]
        s = jnp.einsum("bqgrd,bkgd->bqgrk", q_block.reshape(B, n, G, H // G, d), k)
        s = jnp.where(keys[:, :, :, None, :], s / np.sqrt(d), -jnp.inf)
        o = jnp.einsum("bqgrk,bkgd->bqgrd", jax.nn.softmax(s, axis=-1), v)
        return o.reshape(B, n, H, d)

    return _query_blocks(
        _blocked(one_block, checkpoint_blocks), T, min(query_block, T), q, seen
    )


def all_blocks(B, T, G, block):
    """``seen`` of plain causal attention: every block up to a query's own."""
    own = (np.arange(T) // block)[:, None]
    seen = np.arange(-(-T // block))[None, :] <= own
    return jnp.broadcast_to(jnp.asarray(seen)[None, :, None, :], (B, T, G, seen.shape[1]))


@functools.partial(jax.jit, static_argnames=("r",))
def _swiglu(h, w, r):
    return r(jax.nn.silu(h @ r(w["w_gate"])) * (h @ r(w["w_up"]))) @ r(w["w_down"])


@functools.partial(jax.jit, static_argnames=("c", "r"))
def lightning_inputs(h, layer, c: Config, r=_same):
    """What the recurrence takes, from normed hidden states ``h``."""
    B, T, _ = h.shape
    heads = lambda w: (h @ r(layer[w])).reshape(B, T, c.lightning_heads, c.lightning_head_dim)
    q = _rope(_norm(heads("wq"), layer["q_norm"], c.norm_eps), c.rope_theta)
    k = _rope(_norm(heads("wk"), layer["k_norm"], c.norm_eps), c.rope_theta)
    return r(q), r(k), r(heads("wv"))


@functools.partial(jax.jit, static_argnames=("c", "r"))
def _lightning_out(h, o, layer, c: Config, r):
    gate = jax.nn.sigmoid(h @ r(layer["wg"])).reshape(o.shape)
    y = r(_norm(o, layer["o_norm"], c.norm_eps) * gate)
    return y.reshape(h.shape[:2] + (-1,)) @ r(layer["wo"])


def _lightning_mixer(h, layer, c: Config, r, seen):
    q, k, v = lightning_inputs(h, layer, c, r)
    o = r(lightning(
        q, k, v, slopes(c.lightning_heads), c.scan_block, c.checkpoint_layers
    ))
    return _lightning_out(h, o, layer, c, r)


@functools.partial(jax.jit, static_argnames=("c", "r"))
def sparse_inputs(h, layer, c: Config, r=_same):
    B, T, _ = h.shape
    heads = lambda w, n: (h @ r(layer[w])).reshape(B, T, n, c.head_dim)
    q = _norm(heads("wq", c.n_heads), layer["q_norm"], c.norm_eps)
    k = _norm(heads("wk", c.n_kv_heads), layer["k_norm"], c.norm_eps)
    return r(q), r(k), r(heads("wv", c.n_kv_heads))


@functools.partial(jax.jit, static_argnames=("r",))
def _sparse_out(h, o, layer, r):
    gate = jax.nn.sigmoid(h @ r(layer["wg"]))
    return r(o.reshape(h.shape[:2] + (-1,)) * gate) @ r(layer["wo"])


def selection(q, k, c: Config):
    """``seen`` (B, T, G, blocks) of q over k, without a gradient: every
    causal block for a row up to ``dense_len``."""
    B, T = q.shape[:2]
    if T <= c.dense_len:
        return all_blocks(B, T, k.shape[2], c.block)
    q, k = jax.lax.stop_gradient(q), jax.lax.stop_gradient(k)
    return visible_blocks(block_scores(q, k, c), c)


def _sparse_mixer(h, layer, c: Config, r, seen):
    q, k, v = sparse_inputs(h, layer, c, r)
    if seen is None:
        seen = selection(q, k, c)
    o = sparse_attention(q, k, v, seen, c.block, c.query_block, c.checkpoint_layers)
    return _sparse_out(h, o, layer, r)


@functools.partial(jax.jit, static_argnames=("eps", "r"))
def _normed(x, w, eps, r):
    return r(_norm(x, w, eps))


@functools.partial(jax.jit, static_argnames=("scale", "r"))
def _residual(x, out, scale, r):
    return r(x + scale * r(out))


def _layer(x, layer, c: Config, r, sparse: bool, seen=None):
    """One block; ``seen`` stands in for a sparse layer's own selection."""
    mixer = _sparse_mixer if sparse else _lightning_mixer
    h = _normed(x, layer["input_norm"], c.norm_eps, r)
    x = _residual(x, mixer(h, layer, c, r, seen), c.residual_scale, r)
    h = _normed(x, layer["pre_mlp_norm"], c.norm_eps, r)
    return _residual(x, _swiglu(h, layer, r), c.residual_scale, r)


@functools.partial(jax.jit, static_argnames=("eps", "div", "r"))
def _head(x, final_norm, lm_head, eps, div, r):
    return r(_norm(x, final_norm, eps) / div) @ r(lm_head)


def forward(params: Params, tokens, c: Config,
            compute_dtype: Optional[Any] = None, layer_fn=None,
            seen: Optional[Sequence[Any]] = None) -> jax.Array:
    """Logits (B, T, V).  ``layer_fn`` stands in for :func:`_layer` (its
    arguments, its result): a caller's way to order one layer's part of a
    backward pass, or to look at a layer's input.  ``seen``: one entry a
    layer (``None`` for a lightning layer, or to let a sparse layer select
    for itself) - the blocks a sparse layer's queries see, given."""
    r = _rounder(compute_dtype)
    with jax.default_matmul_precision("highest"):
        # float32 from here on, whatever dtype the weights are stored in
        x = c.scale_emb * r(params["embed"])[tokens].astype(jnp.float32)
        layer_fn = layer_fn or _layer
        if c.checkpoint_layers:
            layer_fn = jax.checkpoint(
                layer_fn, static_argnums=(2, 3, 4),
                policy=jax.checkpoint_policies.nothing_saveable,
            )
        for n, (layer, sparse) in enumerate(zip(params["layers"], c.sparse_layers)):
            x = layer_fn(x, layer, c, r, sparse, None if seen is None else seen[n])
        return _head(
            x, params["final_norm"], params["lm_head"], c.norm_eps, c.logit_div, r
        )


def cross_entropy(logits, tokens):
    """Mean next-token cross-entropy: position t predicts token t+1; the
    last position predicts nothing."""
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    return -jnp.mean(picked)


def loss(params: Params, tokens, c: Config,
         compute_dtype: Optional[Any] = None, layer_fn=None, seen=None) -> jax.Array:
    return cross_entropy(
        forward(params, tokens, c, compute_dtype, layer_fn, seen), tokens
    )


def loss_and_grads(params: Params, tokens, c: Config):
    return jax.value_and_grad(loss)(params, tokens, c)
