"""LFM2-MoE decoder LM (``model_type: lfm2_moe`` — here at Liquid AI's
LFM2-24B-A2B sizes): gated short convolutions and grouped-query attention
over one stack, in front of sigmoid-routed experts without a shared expert,
under a tied head.

Every layer, with pre-norm residuals and no other norm::

    x = x + Mix(RMSNorm(x; operator_norm));   x = x + FFN(RMSNorm(x; ffn_norm))

``Mix`` is static data of the config (``layer_types``: ``conv`` and
``full_attention``, in LFM2-24B-A2B ``(conv, conv, full_attention, conv)``
ten times over):

- ``conv`` — the gated short convolution, no state matrix and no positions::

      [B, C, u] = split3(h W_in)                      W_in: d -> 3 d, no bias
      a = B * u
      c_t = sum_j w_j * a_{t-(K-1)+j}                 per channel, K = conv_kernel,
                                                      zero before the row, no bias,
                                                      NO activation
      out = (C * c) W_out

  ``C * taps(B * u)`` is ONE routine with its own backward pass
  (:func:`gated_short_conv`): three bandwidth-bound passes over a 3 d-wide
  activation between two matmuls, which autodiff would turn into K shifted
  float32 copies of the row (``olmo_hybrid._silu_conv`` says what those
  cost).
- ``full_attention`` — ``n_heads`` query heads over ``n_kv_heads`` key heads
  of ``d_model / n_heads`` (32 x 64 over 8 x 64), RMSNorm over a head's width
  with one learned weight on q and on k BEFORE rotate-half RoPE over the
  whole head, causal softmax, through the one attention dispatcher.

``FFN`` is a dense SwiGLU in the first ``n_dense_layers`` layers and
``moe.sigmoid_expert_mlp`` in the rest — the routine ``models/afmoe.py`` and
``models/deepseek_v3.py`` run, here with ``n_shared_experts = 0`` and the
normalisation's ``route_eps = 1e-6``: ``s = sigmoid(h Wr)`` in float32, ``sel
= top_k(s + expert_bias)`` (the bias in the selection only), ``w = s[sel] /
(sum + 1e-6) * route_scale``, ``sum_k w_k Expert_sel_k(h)``.

The head is TIED: ``logits = RMSNorm(x; final_norm) E^T`` over the
embedding's own rows (``decoder.lm_head`` on a ``Table`` without the
``lm_head`` row), so the embedding's gradient has two sources.

What every decoder shares is ``models/decoder.py``'s (``rms_norm``,
``rope``, ``swiglu``, ``taps_sum``, the stack, the parameter table); the
experts are ``moe.ragged_experts`` with the RANGE OF EXPERTS HELD HERE
(``held_experts=(first, count)`` of the router's ``n_experts``): one chip's
share of a layer divided over chips by experts, as in ``models/afmoe.py``
and for its reasons — nothing stands in for the absent experts, a share
does not train its router, and ``expert_bias`` stays at its initial zeros
(the published recipe moves it outside the gradient; that update does not
exist here).

Serving is not here: a conv layer's cache is the convolution's last ``K -
1`` inputs ``a`` (two a channel), which nothing holds or measures, so
:func:`forward_with_cache` and :func:`generate` raise by name; nor is a mesh
wider than ``dp`` given a layout of its own (no ``stage_params``).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ddl_tpu.models import decoder as _decoder
from ddl_tpu.models import moe as _moe
from ddl_tpu.models import remat as _remat
from ddl_tpu.ops.naming import scope

Params = Dict[str, Any]

CONV, FULL = "conv", "full_attention"


@dataclasses.dataclass(frozen=True)
class Lfm2MoeConfig:
    vocab: int = 256
    d_model: int = 64
    n_heads: int = 4  # head_dim = d_model / n_heads
    n_kv_heads: int = 2
    d_ff: int = 192  # the dense layers' SwiGLU width
    d_expert: int = 32  # each routed expert's
    n_experts: int = 8  # the router's width, whatever is held here
    topk: int = 2
    #: One mixer kind a layer; its length is the depth.
    layer_types: Tuple[str, ...] = (CONV, CONV, FULL, CONV)
    #: The leading layers whose FFN is dense; the rest route.
    n_dense_layers: int = 1
    #: Taps of the short convolution (``conv_L_cache``).
    conv_kernel: int = 3
    route_norm: bool = True
    route_scale: float = 1.0
    #: Added to the picked scores' sum where ``route_norm`` divides by it.
    route_eps: float = 1e-6
    #: ``(first, count)`` of the ``n_experts`` whose weights live here;
    #: ``None`` is all of them.
    held_experts: Optional[Tuple[int, int]] = None
    max_seq: int = 512
    rope_theta: float = 1e6
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    #: Remat policy, as :attr:`LlamaConfig.remat`.
    remat: Any = False
    attn_impl: str = "auto"

    #: The family has no shared expert (``moe.sigmoid_expert_rows`` reads it).
    n_shared_experts = 0

    def __post_init__(self) -> None:
        _remat.resolve(self.remat)  # fail on junk at config build time
        bad = set(self.layer_types) - {CONV, FULL}
        if bad or not self.layer_types:
            raise ValueError(f"layer_types must be of {CONV!r}/{FULL!r}: {bad}")
        if not 0 <= self.n_dense_layers <= len(self.layer_types):
            raise ValueError("n_dense_layers outside the stack")
        if self.d_model % self.n_heads or self.n_heads % self.n_kv_heads:
            raise ValueError("n_heads must divide d_model, n_kv_heads n_heads")
        if self.conv_kernel < 1:
            raise ValueError("conv_kernel counts the taps: at least one")
        first, count = self.held
        if not (0 <= first and count >= 1 and first + count <= self.n_experts):
            raise ValueError(
                f"held_experts={self.held_experts} is not a range of the "
                f"router's {self.n_experts}"
            )

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def held(self) -> Tuple[int, int]:
        """(first, count) of the experts held here."""
        return self.held_experts or (0, self.n_experts)

    def is_dense(self, layer: int) -> bool:
        return layer < self.n_dense_layers

    @staticmethod
    def lfm2_24b_a2b() -> "Lfm2MoeConfig":
        """LFM2-24B-A2B (``LiquidAI/LFM2-24B-A2B``, 24B total / 2B active)
        at full depth with every expert held: 40 layers, ``(conv, conv,
        full_attention, conv)`` x 10, hidden 2048, 32 query heads over 8 key
        heads of 64, 3 taps, two leading dense layers (SwiGLU 11,776) then
        64 routed experts x 1536, 4 per token, no shared expert; sigmoid
        scores under a selection bias, normalised (+ 1e-6), x 1; vocabulary
        65,536 tied; bf16 storage.  The benchmark's configuration file
        builds the same config at its published depth, experts and
        vocabulary (a test holds the two together)."""
        return Lfm2MoeConfig(
            vocab=65536, d_model=2048, n_heads=32, n_kv_heads=8, d_ff=11776,
            d_expert=1536, n_experts=64, topk=4,
            layer_types=(CONV, CONV, FULL, CONV) * 10, n_dense_layers=2,
            conv_kernel=3, route_norm=True, route_scale=1.0, route_eps=1e-6,
            max_seq=128000, rope_theta=1e6, norm_eps=1e-5,
            param_dtype=jnp.bfloat16,
        )


def _kinds(cfg: Lfm2MoeConfig) -> Tuple[Tuple[bool, bool], ...]:
    """A layer's kind: (its mixer is the convolution, its FFN is dense)."""
    return tuple(
        (kind == CONV, cfg.is_dense(li)) for li, kind in enumerate(cfg.layer_types)
    )


def _layer_rows(cfg: Lfm2MoeConfig, kind: Tuple[bool, bool]) -> List[_decoder.Row]:
    """The parameter table of a layer (the Megatron fsdp x tp layout; the
    taps replicated, their fan-in the kernel)."""
    d, hd = cfg.d_model, cfg.head_dim
    conv, dense = kind
    mixer = [
        _decoder.Row("w_in", (d, 3 * d), _decoder.COL),
        _decoder.Row("conv", (cfg.conv_kernel, d), P(None, None)),
        _decoder.Row("w_out", (d, d), _decoder.ROW),
    ] if conv else [
        *_decoder.attn_rows(d, cfg.n_heads * hd, cfg.n_kv_heads * hd),
        _decoder.ones("q_norm", hd),
        _decoder.ones("k_norm", hd),
    ]
    return [
        _decoder.ones("operator_norm", d),
        _decoder.ones("ffn_norm", d),
        *mixer,
        *(_decoder.swiglu_rows(d, cfg.d_ff) if dense
          else _moe.sigmoid_expert_rows(cfg)),
    ]


#: ``init_params(cfg, key)`` — seeded normal / sqrt(fan_in) matrices, norm
#: weights 1, ``expert_bias`` 0, no ``lm_head`` (the head is the embedding's
#: rows) — and ``param_specs(cfg)`` of one table.
_TABLE = _decoder.Table(_kinds, _layer_rows, (1, 8), tied=True)
init_params, param_specs = _TABLE.init_params, _TABLE.param_specs


# -- the gated short convolution ------------------------------------------------------


def _gated_taps(bcx: jax.Array, taps: jax.Array):
    """``(a, c)`` of ``bcx = [B | C | u]`` (N, T, 3 d) in float32: the gated
    input ``a = B * u`` padded IN FRONT of the row by the ``K - 1`` zeros the
    taps read there, and the taps' output ``c`` (N, T, d).  ``pad(B) * pad(u)
    = pad(B * u)``, so no array of the row's size stands between the split
    and the taps."""
    d, K = bcx.shape[-1] // 3, taps.shape[0]
    padded = jnp.pad(bcx, ((0, 0), (K - 1, 0), (0, 0))).astype(jnp.float32)
    a = padded[..., :d] * padded[..., 2 * d:]
    return a, _decoder.taps_sum(a, taps, bcx.shape[1], False)


@jax.jit
def _shortconv_fwd(bcx: jax.Array, taps: jax.Array) -> jax.Array:
    """``C * taps(B * u)`` in one pass: the gates and the taps in float32,
    the result in ``bcx``'s dtype."""
    d = bcx.shape[-1] // 3
    _, c = _gated_taps(bcx, taps)
    return (bcx[..., d : 2 * d].astype(jnp.float32) * c).astype(bcx.dtype)


@jax.jit
def _shortconv_bwd(bcx: jax.Array, taps: jax.Array, dy: jax.Array):
    """The cotangents of :func:`_shortconv_fwd`'s operands in one pass over
    ``bcx`` and ``dy``: ``a`` and ``c`` are computed again, never kept, and
    ``a[t]`` met tap ``j`` at output ``t + K - 1 - j`` - the same taps sum
    over ``dc = dy * C`` padded BEHIND the row, taps in reverse."""
    f32 = jnp.float32
    d, (K, T) = bcx.shape[-1] // 3, (taps.shape[0], bcx.shape[1])
    a, c = _gated_taps(bcx, taps)
    dy = dy.astype(f32)
    dc = dy * bcx[..., d : 2 * d].astype(f32)
    behind = jnp.pad(dc, ((0, 0), (0, K - 1), (0, 0)))
    da = _decoder.taps_sum(behind, taps, T, True)
    d_bcx = jnp.concatenate([
        da * bcx[..., 2 * d:].astype(f32), dy * c, da * bcx[..., :d].astype(f32),
    ], axis=-1).astype(bcx.dtype)
    d_taps = jnp.stack([
        jnp.sum(dc * a[:, j : j + T], axis=(0, 1)) for j in range(K)
    ]).astype(taps.dtype)
    return d_bcx, d_taps


@jax.custom_vjp
def gated_short_conv(bcx: jax.Array, taps: jax.Array) -> jax.Array:
    """The gated short convolution's core, ``C * conv_K(B * u)``: ``bcx``
    (N, T, 3 d) is ``h W_in``, split ``[B | C | u]``; ``taps`` (K, d) is
    causal and depthwise, the last tap on the position itself; no bias, no
    activation.  Its own backward pass (:func:`_shortconv_bwd`): one pass
    over the cotangent, nothing K-fold or float32 at the row's size kept.
    Under ``remat="selective"`` its rule tags ``bcx`` - the ONE residual -
    as saved, so a rematerialised backward runs neither the operator norm
    nor ``W_in`` again, computes ``y`` once more for ``W_out``'s gradient
    and runs the backward pass: two forward passes and one backward a
    layer and step (``models/remat.py``)."""
    return _shortconv_fwd(bcx, taps)


def _gated_short_conv_fwd(bcx, taps):
    bcx = _remat.tag_attn_out(bcx)
    return _shortconv_fwd(bcx, taps), (bcx, taps)


def _gated_short_conv_bwd(res, dy):
    return _shortconv_bwd(*res, dy)


gated_short_conv.defvjp(_gated_short_conv_fwd, _gated_short_conv_bwd)


def _conv_block(layer: Params, x: jax.Array, cfg: Lfm2MoeConfig) -> jax.Array:
    """The gated short convolution on the residual stream."""
    dt = x.dtype
    with scope("ddl.shortconv_proj"):
        h = _decoder.rms_norm(x, layer["operator_norm"], cfg.norm_eps)
        bcx = h @ layer["w_in"].astype(dt)
    with scope("ddl.shortconv"):
        y = gated_short_conv(bcx, layer["conv"])
    with scope("ddl.shortconv_out"):
        return x + y @ layer["w_out"].astype(dt)


def _attn_block(
    layer: Params,
    x: jax.Array,
    cfg: Lfm2MoeConfig,
    positions: jax.Array,
    mesh: Optional[Any],
) -> jax.Array:
    """Grouped-query attention, per-head QK-norm in front of RoPE."""
    from ddl_tpu.parallel.ring_attention import attention

    B, T = x.shape[:2]
    dt, eps = x.dtype, cfg.norm_eps
    with scope("ddl.attn"):
        h = _decoder.rms_norm(x, layer["operator_norm"], eps)

        def heads(w: str, n: int) -> jax.Array:
            return (h @ layer[w].astype(dt)).reshape(B, T, n, cfg.head_dim)

        # One head_dim-long weight, applied to every head.
        q = _decoder.rms_norm(heads("wq", cfg.n_heads), layer["q_norm"], eps)
        k = _decoder.rms_norm(heads("wk", cfg.n_kv_heads), layer["k_norm"], eps)
        v = heads("wv", cfg.n_kv_heads)
        q = _decoder.rope(q, positions, cfg.rope_theta)
        k = _decoder.rope(k, positions, cfg.rope_theta)
        attn = attention(
            q, k, v, mesh=mesh, impl=cfg.attn_impl, causal=True,
            kv_repeat=cfg.n_heads // cfg.n_kv_heads,
        )
        return x + attn.reshape(B, T, -1) @ layer["wo"].astype(dt)


def _layer_apply(
    layer: Params,
    x: jax.Array,
    cfg: Lfm2MoeConfig,
    positions: jax.Array,
    conv: bool,
    dense: bool,
    mesh: Optional[Any],
):
    """One block of the stated kinds → (x, the router's picks (B, T,
    topk), or ``None`` from a dense layer, no auxiliary loss)."""
    if conv:
        x = _conv_block(layer, x, cfg)
    else:
        x = _attn_block(layer, x, cfg, positions, mesh)
    with scope("ddl.mlp" if dense else "ddl.moe"):
        h = _decoder.rms_norm(x, layer["ffn_norm"], cfg.norm_eps)
        if dense:
            return x + _decoder.swiglu(layer, h), None, None
        out, top_e = _moe.sigmoid_expert_mlp(h, layer, cfg, mesh)
        return x + out, top_e, None


def forward_with_choices(
    params: Params,
    tokens: jax.Array,
    cfg: Lfm2MoeConfig,
    mesh: Optional[Any] = None,
) -> Tuple[jax.Array, jax.Array]:
    """(logits (B, T, vocab) float32, the expert ids every expert layer's
    router picked (L_expert, B, T, topk) — out of all ``n_experts``, held
    here or not)."""
    positions = jnp.arange(tokens.shape[1])

    def block(kind: Tuple[bool, bool]):
        return lambda x, layer: _layer_apply(layer, x, cfg, positions, *kind, mesh)

    logits, picks, _ = _decoder.forward(params, tokens, cfg, _TABLE, block)
    return logits, _decoder.stack_picks(picks, tokens, cfg.topk)


def forward(
    params: Params,
    tokens: jax.Array,
    cfg: Lfm2MoeConfig,
    mesh: Optional[Any] = None,
) -> jax.Array:
    """Next-token logits, (B, T, vocab) float32."""
    return forward_with_choices(params, tokens, cfg, mesh)[0]


#: Mean next-token cross-entropy.  No auxiliary router loss: the published
#: recipe balances by moving ``expert_bias``, not by a term of the loss.
next_token_loss = _decoder.loss_of(forward)

forward_with_cache, generate = _decoder.no_decode(
    "lfm2_moe", "the short convolution's cache: a conv layer's is its last "
    "conv_kernel - 1 gated inputs B * u a channel, which does not exist yet",
)
