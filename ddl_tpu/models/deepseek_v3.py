"""DeepSeek-V3-shaped decoder LM (``model_type: deepseek_v3`` — here at
Kakao's Kanana-2-30B-A3B sizes): latent attention in front of
sigmoid-routed experts.

Every layer, with ``h = RMSNorm(x)`` and pre-norm residuals
(``x + Attn(norm(x))``, ``x + MLP(norm(x))``):

- latent attention (MLA): ``q = h Wq`` - or, with a query low-rank step
  (``q_lora_rank``; Kanana-2 has none), ``q = RMSNorm(h Wq_a) Wq_b`` -
  per head ``[q_nope (qk_nope_dim) | q_rope (qk_rope_dim)]``;
  ``[c | k_r] = h Wkv_a`` (``kv_lora_rank`` + ``qk_rope_dim``),
  ``c = RMSNorm(c)``, ``[k_nope | v] = c Wkv_b`` per head; ``k_r`` is ONE
  rotary key a position, shared by all heads.  RoPE on ``q_rope`` and
  ``k_r`` only, on adjacent pairs ``(x[2i], x[2i+1])``
  (``rope_interleave``): the pairs are de-interleaved and then rotated in
  ``decoder.rope``'s half-split form — q and k are permuted alike, so the
  scores are the published ones on the published weight layout.
  ``s = (q_nope . k_nope + q_rope . k_r) / sqrt(qk_nope_dim + qk_rope_dim)``,
  causal softmax, ``o = softmax(s) v`` (``v_head_dim`` a head),
  ``concat(o) Wo``.  The two products reach the attention dispatcher as
  they are (``attention(q_rope=, k_rope=)``): on a TPU the
  ``ddl_flash_mla_*`` kernels, which read the shared key through their
  index map, so neither a 192-wide q/k nor the H-fold ``k_r`` is written.
  The per-head cuts are made in the WEIGHTS (:func:`_head_columns`: ``Wq``
  or ``Wq_b`` into its ``q_nope`` and ``q_rope`` columns, ``Wkv_b`` into
  ``k_nope``'s and ``v``'s), so each of the four comes out of a product of
  its own as ``(B, T, H * width)`` and is only reshaped: no ``(B, T, H,
  192)`` or ``(B, T, H, 256)`` activation exists to be sliced per head in
  the forward pass or padded and added per head in the backward - strided
  copies of 134-201 MB arrays a layer that XLA did not fuse (PERF.md
  section 6, PR 48).  The parameters, their names and their initial values
  are what they were.
  With ``rope_scaling`` (:class:`Yarn`; Kanana-2 has none) the rotary
  frequencies are YaRN's blend (:func:`yarn_inv_freq`), cos and sin carry
  ``m(mscale) / m(mscale_all_dim)`` and the score's scale is multiplied by
  ``m(mscale_all_dim)^2``, ``m(x) = 0.1 x ln(factor) + 1`` - inside the
  kernels' one scale (``attention(score_scale=)``): q is not rescaled in
  bfloat16.
- MLP: a dense SwiGLU (the first ``n_dense_layers`` layers,
  ``first_k_dense_replace``) or ``moe.sigmoid_expert_mlp`` — the routine
  ``models/afmoe.py`` runs too: ``sc = sigmoid(h Wr)`` in float32, ``sel =
  top_k(sc + expert_bias)`` (the bias in the selection only; ``n_group``
  1, so no group limit), ``w = sc[sel] / (sum + 1e-20) * route_scale``,
  ``sum_k w_k Expert_sel_k(h) + Shared(h)``, the shared experts one ungated
  SwiGLU of ``n_shared_experts * d_expert``.

What every decoder shares is ``models/decoder.py``'s (``rms_norm``,
``rope``, ``mlp_block``, the stack, the parameter table); the experts are
``moe.ragged_experts`` with the RANGE OF EXPERTS HELD HERE
(``held_experts=(first, count)`` of the router's ``n_experts``): one
chip's share of a layer divided over chips by experts, as in
``models/afmoe.py`` and for its reasons — nothing stands in for the absent
experts, a share does not train its router, and ``expert_bias`` stays at
its initial zeros (``noaux_tc`` moves it outside the gradient; that
update does not exist here).

Serving is not here: a latent cache holds ``c`` and ``k_r`` (576 numbers a
position, not 32 x 320) and decodes in the absorbed form (``Wkv_b`` folded
into the query and the output); neither exists, so
:func:`forward_with_cache` and :func:`generate` raise by name.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ddl_tpu.models import decoder as _decoder
from ddl_tpu.models import moe as _moe
from ddl_tpu.models import remat as _remat
from ddl_tpu.ops.naming import scope

Params = Dict[str, Any]


class Yarn(NamedTuple):
    """``rope_scaling`` of type ``yarn``, as a published ``config.json``
    states it (the defaults are the family's)."""

    factor: float
    original_max_position_embeddings: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0

    def m(self, x: float) -> float:
        """``yarn_get_mscale``: ``0.1 x ln(factor) + 1`` above factor 1."""
        return 1.0 if self.factor <= 1 else 0.1 * x * math.log(self.factor) + 1.0


def yarn_inv_freq(theta: float, dim: int, yarn: Yarn) -> np.ndarray:
    """The ``dim / 2`` rotary frequencies under YaRN, float32: ``f_i =
    theta^(-2i/dim)`` blended with ``f_i / factor`` by the ramp ``r_i =
    clip((i - low) / (high - low), 0, 1)``, ``low = floor(c(beta_fast))``,
    ``high = ceil(c(beta_slow))`` clamped to ``[0, dim - 1]``, ``c(b) = dim
    ln(original / (2 pi b)) / (2 ln theta)``: pairs that turn more than
    ``beta_fast`` times over the original context keep their frequency,
    those that turn less than ``beta_slow`` times are interpolated."""
    def c(b: float) -> float:
        return dim * math.log(
            yarn.original_max_position_embeddings / (2 * math.pi * b)
        ) / (2 * math.log(theta))

    low = max(math.floor(c(yarn.beta_fast)), 0)
    high = min(math.ceil(c(yarn.beta_slow)), dim - 1)
    i = np.arange(dim // 2, dtype=np.float64)
    f = theta ** (-2.0 * i / dim)
    r = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return (f * ((1.0 - r) + r / yarn.factor)).astype(np.float32)


@dataclasses.dataclass(frozen=True)
class DeepseekV3Config:
    vocab: int = 256
    d_model: int = 64
    n_layers: int = 3
    n_heads: int = 4
    #: The score's two widths and the value's, stated, not derived: 128 +
    #: 64 and 128 in Kanana-2, over a hidden size of 2048 / 32 heads.
    qk_nope_dim: int = 16
    qk_rope_dim: int = 8
    v_head_dim: int = 16
    kv_lora_rank: int = 32
    #: The query's low-rank step, ``q = RMSNorm(h Wq_a) Wq_b``; ``None``
    #: (Kanana-2): ``q = h Wq``.
    q_lora_rank: Optional[int] = None
    d_ff: int = 192  # the dense layers' SwiGLU width
    d_expert: int = 32  # each routed expert's; the shared experts' unit
    n_experts: int = 8  # the router's width, whatever is held here
    topk: int = 2
    n_shared_experts: int = 2
    #: The leading layers whose MLP is dense; the rest route.
    n_dense_layers: int = 1
    route_norm: bool = True
    route_scale: float = 1.0
    #: Added to the picked scores' sum where ``route_norm`` divides by it.
    route_eps: float = 1e-20
    #: ``(first, count)`` of the ``n_experts`` whose weights live here;
    #: ``None`` is all of them.
    held_experts: Optional[Tuple[int, int]] = None
    max_seq: int = 512
    rope_theta: float = 1e6
    #: YaRN, as the published config states it; ``None``: plain RoPE.
    rope_scaling: Optional[Yarn] = None
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    #: Remat policy, as :attr:`LlamaConfig.remat`.
    remat: Any = False
    attn_impl: str = "auto"

    def __post_init__(self) -> None:
        _remat.resolve(self.remat)  # fail on junk at config build time
        if not 0 <= self.n_dense_layers <= self.n_layers or self.n_layers < 1:
            raise ValueError("n_dense_layers outside the stack")
        if self.qk_rope_dim % 2:
            raise ValueError("qk_rope_dim is rotated in pairs: an even width")
        first, count = self.held
        if not (0 <= first and count >= 1 and first + count <= self.n_experts):
            raise ValueError(
                f"held_experts={self.held_experts} is not a range of the "
                f"router's {self.n_experts}"
            )

    @property
    def held(self) -> Tuple[int, int]:
        """(first, count) of the experts held here."""
        return self.held_experts or (0, self.n_experts)

    def is_dense(self, layer: int) -> bool:
        return layer < self.n_dense_layers

    @property
    def score_scale(self) -> float:
        """What multiplies the score's ``1/sqrt(qk_nope_dim + qk_rope_dim)``:
        YaRN's ``m(mscale_all_dim)^2`` (where ``mscale_all_dim`` is set), or 1."""
        yarn = self.rope_scaling
        return yarn.m(yarn.mscale_all_dim) ** 2 if yarn and yarn.mscale_all_dim else 1.0

    @staticmethod
    def kanana_2_30b_a3b() -> "DeepseekV3Config":
        """Kanana-2-30B-A3B (``kakaocorp/kanana-2-30b-a3b-instruct-2601``,
        30B total / 3B active) at full depth with every expert held: 48
        layers, 32 heads of 128 + 64 score and 128 value width over a
        512-wide latent, one dense layer (SwiGLU 6144) then 128 routed
        experts x 768, 6 per token, plus 2 shared; sigmoid scores,
        normalised, x 2.448; vocabulary 128,256 untied; bf16 storage.  The
        benchmark's configuration file builds the same config at its
        published depth, experts and vocabulary (a test holds the two
        together)."""
        return DeepseekV3Config(
            vocab=128256, d_model=2048, n_layers=48, n_heads=32,
            qk_nope_dim=128, qk_rope_dim=64, v_head_dim=128, kv_lora_rank=512,
            d_ff=6144, d_expert=768, n_experts=128, topk=6,
            n_shared_experts=2, n_dense_layers=1, route_norm=True,
            route_scale=2.448, max_seq=32768, rope_theta=1e6, norm_eps=1e-6,
            param_dtype=jnp.bfloat16,
        )


def _kinds(cfg: DeepseekV3Config) -> Tuple[bool, ...]:
    """A layer's kind: its MLP is dense."""
    return tuple(cfg.is_dense(li) for li in range(cfg.n_layers))


def _layer_rows(cfg: DeepseekV3Config, dense: bool) -> List[_decoder.Row]:
    """The parameter table of a layer (the Megatron fsdp x tp layout: heads
    over ``tp`` in ``wq``, ``wkv_b`` and ``wo``; the latent projection,
    shared by all heads, is not head-sharded)."""
    d, H, rank = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    col = _decoder.COL
    q_out = H * (cfg.qk_nope_dim + cfg.qk_rope_dim)
    query = [_decoder.Row("wq", (d, q_out), col)] if cfg.q_lora_rank is None else [
        _decoder.Row("wq_a", (d, cfg.q_lora_rank), P("fsdp", None)),
        _decoder.ones("q_a_norm", cfg.q_lora_rank),
        _decoder.Row("wq_b", (cfg.q_lora_rank, q_out), col),
    ]
    return [
        _decoder.ones("attn_norm", d),
        _decoder.ones("mlp_norm", d),
        *query,
        _decoder.Row("wkv_a", (d, rank + cfg.qk_rope_dim), P("fsdp", None)),
        _decoder.ones("kv_a_norm", rank),
        _decoder.Row("wkv_b", (rank, H * (cfg.qk_nope_dim + cfg.v_head_dim)), col),
        _decoder.Row("wo", (H * cfg.v_head_dim, d), _decoder.ROW),
        *(_decoder.swiglu_rows(d, cfg.d_ff) if dense
          else _moe.sigmoid_expert_rows(cfg)),
    ]


#: ``init_params(cfg, key)`` — seeded normal / sqrt(fan_in) matrices, norm
#: weights 1, ``expert_bias`` 0 — and ``param_specs(cfg)`` of one table.  A
#: layer draws 11 keys (the split's width is part of every key, so Kanana-2's
#: weights stay what they were) and with the query's low-rank step 12.
_TABLE = _decoder.Table(_kinds, _layer_rows, (2, 11))
_TABLE_Q_LORA = _TABLE._replace(n_keys=(2, 12))


def _table(cfg: DeepseekV3Config) -> _decoder.Table:
    return _TABLE if cfg.q_lora_rank is None else _TABLE_Q_LORA


def init_params(cfg: DeepseekV3Config, key: jax.Array) -> Params:
    return _table(cfg).init_params(cfg, key)


param_specs = _TABLE.param_specs


def _rope_pairs(x: jax.Array, positions: jax.Array, theta: float,
                yarn: Optional[Yarn] = None) -> jax.Array:
    """RoPE on adjacent pairs ``(x[2i], x[2i+1])`` of the last axis
    (``rope_interleave``); x: (B, T, H, R).  The pairs are de-interleaved —
    evens first, then odds — and rotated in the half-split form; the result
    stays de-interleaved, in q and in k alike, so their product is the
    interleaved form's.  Under ``yarn`` the frequencies are
    :func:`yarn_inv_freq`'s and cos and sin carry ``m(mscale) /
    m(mscale_all_dim)``."""
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    if yarn is None:
        return _decoder.rope(x, positions, theta)
    x = _decoder.rope(
        x, positions, theta, jnp.asarray(yarn_inv_freq(theta, x.shape[-1], yarn)))
    factor = yarn.m(yarn.mscale) / yarn.m(yarn.mscale_all_dim)
    return x if factor == 1.0 else (x * factor).astype(x.dtype)


def _head_columns(w: jax.Array, H: int, at: int) -> Tuple[jax.Array, jax.Array]:
    """A ``(d, H * width)`` weight whose columns are ``H`` heads' blocks, cut
    at column ``at`` of every block: ``(d, H * at)`` and ``(d, H * (width -
    at))``.  The WEIGHT is split so that the activations never are: each
    product comes out as all heads' columns of one kind side by side,
    ``(B, T, H * D)``, which XLA lets the matmul write in the attention
    kernels' head-major layout itself - where a split of ``(B, T, H,
    width)`` per head is a strided copy of an activation, forward, and a
    pad-and-add of one, backward."""
    w = w.reshape(w.shape[0], H, -1)
    return (w[:, :, :at].reshape(w.shape[0], -1),
            w[:, :, at:].reshape(w.shape[0], -1))


def attn(
    layer: Params,
    x: jax.Array,
    cfg: DeepseekV3Config,
    positions: jax.Array,
    mesh: Optional[Any],
    residual: bool = True,
) -> jax.Array:
    """Latent attention on the pre-normed stream: ``x + Attn(RMSNorm(x))``,
    or ``Attn(RMSNorm(x))`` alone where the caller owns the residual path
    (``models/xing4.py``)."""
    from ddl_tpu.parallel.ring_attention import attention

    B, T = x.shape[:2]
    dt = x.dtype
    H, nope, rank = cfg.n_heads, cfg.qk_nope_dim, cfg.kv_lora_rank
    # Without YaRN the call is the three-argument one it always was.
    turn = (positions, cfg.rope_theta) + (
        (cfg.rope_scaling,) if cfg.rope_scaling else ())
    scaled = {"score_scale": cfg.score_scale} if cfg.rope_scaling else {}
    with scope("ddl.attn"):
        h = _decoder.rms_norm(x, layer["attn_norm"], cfg.norm_eps)
        with scope("ddl.mla_q"):
            if cfg.q_lora_rank is None:
                q_in, w, kind = h, layer["wq"].astype(dt), _remat.PROJ
            else:
                q_a = _remat.tag(h @ layer["wq_a"].astype(dt), _remat.PROJ)
                q_in = _decoder.rms_norm(q_a, layer["q_a_norm"], cfg.norm_eps)
                w, kind = layer["wq_b"].astype(dt), _remat.LATENT_UP
            q_nope, q_rope = (
                _remat.tag(q_in @ part, kind).reshape(B, T, H, -1)
                for part in _head_columns(w, H, nope))
            q_rope = _rope_pairs(q_rope, *turn)
        with scope("ddl.mla_kv_up"):
            kv_a = _remat.tag(  # (B, T, rank + rope)
                h @ layer["wkv_a"].astype(dt), _remat.PROJ)
            c = _decoder.rms_norm(
                kv_a[..., :rank], layer["kv_a_norm"], cfg.norm_eps
            )
            k_nope, v = (
                _remat.tag(c @ part, _remat.LATENT_UP).reshape(B, T, H, -1)
                for part in _head_columns(layer["wkv_b"].astype(dt), H, nope))
            # One rotary key a position, for all heads.
            k_rope = _rope_pairs(kv_a[..., None, rank:], *turn)
        out = attention(
            q_nope, k_nope, v, mesh=mesh, impl=cfg.attn_impl, causal=True,
            q_rope=q_rope, k_rope=k_rope, **scaled,
        )
        out = out.reshape(B, T, -1) @ layer["wo"].astype(dt)
        return x + out if residual else out


def _layer_apply(
    layer: Params,
    x: jax.Array,
    cfg: DeepseekV3Config,
    positions: jax.Array,
    dense: bool,
    mesh: Optional[Any],
):
    """One block → (x, the router's picks (B, T, topk), or ``None`` from a
    dense layer, no auxiliary loss)."""
    x = attn(layer, x, cfg, positions, mesh)
    if dense:  # the llama block's norm, SwiGLU and residual
        return _decoder.mlp_block(layer, x, cfg), None, None
    with scope("ddl.moe"):
        h = _decoder.rms_norm(x, layer["mlp_norm"], cfg.norm_eps)
        out, top_e = _moe.sigmoid_expert_mlp(h, layer, cfg, mesh)
        return x + out, top_e, None


def forward_with_choices(
    params: Params,
    tokens: jax.Array,
    cfg: DeepseekV3Config,
    mesh: Optional[Any] = None,
) -> Tuple[jax.Array, jax.Array]:
    """(logits (B, T, vocab) float32, the expert ids every expert layer's
    router picked (L_expert, B, T, topk) — out of all ``n_experts``, held
    here or not)."""
    positions = jnp.arange(tokens.shape[1])

    def block(dense: bool):
        return lambda x, layer: _layer_apply(layer, x, cfg, positions, dense, mesh)

    logits, picks, _ = _decoder.forward(params, tokens, cfg, _table(cfg), block)
    return logits, _decoder.stack_picks(picks, tokens, cfg.topk)


def forward(
    params: Params,
    tokens: jax.Array,
    cfg: DeepseekV3Config,
    mesh: Optional[Any] = None,
) -> jax.Array:
    """Next-token logits, (B, T, vocab) float32."""
    return forward_with_choices(params, tokens, cfg, mesh)[0]


#: Mean next-token cross-entropy.  No auxiliary router loss (``topk_method:
#: noaux_tc`` balances by moving ``expert_bias``, not by a term of the loss).
next_token_loss = _decoder.loss_of(forward)

forward_with_cache, generate = _decoder.no_decode(
    "deepseek_v3", "a latent KV cache (the normalised latent and the shared "
    "rotary key a position) and the absorbed decode form, which do not exist yet",
)
