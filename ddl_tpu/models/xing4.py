"""Xing4.0-shaped decoder LM (``model_type: xing4_0`` — here at
XingChen-AGI's Xing4.0-29B-A4B sizes): a hyper-connected residual path four
streams wide around latent attention and sigmoid-routed experts, with a
multi-token-prediction module in the train loss.

The stack carries ``X (B, n, T, C)``, ``n = hc_mult`` streams (the layout is
``models/hyper_connections.py``'s, and so are the equations of a wrap):

- **open / close**: ``X_0[i] = E[token]`` for every stream (the embedded row
  replicated); behind the last layer ``x_L = sum_i X_L[i]``; ``logits =
  RMSNorm(x_L) W_head``.
- **a layer** is two wraps, each with mixing parameters of its own::

      h, Hpost, Hres, X = hc_pre(X; hc_attn);  X = hc_post(X, Attn(RMSNorm(h)), Hpost, Hres)
      h, Hpost, Hres, X = hc_pre(X; hc_mlp);   X = hc_post(X, FFN(RMSNorm(h)), Hpost, Hres)

  (``hc_pre`` hands ``X`` on to ``hc_post``: the stream's two cotangents
  then meet in one pass of the wrap's backward.)

  ``Attn`` is ``models/deepseek_v3.py``'s latent attention with the query's
  low-rank step and YaRN (:func:`ddl_tpu.models.deepseek_v3.attn`), without
  its residual; ``FFN`` a dense SwiGLU in the first ``n_dense_layers``
  layers and ``moe.sigmoid_expert_mlp`` in the rest (one ungated shared
  expert), as Kanana-2 runs it, with the RANGE OF EXPERTS HELD HERE
  (``held_experts``) and for ``models/deepseek_v3.py``'s reasons.
- **the multi-token-prediction module** (``num_nextn_predict_layers`` 1;
  DeepSeek-V3's section 2.2): ``h'_i = [RMSNorm(E[t_(i+1)]; enorm) |
  RMSNorm(x_L,i; hnorm)] W_eh`` (2 C -> C) from the closed stream BEFORE the
  final norm, one whole routed layer of the model's own kind on a stream of
  its own (opened by replication, closed by the sum), ``logits'_i =
  RMSNorm(.; its norm) W_head`` under the main embedding and head, target
  ``t_(i+2)``.  **Train loss** ``= CE_main + MTP_LOSS_WEIGHT * CE_mtp``, the
  second over positions ``0 .. T-3``.  The row keeps its length (the targets
  are rolled and the last positions masked, as
  ``losses.next_token_cross_entropy`` does): under a causal mask the
  positions that count never see the rolled-in ones.

The two walks (the stack, the module's one layer) are planned together
(``remat.planned`` over ``L + 1`` layer bodies: one budget) and share
``decoder.walk``; the mixing matrices are ``remat.HC``'s.

Not here: a mesh (the wraps' parameters and the stream have no layout;
:func:`forward` refuses one by name), serving (the latent cache of
``models/deepseek_v3.py`` and a stream a cached position), ``expert_bias``'s
update, the wraps as kernels.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ddl_tpu.models import decoder as _decoder
from ddl_tpu.models import deepseek_v3 as _deepseek
from ddl_tpu.models import hyper_connections as _hc
from ddl_tpu.models import moe as _moe
from ddl_tpu.models import remat as _remat
from ddl_tpu.models.deepseek_v3 import Yarn
from ddl_tpu.models.losses import cross_entropy, next_token_cross_entropy
from ddl_tpu.ops.naming import scope

Params = Dict[str, Any]

#: Weight of the multi-token-prediction term in the train loss.  ASSUMED:
#: ``config.json`` has no key for it; DeepSeek-V3's late-stage value.
MTP_LOSS_WEIGHT = 0.1


@dataclasses.dataclass(frozen=True)
class Xing4Config(_deepseek.DeepseekV3Config):
    """``DeepseekV3Config`` (latent attention, the routed block) and what
    the published config adds to it."""

    hc_mult: int = 4
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    #: ``(mhc_h_res_clamp_min, mhc_h_res_clamp_max)``.
    hc_clamp: Tuple[float, float] = (-30.0, 30.0)
    #: ``num_nextn_predict_layers``: 0 or 1.
    n_mtp: int = 1

    def __post_init__(self) -> None:
        super().__post_init__()
        if self.n_mtp not in (0, 1):
            raise ValueError("one multi-token-prediction module, or none")
        if self.hc_mult < 2:
            raise ValueError("hc_mult counts the streams: at least two")

    @property
    def hc(self) -> _hc.HyperConnections:
        return _hc.HyperConnections(
            self.hc_mult, self.hc_sinkhorn_iters, self.hc_eps, self.hc_clamp,
            self.norm_eps)

    @staticmethod
    def xing4_0_29b_a4b() -> "Xing4Config":
        """Xing4.0-29B-A4B (``XingChen-AGI/Xing4.0-29B-A4B``, 29B total / 4B
        active) at full depth with every expert held: 40 layers, hidden
        3,584, 32 heads of 128 + 64 score and 128 value width over a 512-wide
        latent behind a 768-wide query step, YaRN x 64 over 4,096, two dense
        layers (SwiGLU 9,216) then 64 routed experts x 1,024, 4 per token,
        plus 1 shared; sigmoid scores, normalised, x 2; four streams, 20
        Sinkhorn rounds; one MTP module; vocabulary 131,072 untied; bf16
        storage.  The benchmark's configuration file builds the same config at
        its published depth, experts and vocabulary (a test holds the two
        together)."""
        return Xing4Config(
            vocab=131072, d_model=3584, n_layers=40, n_heads=32,
            qk_nope_dim=128, qk_rope_dim=64, v_head_dim=128, kv_lora_rank=512,
            q_lora_rank=768, d_ff=9216, d_expert=1024, n_experts=64, topk=4,
            n_shared_experts=1, n_dense_layers=2, route_norm=True,
            route_scale=2.0, max_seq=262144, rope_theta=1e4,
            rope_scaling=Yarn(64.0, 4096, 32.0, 1.0, 1.0, 1.0), norm_eps=1e-6,
            hc_mult=4, hc_sinkhorn_iters=20, hc_eps=1e-6, hc_clamp=(-30.0, 30.0),
            n_mtp=1, param_dtype=jnp.bfloat16,
        )


def _layer_rows(cfg: Xing4Config, dense: bool) -> List[_decoder.Row]:
    """A layer's parameters: ``models/deepseek_v3.py``'s and the two wraps'."""
    return [
        *_deepseek._layer_rows(cfg, dense),
        *_hc.wrap_rows("hc_attn", cfg.hc_mult, cfg.d_model),
        *_hc.wrap_rows("hc_mlp", cfg.hc_mult, cfg.d_model),
    ]


def _mtp_rows(cfg: Xing4Config) -> List[_decoder.Row]:
    """The module's parameters under ``mtp``: its three norms, ``W_eh`` and
    one routed layer; the embedding and the head are the model's."""
    if not cfg.n_mtp:
        return []
    d = cfg.d_model
    return [
        _decoder.ones("mtp.enorm", d), _decoder.ones("mtp.hnorm", d),
        _decoder.Row("mtp.w_eh", (2 * d, d), _decoder.COL),
        _decoder.ones("mtp.norm", d),
        *(row._replace(name="mtp.layer." + row.name)
          for row in _layer_rows(cfg, False)),
    ]


#: A layer draws at most 20 keys (five of attention, seven of a routed FFN,
#: four a wrap); the embedding, the head, ``W_eh`` and the module's layer 23.
_TABLE = _decoder.Table(
    _deepseek._kinds, _layer_rows, (23, 20), extra_rows=_mtp_rows)
init_params, param_specs = _TABLE.init_params, _TABLE.param_specs


def _layer_apply(layer: Params, X: jax.Array, cfg: Xing4Config,
                 positions: jax.Array, dense: bool, mesh: Optional[Any]):
    """One layer on the stream → (X, the router's picks or ``None``, no
    auxiliary loss)."""
    hc = cfg.hc
    with scope("ddl.hc_pre"):
        h, post, res, X = _hc.hc_pre(X, layer["hc_attn"], hc)
    y = _deepseek.attn(layer, h, cfg, positions, mesh, residual=False)
    with scope("ddl.hc_post"):
        X = _hc.hc_post(X, y, post, res)
    with scope("ddl.hc_pre"):
        h, post, res, X = _hc.hc_pre(X, layer["hc_mlp"], hc)
    with scope("ddl.mlp" if dense else "ddl.moe"):
        h = _decoder.rms_norm(h, layer["mlp_norm"], cfg.norm_eps)
        if dense:
            y, top_e = _decoder.swiglu(layer, h), None
        else:
            y, top_e = _moe.sigmoid_expert_mlp(h, layer, cfg, mesh)
    with scope("ddl.hc_post"):
        X = _hc.hc_post(X, y, post, res)
    return X, top_e, None


def _mtp_targets(tokens: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """(targets ``t_(i+2)``, the positions that have one)."""
    T = tokens.shape[1]
    mask = jnp.broadcast_to((jnp.arange(T) < T - 2)[None, :], tokens.shape)
    return jnp.roll(tokens, -2, axis=1), mask


def forward_all(
    params: Params, tokens: jax.Array, cfg: Xing4Config, mesh: Optional[Any] = None,
) -> Tuple[jax.Array, Optional[jax.Array], jax.Array]:
    """(logits (B, T, vocab) float32, the module's logits — position ``i``
    predicts ``t_(i+2)``; ``None`` without a module —, the expert ids every
    routed layer's router picked (L_routed, B, T, topk), the module's layer
    last, out of all ``n_experts``)."""
    if mesh is not None and mesh.devices.size > 1:
        raise NotImplementedError(
            "xing4: the hyper-connected stream and the wraps' parameters have "
            "no layout over a mesh yet")
    n, dt = cfg.hc_mult, cfg.dtype
    positions = jnp.arange(tokens.shape[1])

    def block(dense: bool):
        return lambda X, layer: _layer_apply(layer, X, cfg, positions, dense, mesh)

    bodies = [block(dense) for dense in _TABLE.kinds(cfg)]
    layers = list(params["layers"])
    if cfg.n_mtp:
        bodies.append(block(False))
        layers.append(params["mtp"]["layer"])
    L = cfg.n_layers
    x = _decoder.embed(params, tokens, cfg)
    with scope("ddl.hc_pre"):
        X = _hc.open_stream(x, n)
    logits_bytes = 4 * tokens.size * cfg.vocab * (1 + cfg.n_mtp)
    with _remat.planned(cfg.remat, bodies, X, layers, logits_bytes) as plan:
        X, picks, _ = _decoder.walk(X, layers[:L], bodies[:L], plan[:L], cfg)
        with scope("ddl.hc_post"):
            x = _hc.close_stream(X)  # the module reads it un-normed
        logits, mtp_logits = _decoder.lm_head(params, x, cfg), None
        if cfg.n_mtp:
            mtp = params["mtp"]
            with scope("ddl.mtp"):
                ahead = _decoder.embed(params, jnp.roll(tokens, -1, axis=1), cfg)
                both = jnp.concatenate([
                    _decoder.rms_norm(ahead, mtp["enorm"], cfg.norm_eps),
                    _decoder.rms_norm(x, mtp["hnorm"], cfg.norm_eps),
                ], axis=-1)
                h = both @ mtp["w_eh"].astype(dt)
                with scope("ddl.hc_pre"):
                    X = _hc.open_stream(h, n)
                X, mtp_picks, _ = _decoder.walk(X, layers[L:], bodies[L:], plan[L:], cfg)
                with scope("ddl.hc_post"):
                    x = _hc.close_stream(X)
                mtp_logits = _decoder.lm_head(
                    {"final_norm": mtp["norm"], "lm_head": params["lm_head"]}, x, cfg)
            picks = picks + mtp_picks
    return logits, mtp_logits, _decoder.stack_picks(picks, tokens, cfg.topk)


def forward_with_choices(
    params: Params, tokens: jax.Array, cfg: Xing4Config, mesh: Optional[Any] = None,
) -> Tuple[jax.Array, jax.Array]:
    """(logits, the routers' picks): :func:`forward_all` without the module's
    logits."""
    logits, _, picks = forward_all(params, tokens, cfg, mesh)
    return logits, picks


def forward(params: Params, tokens: jax.Array, cfg: Xing4Config,
            mesh: Optional[Any] = None) -> jax.Array:
    """Next-token logits, (B, T, vocab) float32."""
    return forward_all(params, tokens, cfg, mesh)[0]


def losses(params: Params, tokens: jax.Array, cfg: Xing4Config,
           mesh: Optional[Any] = None) -> Tuple[jax.Array, jax.Array]:
    """(``CE_main``, ``CE_mtp``): the train loss's two terms apart (the
    second 0 without a module)."""
    logits, mtp_logits, _ = forward_all(params, tokens, cfg, mesh)
    main = next_token_cross_entropy(logits, tokens)
    if mtp_logits is None:
        return main, jnp.zeros((), jnp.float32)
    with scope("ddl.mtp"):
        return main, cross_entropy(mtp_logits, *_mtp_targets(tokens))


def next_token_loss(params: Params, tokens: jax.Array, cfg: Xing4Config,
                    mesh: Optional[Any] = None) -> jax.Array:
    """The train loss: ``next_token_cross_entropy(forward(...))`` plus
    :data:`MTP_LOSS_WEIGHT` times the module's cross-entropy over targets
    two ahead.  No auxiliary router loss (``noaux_tc``)."""
    main, mtp = losses(params, tokens, cfg, mesh)
    return main + MTP_LOSS_WEIGHT * mtp if cfg.n_mtp else main


forward_with_cache, generate = _decoder.no_decode(
    "xing4", "a latent KV cache (models/deepseek_v3.py's) and the four-row "
    "stream of a cached position, which do not exist yet",
)
