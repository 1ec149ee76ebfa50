"""Olmo-Hybrid decoder LM (``model_type: olmo_hybrid``): gated-delta-rule
linear attention and full softmax attention over one stack.

Every layer is a mixer kind followed by a SwiGLU MLP; the kind is static
data of the config (``layer_types``: three ``linear_attention`` to one
``full_attention`` in Olmo-Hybrid-7B).  Norm placement is OLMo-2/3's
(``norm_placement`` in the benchmark's configuration file, ASSUMED there):
no norm in front of a block, one on its output, so with ``h = x`` the
residual stream itself::

    x = x + RMSNorm(Mixer(x));   x = x + RMSNorm(SwiGLU(x))

``linear_attention`` (Gated DeltaNet, arXiv:2412.06464), ``H`` heads of
``d_k`` keys and ``d_v`` values (30 x 96 / 192)::

    q~ = SiLU(conv(h Wq)), k~ = SiLU(conv(h Wk)), v = SiLU(conv(h Wv))
                                causal depthwise, kernel 4, no bias
    q = q~ / ||q~||_2 / sqrt(d_k),  k = k~ / ||k~||_2           per head
    beta_t = 2 sigmoid(h_t Wb)   (the 2: ``linear_allow_neg_eigval``)
    g_t = -exp(A_log) softplus(h_t Wa + dt_bias),  alpha_t = exp(g_t)
    S_t = alpha_t S_{t-1} + beta_t (v_t - alpha_t S_{t-1} k_t) k_t^T
    o_t = S_t q_t                       S in R^{d_v x d_k}, float32, S_0 = 0
    y_t = RMSNorm_{d_v}(o_t) * SiLU(h_t Wg),   out = y Wo

The recurrence is ``ops/gated_delta.gated_delta_rule`` (chunked; a chunk
from q, k, v, g and beta to o a Pallas kernel with a backward pass); ``g``
and ``beta`` are float32.

``full_attention``: causal softmax attention, ``n_heads`` x ``head_dim``,
NO position encoding (``rope_theta`` null), RMSNorm with a learned weight
over the whole projected q and k (the form ``LlamaConfig.qk_norm`` has),
through the one attention dispatcher.

What every decoder shares is ``models/decoder.py``'s: ``rms_norm``,
``swiglu``, the stack, the parameter table.  Serving is not here: a linear layer's cache is its
recurrent state and the convolution's last three inputs, which nothing
holds or measures, so :func:`forward_with_cache` and :func:`generate`
raise by name; nor is a mesh (the scan is not shard-mapped yet).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ddl_tpu.models import decoder as _decoder
from ddl_tpu.models import remat as _remat
from ddl_tpu.ops.gated_delta import gated_delta_rule
from ddl_tpu.ops.naming import scope

Params = Dict[str, Any]

LINEAR, FULL = "linear_attention", "full_attention"


@dataclasses.dataclass(frozen=True)
class OlmoHybridConfig:
    vocab: int = 256
    d_model: int = 64
    n_heads: int = 4  # the full-attention layers'; head_dim = d_model / n_heads
    d_ff: int = 192
    #: One mixer kind a layer; its length is the depth.
    layer_types: Tuple[str, ...] = (LINEAR, LINEAR, LINEAR, FULL)
    n_linear_heads: int = 4  # key heads = value heads
    linear_key_dim: int = 8
    linear_value_dim: int = 16
    conv_kernel: int = 4
    #: ``linear_allow_neg_eigval``: beta in (0, 2), so that a state
    #: transition ``I - beta k k^T`` may have the eigenvalue -1.
    allow_neg_eigval: bool = True
    max_seq: int = 512
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    #: Remat policy, as :attr:`LlamaConfig.remat`.
    remat: Any = False
    attn_impl: str = "auto"

    def __post_init__(self) -> None:
        _remat.resolve(self.remat)  # fail on junk at config build time
        bad = set(self.layer_types) - {LINEAR, FULL}
        if bad or not self.layer_types:
            raise ValueError(f"layer_types must be of {LINEAR!r}/{FULL!r}: {bad}")
        if self.d_model % self.n_heads:
            raise ValueError("n_heads must divide d_model")

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @staticmethod
    def olmo_hybrid_7b() -> "OlmoHybridConfig":
        """Olmo-Hybrid-7B (``allenai/Olmo-Hybrid-7B``) at full depth and
        vocabulary: 32 layers, three linear to one full, hidden 3840, 30
        linear heads of 96 / 192, 30 full heads of 128, SwiGLU 11,008,
        vocabulary 100,352 untied; bf16 storage.  The benchmark's
        configuration file builds the same config at its published depth
        and vocabulary (a test holds the two together)."""
        return OlmoHybridConfig(
            vocab=100352, d_model=3840, n_heads=30, d_ff=11008,
            layer_types=(LINEAR, LINEAR, LINEAR, FULL) * 8, n_linear_heads=30,
            linear_key_dim=96, linear_value_dim=192, conv_kernel=4,
            allow_neg_eigval=True, max_seq=65536, norm_eps=1e-6,
            param_dtype=jnp.bfloat16,
        )


def _draw_dt_bias(key: jax.Array, shape: Tuple[int, ...]) -> jax.Array:
    """The inverse softplus of a step log-uniform on (1e-3, 1e-1)."""
    step = jnp.exp(jax.random.uniform(
        key, shape, jnp.float32, math.log(1e-3), math.log(1e-1)
    ))
    return step + jnp.log(-jnp.expm1(-step))


def _draw_a_log(key: jax.Array, shape: Tuple[int, ...]) -> jax.Array:
    """``log A`` with ``A`` uniform on (0, 16)."""
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1e-3, 16.0))


def _layer_rows(cfg: OlmoHybridConfig, kind: str) -> List[_decoder.Row]:
    """The parameter table of a layer, in the order its keys are drawn (the
    Megatron fsdp x tp layout of the other families; per-head vectors and
    the convolutions' taps replicated).  The convolutions' fan-in is their
    kernel; ``A_log`` and ``dt_bias`` are drawn as Gated DeltaNet's
    reference implementation draws them, both float32 whatever the storage
    dtype."""
    Row, col, row = _decoder.Row, _decoder.COL, _decoder.ROW
    d, H, K = cfg.d_model, cfg.n_linear_heads, cfg.conv_kernel
    qk, vv = H * cfg.linear_key_dim, H * cfg.linear_value_dim
    rows = [
        _decoder.ones("post_attn_norm", d),
        _decoder.ones("post_mlp_norm", d),
        *_decoder.swiglu_rows(d, cfg.d_ff),
    ]
    if kind == FULL:
        return rows + [
            *_decoder.attn_rows(d, d, d),
            _decoder.ones("q_norm", d), _decoder.ones("k_norm", d),
        ]
    none = P(None, None)
    return rows + [
        Row("dt_bias", (H,), P(None), draw=_draw_dt_bias, dtype=jnp.float32),
        Row("wq", (d, qk), col), Row("wk", (d, qk), col), Row("wv", (d, vv), col),
        Row("wa", (d, H), none), Row("wb", (d, H), none), Row("wg", (d, vv), col),
        Row("wo", (vv, d), row),
        Row("conv_q", (K, qk), none), Row("conv_k", (K, qk), none),
        Row("conv_v", (K, vv), none),
        Row("A_log", (H,), P(None), draw=_draw_a_log, dtype=jnp.float32),
        _decoder.ones("o_norm", cfg.linear_value_dim),
    ]


#: ``init_params(cfg, key)`` — seeded normal / sqrt(fan_in) matrices,
#: unit-variance embedding rows (no norm stands between them and the first
#: block), norm weights 1 — and ``param_specs(cfg)`` of one table.
_TABLE = _decoder.Table(
    lambda cfg: cfg.layer_types, _layer_rows, (2, 16), embed_fan_in=1
)
init_params, param_specs = _TABLE.init_params, _TABLE.param_specs


@jax.custom_vjp
def _silu_conv(x: jax.Array, taps: jax.Array) -> jax.Array:
    """``SiLU`` of the causal depthwise convolution of ``x`` (B, T, C) with
    ``taps`` (K, C), accumulated in float32, in ``x``'s dtype: position
    ``t`` sees ``x[t-K+1 .. t]``, the last tap on ``x[t]``; what lies before
    the row is zero.  Its own backward pass, so that the K shifted products
    are one pass over the cotangent and never K arrays of the row's size
    (autodiff's are float32 and 360 MB each at 16,384 x 5,760), and the
    pre-activation is recomputed there, not kept."""
    K, T = taps.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    return jax.nn.silu(_decoder.taps_sum(padded, taps, T, False)).astype(x.dtype)


def _silu_conv_fwd(x, taps):
    return _silu_conv(x, taps), (x, taps)


def _silu_conv_bwd(res, dy):
    x, taps = res
    K, T = taps.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    pre = _decoder.taps_sum(padded, taps, T, False)
    sig = jax.nn.sigmoid(pre)
    d_pre = dy.astype(jnp.float32) * sig * (1.0 + pre * (1.0 - sig))
    # x[t] met tap j at output t + K - 1 - j: the same sum over the
    # cotangent padded BEHIND the row, taps in reverse.
    behind = jnp.pad(d_pre, ((0, 0), (0, K - 1), (0, 0)))
    d_x = _decoder.taps_sum(behind, taps, T, True).astype(x.dtype)
    d_taps = jnp.stack([
        jnp.sum(d_pre * padded[:, j : j + T].astype(jnp.float32), axis=(0, 1))
        for j in range(K)
    ]).astype(taps.dtype)
    return d_x, d_taps


_silu_conv.defvjp(_silu_conv_fwd, _silu_conv_bwd)


def _unit(x: jax.Array) -> jax.Array:
    """``x / ||x||_2`` over the last axis, in float32."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _linear_block(layer: Params, x: jax.Array, cfg: OlmoHybridConfig) -> jax.Array:
    """Gated-delta-rule linear attention on the residual stream."""
    B, T = x.shape[:2]
    dt, f32 = x.dtype, jnp.float32
    H, dk, dv = cfg.n_linear_heads, cfg.linear_key_dim, cfg.linear_value_dim
    with scope("ddl.gdn_proj"):
        q, k, v, a, b, gate = (
            x @ layer[w].astype(dt) for w in ("wq", "wk", "wv", "wa", "wb", "wg")
        )
    with scope("ddl.gdn_conv"):
        q = _unit(_silu_conv(q, layer["conv_q"]).reshape(B, T, H, dk)) * dk**-0.5
        k = _unit(_silu_conv(k, layer["conv_k"]).reshape(B, T, H, dk))
        v = _silu_conv(v, layer["conv_v"]).reshape(B, T, H, dv)
        beta = jax.nn.sigmoid(b.astype(f32))
        if cfg.allow_neg_eigval:
            beta = 2.0 * beta
        g = -jnp.exp(layer["A_log"].astype(f32)) * jax.nn.softplus(
            a.astype(f32) + layer["dt_bias"].astype(f32)
        )
    with scope("ddl.gdn_scan"):
        o = gated_delta_rule(q.astype(dt), k.astype(dt), v, g, beta)
    with scope("ddl.gdn_out"):
        y = _decoder.rms_norm(o, layer["o_norm"], cfg.norm_eps) * jax.nn.silu(
            gate.reshape(B, T, H, dv)
        )
        out = y.reshape(B, T, -1) @ layer["wo"].astype(dt)
        return x + _decoder.rms_norm(out, layer["post_attn_norm"], cfg.norm_eps)


def _full_block(
    layer: Params, x: jax.Array, cfg: OlmoHybridConfig, mesh: Optional[Any]
) -> jax.Array:
    """Causal softmax attention without positions, QK-norm over the whole
    projection."""
    from ddl_tpu.parallel.ring_attention import attention

    B, T = x.shape[:2]
    dt, eps = x.dtype, cfg.norm_eps
    with scope("ddl.attn"):

        def heads(y: jax.Array) -> jax.Array:
            return y.reshape(B, T, cfg.n_heads, cfg.head_dim)

        q = heads(_decoder.rms_norm(x @ layer["wq"].astype(dt), layer["q_norm"], eps))
        k = heads(_decoder.rms_norm(x @ layer["wk"].astype(dt), layer["k_norm"], eps))
        v = heads(x @ layer["wv"].astype(dt))
        attn = attention(q, k, v, mesh=mesh, impl=cfg.attn_impl, causal=True)
        out = attn.reshape(B, T, -1) @ layer["wo"].astype(dt)
        return x + _decoder.rms_norm(out, layer["post_attn_norm"], eps)


def _layer_apply(
    layer: Params, x: jax.Array, cfg: OlmoHybridConfig, linear: bool,
    mesh: Optional[Any],
) -> jax.Array:
    """One block of the stated mixer kind."""
    x = _linear_block(layer, x, cfg) if linear else _full_block(layer, x, cfg, mesh)
    with scope("ddl.mlp"):
        out = _decoder.swiglu(layer, x)
        return x + _decoder.rms_norm(out, layer["post_mlp_norm"], cfg.norm_eps)


def forward(
    params: Params,
    tokens: jax.Array,
    cfg: OlmoHybridConfig,
    mesh: Optional[Any] = None,
) -> jax.Array:
    """Next-token logits, (B, T, vocab) float32."""
    if mesh is not None and mesh.devices.size > 1:
        raise NotImplementedError(
            "olmo_hybrid.forward(mesh=): the gated-delta scan is not "
            "shard-mapped over a mesh yet"
        )

    def block(kind: str):
        return lambda x, layer: _layer_apply(layer, x, cfg, kind == LINEAR, mesh)

    return _decoder.forward(params, tokens, cfg, _TABLE, block)[0]


next_token_loss = _decoder.loss_of(forward)

forward_with_cache, generate = _decoder.no_decode(
    "olmo_hybrid", "the recurrent-state cache: a linear_attention layer's is "
    "its recurrent state and the convolution's last inputs, which do not exist yet",
)
