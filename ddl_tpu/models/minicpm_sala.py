"""MiniCPM-SALA decoder LM (``model_type: minicpm_sala``): Lightning linear
attention and InfLLM-V2 block-sparse attention over one stack.

Every layer is a mixer kind followed by a SwiGLU MLP; the kind is static
data of the config (``mixer_types``: 24 ``lightning-attn`` to 8
``minicpm4`` in MiniCPM-SALA).  The stack is MiniCPM's muP, pre-norm::

    x = scale_emb E[ids]
    x = x + a Mixer(RMSNorm(x));   x = x + a SwiGLU(RMSNorm(x))
    logits = (RMSNorm(x) / (d_model / dim_model_base)) W_head

with ``a = scale_depth / sqrt(mup_denominator)``: the denominator is the
PUBLISHED depth (a field of its own), not the depth of a cut.

``lightning-attn`` (Lightning Attention-2, arXiv:2401.04658), ``H`` heads
of ``d``::

    q = RoPE(RMSNorm_d(h Wq) w_q),  k = RoPE(RMSNorm_d(h Wk) w_k),  v = h Wv
    S_t = lam_h S_{t-1} + k_t^T v_t,   o_t = q_t S_t / sqrt(d)
    y_t = RMSNorm_d(o_t) w_o * sigmoid(h_t Wg),   out = y Wo

``lam_h = exp(-2^(-8 (h + 1) / H))``, a constant of the head; the
recurrence is ``ops/lightning_attention.lightning_attention`` (chunked,
one Pallas kernel a pass).

``minicpm4`` (InfLLM-V2, arXiv:2509.24663): ``n_heads`` query heads over
``n_kv_heads`` key-value heads, per-head QK-norm, NO positions, a gated
output (Trinity-Mini's form, under ``ddl.attn_gate``).  A row up to
``dense_len`` is plain causal attention; a longer row attends a per-query
SELECTION of key blocks (``ops/sparse_attention.py``: compressed keys,
block scores, top-k, one selection a key-value group; no gradient flows
through it), through the one attention dispatcher either way.

What every decoder shares is ``models/decoder.py``'s: ``rms_norm``,
``rope``, ``swiglu``, the stack, the parameter table.  Serving is not here: a lightning layer's
cache is its recurrent state, a sparse layer's its keys, values AND
compressed keys, which nothing holds or measures, so
:func:`forward_with_cache` and :func:`generate` raise by name; nor is a
mesh (neither the scan nor the selection is shard-mapped yet).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ddl_tpu.models import decoder as _decoder
from ddl_tpu.models import remat as _remat
from ddl_tpu.ops.lightning_attention import lightning_attention
from ddl_tpu.ops.naming import scope
from ddl_tpu.ops.sparse_attention import SparseConfig, select_blocks

Params = Dict[str, Any]

LIGHTNING, SPARSE = "lightning-attn", "minicpm4"


@dataclasses.dataclass(frozen=True)
class MiniCPMSalaConfig:
    vocab: int = 256
    d_model: int = 64
    n_heads: int = 4  # the sparse layers' query heads
    n_kv_heads: int = 2
    head_dim: int = 16
    n_lightning_heads: int = 4  # query = key-value heads
    lightning_head_dim: int = 16
    d_ff: int = 128
    #: One mixer kind a layer; its length is the depth.
    mixer_types: Tuple[str, ...] = (SPARSE, LIGHTNING, LIGHTNING, LIGHTNING)
    rope_theta: float = 10000.0  # the lightning layers'; a sparse layer has none
    scale_emb: float = 12.0
    scale_depth: float = 1.4
    #: The depth the residual scale divides by: the PUBLISHED one.
    mup_denominator: int = 32
    dim_model_base: int = 256
    #: The sparse layers' selection (``ops/sparse_attention.SparseConfig``).
    sparse: SparseConfig = SparseConfig()
    #: Rows up to this length run plain causal attention.
    dense_len: int = 8192
    max_seq: int = 512
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    #: Remat policy, as :attr:`LlamaConfig.remat`.
    remat: Any = False
    attn_impl: str = "auto"

    def __post_init__(self) -> None:
        _remat.resolve(self.remat)  # fail on junk at config build time
        bad = set(self.mixer_types) - {LIGHTNING, SPARSE}
        if bad or not self.mixer_types:
            raise ValueError(f"mixer_types must be of {LIGHTNING!r}/{SPARSE!r}: {bad}")
        if self.n_heads % self.n_kv_heads:
            raise ValueError("n_kv_heads must divide n_heads")

    @property
    def n_layers(self) -> int:
        return len(self.mixer_types)

    @property
    def residual_scale(self) -> float:
        return self.scale_depth / self.mup_denominator**0.5

    @staticmethod
    def minicpm_sala() -> "MiniCPMSalaConfig":
        """MiniCPM-SALA (``openbmb/MiniCPM-SALA``) at full depth and
        vocabulary: 32 layers, sparse at 0, 9, 16, 17, 22, 29, 30, 31,
        hidden 4096, 32 x 128 query heads over 2 key-value heads, 32 x 128
        lightning heads, SwiGLU 16,384, vocabulary 73,448 untied; bf16
        storage.  The benchmark's configuration file builds the same
        config at its published depth and vocabulary (a test holds the two
        together)."""
        sparse_at = (0, 9, 16, 17, 22, 29, 30, 31)
        return MiniCPMSalaConfig(
            vocab=73448, d_model=4096, n_heads=32, n_kv_heads=2, head_dim=128,
            n_lightning_heads=32, lightning_head_dim=128, d_ff=16384,
            mixer_types=tuple(
                SPARSE if n in sparse_at else LIGHTNING for n in range(32)
            ),
            rope_theta=10000.0, scale_emb=12.0, scale_depth=1.4,
            mup_denominator=32, dim_model_base=256, sparse=SparseConfig(),
            dense_len=8192, max_seq=524288, norm_eps=1e-6,
            param_dtype=jnp.bfloat16,
        )


def _layer_rows(cfg: MiniCPMSalaConfig, kind: str) -> List[_decoder.Row]:
    """The parameter table of a layer (the Megatron fsdp x tp layout of the
    other families; per-head vectors replicated)."""
    d = cfg.d_model
    if kind == LIGHTNING:
        hd = cfg.lightning_head_dim
        qw = kw = cfg.n_lightning_heads * hd
    else:
        hd = cfg.head_dim
        qw, kw = cfg.n_heads * hd, cfg.n_kv_heads * hd
    rows = [
        _decoder.ones("input_norm", d),
        _decoder.ones("pre_mlp_norm", d),
        *_decoder.swiglu_rows(d, cfg.d_ff),
        *_decoder.attn_rows(d, qw, kw, gated=True),
        _decoder.ones("q_norm", hd),
        _decoder.ones("k_norm", hd),
    ]
    if kind == LIGHTNING:
        rows.append(_decoder.ones("o_norm", hd))
    return rows


#: ``init_params(cfg, key)`` — seeded normal / sqrt(fan_in) matrices,
#: unit-variance embedding rows, norm weights 1 — and ``param_specs(cfg)`` of
#: one table.
_TABLE = _decoder.Table(
    lambda cfg: cfg.mixer_types, _layer_rows, (2, 8), embed_fan_in=1
)
init_params, param_specs = _TABLE.init_params, _TABLE.param_specs


def _lightning_mixer(layer: Params, h: jax.Array, cfg: MiniCPMSalaConfig,
                     positions: jax.Array) -> jax.Array:
    """Fixed-decay linear attention on the normed stream ``h``."""
    B, T = h.shape[:2]
    dt, eps = h.dtype, cfg.norm_eps
    H, d = cfg.n_lightning_heads, cfg.lightning_head_dim
    with scope("ddl.lightning_proj"):
        heads = lambda w: (h @ layer[w].astype(dt)).reshape(B, T, H, d)
        q = _decoder.rms_norm(heads("wq"), layer["q_norm"], eps)
        k = _decoder.rms_norm(heads("wk"), layer["k_norm"], eps)
        q = _decoder.rope(q, positions, cfg.rope_theta)
        k = _decoder.rope(k, positions, cfg.rope_theta)
        v = heads("wv")
    with scope("ddl.lightning_scan"):
        o = lightning_attention(q, k, v)
    with scope("ddl.lightning_out"):
        gate = jax.nn.sigmoid(h @ layer["wg"].astype(dt)).reshape(B, T, H, d)
        y = _decoder.rms_norm(o, layer["o_norm"], eps) * gate
        return y.reshape(B, T, -1) @ layer["wo"].astype(dt)


def _sparse_mixer(layer: Params, h: jax.Array, cfg: MiniCPMSalaConfig,
                  mesh: Optional[Any]) -> jax.Array:
    """Gated grouped-query attention without positions: causal up to
    ``dense_len``, over a selection of key blocks beyond."""
    from ddl_tpu.parallel.ring_attention import attention

    B, T = h.shape[:2]
    dt, eps = h.dtype, cfg.norm_eps
    with scope("ddl.attn"):

        def heads(w: str, n: int) -> jax.Array:
            return (h @ layer[w].astype(dt)).reshape(B, T, n, cfg.head_dim)

        q = _decoder.rms_norm(heads("wq", cfg.n_heads), layer["q_norm"], eps)
        k = _decoder.rms_norm(heads("wk", cfg.n_kv_heads), layer["k_norm"], eps)
        v = heads("wv", cfg.n_kv_heads)
        selection = None
        if T > cfg.dense_len:
            with scope("ddl.sparse_select"):
                selection = select_blocks(q, k, cfg.sparse)
        attn = attention(
            q, k, v, mesh=mesh, impl=cfg.attn_impl, causal=True,
            kv_repeat=cfg.n_heads // cfg.n_kv_heads, selection=selection,
        )
        with scope("ddl.attn_gate"):
            gate = jax.nn.sigmoid(h @ layer["wg"].astype(dt))
            gated = attn.reshape(B, T, -1) * gate
        return gated @ layer["wo"].astype(dt)


def _layer_apply(layer: Params, x: jax.Array, cfg: MiniCPMSalaConfig,
                 positions: jax.Array, sparse: bool, mesh: Optional[Any]) -> jax.Array:
    """One block of the stated mixer kind."""
    a = cfg.residual_scale
    with scope("ddl.attn" if sparse else "ddl.lightning_proj"):
        h = _decoder.rms_norm(x, layer["input_norm"], cfg.norm_eps)
    if sparse:
        out = _sparse_mixer(layer, h, cfg, mesh)
    else:
        out = _lightning_mixer(layer, h, cfg, positions)
    with scope("ddl.attn" if sparse else "ddl.lightning_out"):
        x = x + (a * out).astype(x.dtype)
    with scope("ddl.mlp"):
        h = _decoder.rms_norm(x, layer["pre_mlp_norm"], cfg.norm_eps)
        return x + (a * _decoder.swiglu(layer, h)).astype(x.dtype)


def forward(
    params: Params,
    tokens: jax.Array,
    cfg: MiniCPMSalaConfig,
    mesh: Optional[Any] = None,
) -> jax.Array:
    """Next-token logits, (B, T, vocab) float32."""
    if mesh is not None and mesh.devices.size > 1:
        raise NotImplementedError(
            "minicpm_sala.forward(mesh=): neither the lightning scan nor the "
            "block selection is shard-mapped over a mesh yet"
        )
    positions = jnp.arange(tokens.shape[1])

    def block(kind: str):
        return lambda x, layer: _layer_apply(
            layer, x, cfg, positions, kind == SPARSE, mesh
        )

    # logits = (RMSNorm(x) / (d_model / dim_model_base)) W_head
    return _decoder.forward(
        params, tokens, cfg, _TABLE, block, embed_scale=cfg.scale_emb,
        head_scale=cfg.dim_model_base / cfg.d_model,
    )[0]


next_token_loss = _decoder.loss_of(forward)

forward_with_cache, generate = _decoder.no_decode(
    "minicpm_sala", "the recurrent-state and compressed-key caches: a "
    "lightning-attn layer's is its recurrent state, a minicpm4 layer's holds "
    "compressed keys beside keys and values; neither exists yet",
)
