"""AFMoE decoder LM (``model_type: afmoe`` — Arcee's Trinity family):
layers of different kinds over one stack.

Every layer is an attention kind followed by an MLP kind, both static
data of the config (``layer_types``, ``n_dense_layers``):

- attention: ``sliding_attention`` (causal inside a ``sliding_window``,
  RoPE on q and k) or ``full_attention`` (causal over the whole row, NO
  position encoding).  Both: grouped-query heads of an explicit
  ``head_dim`` (32 x 128 over a hidden size of 2048 in Trinity-Mini),
  RMSNorm with one learned ``head_dim`` weight applied per head to q and
  k, and an output gate — ``(softmax(scores) v * sigmoid(h Wg)) Wo``.
- MLP: a dense SwiGLU (the first ``n_dense_layers`` layers) or routed
  experts plus a shared expert — ``s = sigmoid(h Wr)`` in float32,
  ``sel = top_k(s + expert_bias)`` (the bias enters the selection only),
  ``w = s[sel] / (sum(s[sel]) + 1e-20) * route_scale``, ``Shared(h) +
  sum_k w_k Expert_sel_k(h)``.
- the block: sandwich norms, ``x + post_norm(f(pre_norm(x)))`` for both
  halves; the embedding is scaled by ``sqrt(d_model)`` (``mup_enabled``).

What every decoder shares is ``models/decoder.py``'s (``rope``,
``rms_norm``, ``swiglu``, the stack, the parameter table) and the attention
dispatcher's (``window=`` for the sliding layers).  The routed experts are
``moe.ragged_experts``, the dropless core OLMoE runs, handed this
family's scoring and the RANGE OF EXPERTS HELD HERE
(``held_experts=(first, count)`` of the router's ``n_experts``): one
chip's share of a layer whose experts are divided over chips.  The
share computes what its own experts add for the tokens routed to them;
what the others would have added is left out, and nothing stands in for
them or for their exchange; nor does a share train its router
(``moe.sigmoid_expert_tokens`` says why).  With the whole range it is the uncut layer.

Serving is not here: a sliding layer's cache is a ring of
``sliding_window`` entries and nothing measures one, so
:func:`forward_with_cache` and :func:`generate` raise by name.
``expert_bias`` stays where initialisation put it (zeros): the published
recipe moves it outside the gradient (``load_balance_coeff``), and that
update does not exist here yet.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ddl_tpu.models import decoder as _decoder
from ddl_tpu.models import moe as _moe
from ddl_tpu.models import remat as _remat
from ddl_tpu.ops.naming import scope

Params = Dict[str, Any]

SLIDING, FULL = "sliding_attention", "full_attention"


@dataclasses.dataclass(frozen=True)
class AfmoeConfig:
    vocab: int = 256
    d_model: int = 64
    n_heads: int = 4
    n_kv_heads: int = 2
    #: Stated, not derived: Trinity-Mini's 32 heads x 128 are twice its
    #: hidden size.
    head_dim: int = 32
    d_ff: int = 192  # the dense layers' SwiGLU width
    d_expert: int = 32  # each routed expert's, and the shared expert's
    n_experts: int = 8  # the router's width, whatever is held here
    topk: int = 2
    n_shared_experts: int = 1
    #: One attention kind a layer; its length is the depth.
    layer_types: Tuple[str, ...] = (SLIDING, FULL)
    #: The leading layers whose MLP is dense; the rest route.
    n_dense_layers: int = 1
    sliding_window: int = 16
    route_norm: bool = True
    route_scale: float = 1.0
    #: Added to the picked scores' sum where ``route_norm`` divides by it.
    route_eps: float = 1e-20
    mup_enabled: bool = True
    #: ``(first, count)`` of the ``n_experts`` whose weights live here;
    #: ``None`` is all of them.
    held_experts: Optional[Tuple[int, int]] = None
    max_seq: int = 512
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    #: Remat policy, as :attr:`LlamaConfig.remat`.
    remat: Any = False
    attn_impl: str = "auto"

    def __post_init__(self) -> None:
        _remat.resolve(self.remat)  # fail on junk at config build time
        bad = set(self.layer_types) - {SLIDING, FULL}
        if bad or not self.layer_types:
            raise ValueError(f"layer_types must be of {SLIDING!r}/{FULL!r}: {bad}")
        if not 0 <= self.n_dense_layers <= len(self.layer_types):
            raise ValueError("n_dense_layers outside the stack")
        if self.n_heads % self.n_kv_heads:
            raise ValueError("n_kv_heads must divide n_heads")
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
    def held(self) -> Tuple[int, int]:
        """(first, count) of the experts held here."""
        return self.held_experts or (0, self.n_experts)

    def is_dense(self, layer: int) -> bool:
        return layer < self.n_dense_layers

    @staticmethod
    def trinity_mini() -> "AfmoeConfig":
        """Trinity-Mini (``arcee-ai/Trinity-Mini``, 26B total / 3B active)
        at full depth with every expert held: 32 layers, three sliding
        (window 2048) then one full, two leading dense layers (SwiGLU
        6144) then 128 routed experts x 1024, 8 per token, plus one
        shared; sigmoid scores, normalised, x 2.826; vocabulary 200,192
        untied; bf16 storage.  The benchmark's configuration file builds
        the same config at its published depth, experts and vocabulary
        (a test holds the two together)."""
        return AfmoeConfig(
            vocab=200192, d_model=2048, n_heads=32, n_kv_heads=4,
            head_dim=128, d_ff=6144, d_expert=1024, n_experts=128, topk=8,
            n_shared_experts=1, layer_types=(SLIDING, SLIDING, SLIDING, FULL) * 8,
            n_dense_layers=2, sliding_window=2048, route_norm=True,
            route_scale=2.826, mup_enabled=True, max_seq=8192,
            rope_theta=10000.0, norm_eps=1e-5, param_dtype=jnp.bfloat16,
        )


def _kinds(cfg: AfmoeConfig) -> Tuple[Tuple[bool, bool], ...]:
    """A layer's kind: (its attention slides, its MLP is dense)."""
    return tuple(
        (kind == SLIDING, cfg.is_dense(li)) for li, kind in enumerate(cfg.layer_types)
    )


def _layer_rows(cfg: AfmoeConfig, kind: Tuple[bool, bool]) -> List[_decoder.Row]:
    """The parameter table of a layer (the Megatron fsdp x tp layout)."""
    d, hd = cfg.d_model, cfg.head_dim
    return [
        _decoder.ones("input_norm", d),
        _decoder.ones("post_attn_norm", d),
        _decoder.ones("pre_mlp_norm", d),
        _decoder.ones("post_mlp_norm", d),
        *_decoder.attn_rows(d, cfg.n_heads * hd, cfg.n_kv_heads * hd, gated=True),
        _decoder.ones("q_norm", hd),
        _decoder.ones("k_norm", hd),
        *(_decoder.swiglu_rows(d, cfg.d_ff) if kind[1]
          else _moe.sigmoid_expert_rows(cfg)),
    ]


#: ``init_params(cfg, key)`` — seeded normal / sqrt(fan_in) matrices, norm
#: weights 1, ``expert_bias`` 0 — and ``param_specs(cfg)`` of one table.
_TABLE = _decoder.Table(_kinds, _layer_rows, (2, 12))
init_params, param_specs = _TABLE.init_params, _TABLE.param_specs


def _attn_block(
    layer: Params,
    x: jax.Array,
    cfg: AfmoeConfig,
    positions: jax.Array,
    sliding: bool,
    mesh: Optional[Any],
) -> jax.Array:
    """Gated attention with sandwich norms on the residual stream."""
    from ddl_tpu.parallel.ring_attention import attention

    B, T = x.shape[:2]
    dt = x.dtype
    eps = cfg.norm_eps
    with scope("ddl.attn"):
        h = _decoder.rms_norm(x, layer["input_norm"], eps)

        def heads(w: str, n: int) -> jax.Array:
            return (h @ layer[w].astype(dt)).reshape(B, T, n, cfg.head_dim)

        # One head_dim-long weight, applied to every head.
        q = _decoder.rms_norm(heads("wq", cfg.n_heads), layer["q_norm"], eps)
        k = _decoder.rms_norm(heads("wk", cfg.n_kv_heads), layer["k_norm"], eps)
        v = heads("wv", cfg.n_kv_heads)
        if sliding:  # a full layer carries no position encoding
            q = _decoder.rope(q, positions, cfg.rope_theta)
            k = _decoder.rope(k, positions, cfg.rope_theta)
        attn = attention(
            q, k, v, mesh=mesh, impl=cfg.attn_impl, causal=True,
            kv_repeat=cfg.n_heads // cfg.n_kv_heads,
            window=cfg.sliding_window if sliding else None,
        )
        with scope("ddl.attn_gate"):
            gate = jax.nn.sigmoid(h @ layer["wg"].astype(dt))
            gated = attn.reshape(B, T, -1) * gate
        out = gated @ layer["wo"].astype(dt)
        return x + _decoder.rms_norm(out, layer["post_attn_norm"], eps)


# The routed + shared expert layer is ``moe.sigmoid_expert_mlp``, the one
# routine this family and ``models/deepseek_v3.py`` run (a share's router is
# not trained: ``moe.sigmoid_expert_tokens`` says why).
_moe_tokens = _moe.sigmoid_expert_tokens
_moe_mlp = _moe.sigmoid_expert_mlp


def _layer_apply(
    layer: Params,
    x: jax.Array,
    cfg: AfmoeConfig,
    positions: jax.Array,
    sliding: bool,
    dense: bool,
    mesh: Optional[Any],
):
    """One block of the stated kinds → (x, the router's picks (B, T,
    topk), or ``None`` from a dense layer, no auxiliary loss)."""
    x = _attn_block(layer, x, cfg, positions, sliding, mesh)
    with scope("ddl.mlp" if dense else "ddl.moe"):
        h = _decoder.rms_norm(x, layer["pre_mlp_norm"], cfg.norm_eps)
        if dense:
            out, top_e = _decoder.swiglu(layer, h), None
        else:
            out, top_e = _moe_mlp(h, layer, cfg, mesh)
        out = _decoder.rms_norm(out, layer["post_mlp_norm"], cfg.norm_eps)
        return x + out, top_e, None


def forward_with_choices(
    params: Params,
    tokens: jax.Array,
    cfg: AfmoeConfig,
    mesh: Optional[Any] = None,
) -> Tuple[jax.Array, jax.Array]:
    """(logits (B, T, vocab) float32, the expert ids every expert layer's
    router picked (L_expert, B, T, topk) — out of all ``n_experts``, held
    here or not)."""
    positions = jnp.arange(tokens.shape[1])

    def block(kind: Tuple[bool, bool]):
        return lambda x, layer: _layer_apply(layer, x, cfg, positions, *kind, mesh)

    scale = (
        jnp.asarray(math.sqrt(cfg.d_model), cfg.dtype) if cfg.mup_enabled else None
    )
    logits, picks, _ = _decoder.forward(
        params, tokens, cfg, _TABLE, block, embed_scale=scale
    )
    return logits, _decoder.stack_picks(picks, tokens, cfg.topk)


def forward(
    params: Params,
    tokens: jax.Array,
    cfg: AfmoeConfig,
    mesh: Optional[Any] = None,
) -> jax.Array:
    """Next-token logits, (B, T, vocab) float32."""
    return forward_with_choices(params, tokens, cfg, mesh)[0]


#: Mean next-token cross-entropy.  No auxiliary router loss: the published
#: recipe balances by moving ``expert_bias``, not by a term of the loss.
next_token_loss = _decoder.loss_of(forward)

forward_with_cache, generate = _decoder.no_decode(
    "afmoe", "the windowed KV cache: a sliding_attention layer's is a ring "
    "of sliding_window entries, which does not exist yet",
)
