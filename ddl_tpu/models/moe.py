"""Mixture-of-Experts decoder LM, and the routed-expert core its
siblings share.

The reference had no models and no expert parallelism (SURVEY §2.3 lists EP
as absent).  Two dispatches live here, chosen by what the mesh shows
(``moe_impl="auto"``):

- **Dropless ragged** (:func:`ragged_experts`, wherever no ``ep`` axis
  shards the experts): every (token, slot) choice becomes a row, rows are
  stably sorted by expert, and the three expert matmuls run as
  ``jax.lax.ragged_dot`` group-wise dots against the stacked weights — no
  capacity, no dropped token, no one-hots.  The core takes the router's
  scoring AS GIVEN — (weights, expert ids) per token and slot — so each
  family brings its own: float32 softmax top-k, renormalised
  (``norm_topk_prob``, Mixtral) or raw (OLMoE), here
  (:func:`_router_topk`); sigmoid + selection bias + normalise + scale
  with an ungated shared expert (:func:`sigmoid_expert_mlp`, the one
  routine ``models/afmoe.py`` and ``models/deepseek_v3.py`` both run).  And it takes the RANGE OF EXPERTS IT HOLDS: told
  ``held=(first, count)`` of a wider router, it routes over all of them,
  computes the rows whose choice falls on a held expert and leaves the
  rest out — one chip's share of an expert-parallel layer, without the
  exchange and with nothing standing in for it.  A range narrower than
  half the router runs its row passes over a static bound of held rows
  (:func:`held_row_bound`) and at full width past it, one ``lax.cond`` a
  pass: still dropless, at any routing.
- **Capacity-bounded einsums** (:func:`moe_mlp`, only where an ``ep`` mesh
  axis shards the expert stacks): the GShard/Switch formulation — fully
  static dispatch/combine one-hots, per-expert capacity ``C =
  ceil(topk·N/E·capacity_factor)``, overflow tokens dropped (their combine
  weight is zero, the residual stream carries them) — whose ``ep``
  all-to-alls GSPMD derives from the sharding annotations.  An explicit
  all-to-all for a dropless ``ep`` does not exist yet (ROADMAP).
- Experts: stacked SwiGLU MLPs ``(E, D, F)``, sharded ``P("ep", "fsdp",
  "tp")`` so each device holds ``E/ep`` experts.
- Router losses: the Switch load-balance term ``E · Σ_e
  fraction_dispatched(e) · mean_router_prob(e)`` on slot 0 (default) or
  over all top-k slots (``router_aux_all_slots``, the HF
  ``load_balancing_loss_func`` count), and the router z-loss
  ``mean(logsumexp(router logits)²)`` (``router_z_weight``).

Attention/norms/RoPE reuse the llama building blocks and the shared
attention dispatcher (ring attention over ``sp``, Pallas flash kernel on
TPU).
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ddl_tpu.models import decoder as _decoder
from ddl_tpu.models import llama as _llama
from ddl_tpu.models import remat as _remat
from ddl_tpu.models.losses import next_token_cross_entropy
from ddl_tpu.ops.naming import scope

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class MoeConfig:
    vocab: int = 256
    d_model: int = 128
    n_layers: int = 2
    n_heads: int = 4
    n_kv_heads: int = 2
    d_ff: int = 256  # per-expert hidden size
    n_experts: int = 4
    topk: int = 2
    capacity_factor: float = 1.25
    router_aux_weight: float = 0.01
    max_seq: int = 512
    rope_theta: float = 500000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    #: Storage dtype of the params pytree (see LlamaConfig.param_dtype —
    #: bf16 halves param+optimizer HBM; expert stacks dominate MoE HBM).
    param_dtype: Any = jnp.float32
    #: Remat policy (none/full/selective/dots, bools for back compat —
    #: see :attr:`LlamaConfig.remat` / :mod:`ddl_tpu.models.remat`); the
    #: capacity-bounded dispatch/combine einsums are the big activations
    #: here, and "selective" keeps the attention outputs saved.
    remat: Any = False
    attn_impl: str = "auto"
    #: QK-norm over the whole projected query and key (OLMoE) — see
    #: :attr:`LlamaConfig.qk_norm`; the attention block is llama's.
    qk_norm: bool = False
    #: Renormalise the chosen top-k probabilities to sum to 1 (Mixtral;
    #: the default).  Off (OLMoE, ``norm_topk_prob: false``) the gate
    #: weights are the raw softmax values and sum to less than 1.
    norm_topk_prob: bool = True
    #: The load-balance term counts every top-k slot's choice
    #: (``f_e`` = choices of expert e / N, so ``Σ f_e = topk`` and the
    #: term reads ``topk`` at perfect balance — HF's
    #: ``load_balancing_loss_func``) instead of slot 0 alone (Switch).
    router_aux_all_slots: bool = False
    #: Weight of the router z-loss ``mean_tokens(logsumexp(logits)²)``,
    #: averaged over layers like the load-balance term (0: not computed
    #: into the loss).
    router_z_weight: float = 0.0
    #: Expert-MLP dispatch implementation.  "ragged": sort-based
    #: dropless routing over ``jax.lax.ragged_dot`` — no capacity, no
    #: dropped token, no (N, E, C) one-hots.  On a v5e XLA compiles each
    #: ragged dot and both of its transposes to its own grouped-matmul
    #: kernels (trace families ``ragged-dot-*``), no dense expansion.
    #: Token-sharded meshes (dp/sp) run the routing per shard under
    #: shard_map (dropless, so local == global routing exactly);
    #: tp/fsdp shard weights and compose too.  Only ``ep`` is rejected
    #: — ragged group boundaries are contiguous local row ranges and
    #: cannot align with a sharded expert stack.  "einsum": the
    #: capacity-bounded GShard dispatch/combine formulation — fully
    #: static, the layout GSPMD shards over the ``ep`` mesh axis, and
    #: unusable at scale on one chip (at N = 16,384 tokens, 64 experts,
    #: top-8 its (N, E, C) one-hots are 2.7e9 elements each).  "auto"
    #: (default) reads the mesh: einsum where an ``ep`` axis of size > 1
    #: shards the experts, ragged everywhere else.
    #: What the chip said (my chip run, PR 26; TPU v5 lite, one chip,
    #: OLMoE-1B-7B's widths at 2 of 16 layers, 16,384 tokens a step =
    #: 131,072 routed rows, 2,048 a group on average and 2.7x that in
    #: the fullest, bf16 + adamw, selective remat): the ragged path
    #: trains at 39.9 k tokens/s, 30.9% MFU, with a peak of 10.9 of
    #: 15.75 GiB — the N·topk row duplicate fits.  Its grouped matmuls
    #: take 29% of the device time and run at 56% of the matmul peak;
    #: the 131k-key sorts are 0.4%.
    moe_impl: str = "auto"

    def __post_init__(self) -> None:
        _remat.resolve(self.remat)  # fail on junk at config build time

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    def capacity(self, n_tokens: int) -> int:
        per_expert = self.topk * n_tokens / self.n_experts
        return max(1, math.ceil(per_expert * self.capacity_factor))

    @staticmethod
    def tiny() -> "MoeConfig":
        return MoeConfig()

    @staticmethod
    def mixtral_8x7b() -> "MoeConfig":
        """Mixtral-8x7B dimensions — the pod-scale EP design point."""
        return MoeConfig(
            vocab=32000, d_model=4096, n_layers=32, n_heads=32,
            n_kv_heads=8, d_ff=14336, n_experts=8, topk=2, max_seq=8192,
        )

    @staticmethod
    def olmoe_1b_7b() -> "MoeConfig":
        """OLMoE-1B-7B (``allenai/OLMoE-1B-7B-0125-Instruct``,
        arXiv:2409.02060) at full depth: 64 experts of width 1024, 8 per
        token, raw (not renormalised) gates, full multi-head attention
        with QK-norm, all-slot load-balance loss x 0.01 + z-loss x
        0.001, bf16 storage.  The benchmark's configuration file builds
        the same config (a test holds the two together)."""
        return MoeConfig(
            vocab=50304, d_model=2048, n_layers=16, n_heads=16,
            n_kv_heads=16, d_ff=1024, n_experts=64, topk=8, max_seq=4096,
            rope_theta=10000.0, norm_eps=1e-5, param_dtype=jnp.bfloat16,
            qk_norm=True, norm_topk_prob=False, router_aux_weight=0.01,
            router_aux_all_slots=True, router_z_weight=0.001,
        )


def _layer_rows(cfg: MoeConfig, kind: Any = None) -> List[_decoder.Row]:
    """The parameter table of a layer: llama's attention rows, a router,
    and the expert stacks — their leading E axis sharded over ``ep``,
    within an expert the dense Megatron layout (fsdp × tp)."""
    d, E = cfg.d_model, cfg.n_experts
    return [
        *_llama.attn_rows(cfg),
        _decoder.ones("mlp_norm", d),
        _decoder.Row("w_router", (d, E), P(None, None)),
        *_decoder.swiglu_rows(d, cfg.d_ff, lead=(E,), lead_spec=("ep",)),
        *_llama.qk_norm_rows(cfg),
    ]


#: ``init_params(cfg, key)`` (8 dense draws a layer + embed + lm_head),
#: ``param_specs(cfg)`` and ``param_shapes(cfg)`` — a Mixtral-scale layout
#: prices without materialising the expert stacks — of one table
#: (:class:`ddl_tpu.models.decoder.Table`); one kind of layer.
_TABLE = _decoder.Table(lambda cfg: (None,) * cfg.n_layers, _layer_rows, (2, 8))
init_params, param_specs, param_shapes = (
    _TABLE.init_params, _TABLE.param_specs, _TABLE.param_shapes
)


def _router_topk(
    x: jax.Array, layer: Params, cfg: MoeConfig
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Shared router: float32 softmax gate → top-k → gate weights
    (renormalised over the chosen experts iff ``cfg.norm_topk_prob``).

    ONE implementation for both dispatch impls, so their 'identical
    routing' equivalence holds by construction.  The logits leave the
    matmul in float32 (bf16 operands, float32 accumulation and result):
    rounding them to bf16 first, as HF's module does, makes exact ties
    between the last expert kept and the first one dropped common, and
    a tie is broken by index, not by the router.  Returns
    (probs (N, E) fp32, top_p (N, k), top_e (N, k) ids, z (N,) fp32
    ``logsumexp(logits)``).
    """
    logits = jnp.dot(
        x, layer["w_router"].astype(x.dtype),
        preferred_element_type=jnp.float32,
    )
    z = jax.nn.logsumexp(logits, axis=-1)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, cfg.topk)
    if cfg.norm_topk_prob:
        top_p = top_p / jnp.maximum(jnp.sum(top_p, -1, keepdims=True), 1e-9)
    return probs, top_p, top_e, z


def _router_losses(
    probs: jax.Array, top_e: jax.Array, z: jax.Array, cfg: MoeConfig
) -> jax.Array:
    """One layer's router losses, (2,) float32: the load-balance term
    ``E · Σ_e fraction_dispatched(e) · mean_router_prob(e)`` — on the
    slot-0 decisions (Switch), or over every slot's
    (``cfg.router_aux_all_slots``) — and the z-loss ``mean(z²)``."""
    E = cfg.n_experts
    picks = top_e.reshape(-1) if cfg.router_aux_all_slots else top_e[:, 0]
    frac_dispatched = (
        jnp.sum(jax.nn.one_hot(picks, E, dtype=jnp.float32), axis=0)
        / probs.shape[0]
    )
    balance = E * jnp.sum(frac_dispatched * jnp.mean(probs, axis=0))
    return jnp.stack([balance, jnp.mean(z * z)])


def moe_mlp(
    x: jax.Array, layer: Params, cfg: MoeConfig
) -> Tuple[jax.Array, jax.Array]:
    """Top-k routed SwiGLU experts over flat tokens x: (N, D), by the
    capacity-bounded einsum dispatch.

    Returns (out (N, D), aux load-balance loss scalar).
    """
    out, losses, _ = _einsum_mlp(x, layer, cfg)
    return out, losses[0]


def _einsum_mlp(
    x: jax.Array, layer: Params, cfg: MoeConfig
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """:func:`moe_mlp` with both router losses and the router's picks:
    (out, (2,) losses, top_e (N, k))."""
    N, D = x.shape
    E, k, C = cfg.n_experts, cfg.topk, cfg.capacity(N)
    dt = x.dtype

    probs, top_p, top_e, z = _router_topk(x, layer, cfg)

    mask = jax.nn.one_hot(top_e, E, dtype=jnp.float32)  # (N, k, E)
    # Slot-major priority: all slot-0 picks queue before any slot-1 pick.
    mask_f = mask.transpose(1, 0, 2).reshape(k * N, E)
    pos_f = jnp.cumsum(mask_f, axis=0) - mask_f  # arrival index per expert
    pos = (pos_f * mask_f).sum(-1).reshape(k, N).T.astype(jnp.int32)  # (N, k)
    keep = (pos < C) & (mask.sum(-1) > 0)  # (N, k) boolean

    gates = top_p * keep  # dropped tokens get zero combine weight
    # combine[n, e, c] = gate weight of token n at expert e slot c
    combine = jnp.einsum(
        "nk,nke,nkc->nec",
        gates,
        mask,
        jax.nn.one_hot(pos, C, dtype=jnp.float32),
    )
    dispatch = (combine > 0).astype(dt)  # (N, E, C)

    expert_in = jnp.einsum("nec,nd->ecd", dispatch, x)  # (E, C, D)
    gate = jax.nn.silu(
        jnp.einsum("ecd,edf->ecf", expert_in, layer["w_gate"].astype(dt))
    )
    up = jnp.einsum("ecd,edf->ecf", expert_in, layer["w_up"].astype(dt))
    expert_out = jnp.einsum(
        "ecf,efd->ecd", gate * up, layer["w_down"].astype(dt)
    )
    out = jnp.einsum("nec,ecd->nd", combine.astype(dt), expert_out)
    return out, _router_losses(probs, top_e, z, cfg), top_e


def _ep_sharded(mesh: Optional[Any]) -> bool:
    return (
        mesh is not None
        and "ep" in getattr(mesh, "axis_names", ())
        and mesh.shape["ep"] > 1
    )


def _resolve_impl(cfg: MoeConfig, mesh: Optional[Any]) -> MoeConfig:
    """``cfg`` with ``moe_impl="auto"`` decided from what the mesh
    shows: the einsum dispatch where an ``ep`` axis shards the expert
    stacks (GSPMD derives the all-to-alls from its layout), dropless
    ragged everywhere else.  A forced ``"ragged"`` on an ``ep`` mesh is
    rejected up front instead of letting GSPMD materialise a gathered
    stack silently: its expert groups are contiguous row ranges of a
    locally sorted copy list and cannot align with a sharded stack.
    Token-sharded axes (``dp``/``sp``) ARE supported:
    :func:`_routed_mlp` shard_maps the routing per shard.  tp/fsdp
    shard weights, not tokens — those compose fine."""
    if cfg.moe_impl not in ("auto", "einsum", "ragged"):
        raise ValueError(
            f"unknown moe_impl {cfg.moe_impl!r} (want auto|einsum|ragged)"
        )
    if cfg.moe_impl == "auto":
        impl = "einsum" if _ep_sharded(mesh) else "ragged"
        return dataclasses.replace(cfg, moe_impl=impl)
    if cfg.moe_impl == "ragged" and _ep_sharded(mesh):
        raise ValueError(
            "moe_impl='ragged' does not compose with an ep>1 mesh axis "
            "(expert groups are contiguous local row ranges); use the "
            "einsum impl for expert parallelism"
        )
    return cfg


def moe_mlp_ragged(
    x: jax.Array, layer: Params, cfg: MoeConfig
) -> Tuple[jax.Array, jax.Array]:
    """Sort-based dropless top-k routing over ``jax.lax.ragged_dot``.

    Each token contributes ``topk`` copies; copies are stably sorted by
    expert id, so each expert's rows form one contiguous group and the
    three expert matmuls run as ragged group-wise dots against the
    stacked ``(E, D, F)`` weights — no capacity, no drops, no N·E·C
    one-hot einsums.  The router, top-k gates and router losses are
    identical to :func:`moe_mlp`; outputs match it exactly whenever
    capacity does not bind there (routing is per-token).

    Returns (out (N, D), aux load-balance loss scalar).
    """
    out, losses, _ = _ragged_mlp(x, layer, cfg)
    return out, losses[0]


def _ragged_mlp(
    x: jax.Array, layer: Params, cfg: MoeConfig
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """:func:`moe_mlp_ragged` with both router losses and the router's
    picks: (out, (2,) losses, top_e (N, k)) — this family's softmax
    router in front of :func:`ragged_experts`."""
    with scope("ddl.moe_route"):
        probs, top_p, top_e, z = _router_topk(x, layer, cfg)
    out = ragged_experts(x, layer, top_p, top_e)
    return out, _router_losses(probs, top_e, z, cfg), top_e


# -- the routed core's two row moves --------------------------------------------
#
# ``ragged_experts`` moves rows twice: N·k copies out of N rows into expert
# order, and the experts' N·k rows back into copy order.  Autodiff transposes
# a ``take`` into a scatter-add that must allow for colliding indices, which
# a v5e runs at 0.4-0.55 of the rate of the gather that is its equal
# (``tools/probe_ragged_rows.py``, PERF.md section 6, PR 35; telling the
# scatter ``unique_indices`` changes nothing).  The code knows what XLA
# cannot: ``order`` is a permutation of the copies and ``inv`` its inverse,
# and the forward pass holds both.  So each move carries a backward rule
# that is a gather.  The rules are reverse-mode only (``custom_vjp`` refuses
# ``jvp`` / ``jacfwd`` / ``linearize``; nothing in ``ddl_tpu`` differentiates
# the routed layer forward).


def _rows_at(rows: jax.Array, idx: jax.Array) -> jax.Array:
    """``rows[idx]`` for in-range ``idx`` as one ``lax.gather`` traced in
    place: the row move of the backward rules.  Not ``jnp.take``, which is
    a jitted function: traced twice at one signature it lowers to one
    shared private function, and XLA inlines that without the caller's
    name stack — the backward gathers would reach the device trace under
    no ``ddl.`` scope."""
    return jax.lax.gather(
        rows, idx[:, None],
        jax.lax.GatherDimensionNumbers(
            offset_dims=(1,), collapsed_slice_dims=(0,), start_index_map=(0,)
        ),
        slice_sizes=(1, rows.shape[1]),
        mode=jax.lax.GatherScatterMode.PROMISE_IN_BOUNDS,
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _take_copies(x: jax.Array, order: jax.Array, inv: jax.Array, k: int):
    """``x[order // k]``: the N·k copies (copy ``c`` is row ``c // k``) of
    the N rows of ``x`` in the order the permutation ``order`` gives, ``inv``
    its inverse.  ``repeat(x, k)`` is never materialised.  The cotangent
    ``d_x[order[i] // k] += g[i]`` is ``g[inv]`` — the copies back in copy
    order — summed over each row's k: one gather and a k-way sum,
    accumulated in float32 and rounded once (the scatter-add rounds to
    ``x.dtype`` after each of the k additions)."""
    return _take_copies_fwd(x, order, inv, k)[0]


def _take_copies_fwd(x, order, inv, k):
    # Plain ops, not the rule's own primal: a ``custom_vjp`` called inside
    # a forward rule is traced once a signature, so every layer's
    # ``jnp.take`` would lower to one shared function and lose its scope
    # (see ``_rows_at``).
    return jnp.take(x, order // k, axis=0), inv


def _take_copies_bwd(k, inv, g):
    per_copy = _rows_at(g, inv).reshape(-1, k, g.shape[-1])
    d_x = jnp.sum(per_copy, axis=1, dtype=jnp.float32).astype(g.dtype)
    return d_x, None, None


_take_copies.defvjp(_take_copies_fwd, _take_copies_bwd)


@jax.custom_vjp
def _combine_copies(rows: jax.Array, top_w: jax.Array, order: jax.Array,
                    inv: jax.Array, is_held: Optional[jax.Array]):
    """``out[n] = sum_j top_w[n, j] * rows[inv[n·k + j]]``: the experts'
    N·k rows back in copy order (the un-permute) and the gate-weighted sum
    over each token's k.  ``is_held`` is ``None`` or the (N·k,) mask, in
    copy order, of the choices whose expert is held; ``order`` then sorts
    those first, and the other rows of ``rows`` are undefined: they are
    ``where``-masked out, never multiplied by zero.

    The rule encloses the sum so that the rows' cotangent is gathered from
    the (N, D) cotangent of ``out``: ``d_rows[i] = top_w[order[i]] *
    d_out[order[i] // k]``, the bits autodiff gets by materialising the
    (N, k, D) products and scatter-adding them through ``inv`` — at a
    quarter of its time (``tools/probe_ragged_rows.py``).
    ``top_w``'s cotangent is autodiff's own: it reads the un-permuted
    rows, which the forward pass made."""
    return _combine_copies_fwd(rows, top_w, order, inv, is_held)[0]


def _weighted_sum(top_w, per_slot):
    return jnp.einsum("nk,nkd->nd", top_w.astype(per_slot.dtype), per_slot)


def _combine_copies_fwd(rows, top_w, order, inv, is_held):
    N, k = top_w.shape
    per_slot = jnp.take(rows, inv, axis=0).reshape(N, k, rows.shape[1])
    if is_held is not None:
        per_slot = jnp.where(is_held.reshape(N, k, 1), per_slot, 0)
    return _weighted_sum(top_w, per_slot), (per_slot, top_w, order, is_held)


def _combine_copies_bwd(res, d_out):
    per_slot, top_w, order, is_held = res
    N, k = top_w.shape
    (d_w,) = jax.vjp(lambda w: _weighted_sum(w, per_slot), top_w)[1](d_out)
    w_sorted = _rows_at(top_w.reshape(N * k, 1), order).astype(d_out.dtype)
    d_rows = w_sorted * _rows_at(d_out, order // k)
    if is_held is not None:  # the held copies' rows: the first of the sort
        held_rows = jnp.arange(N * k) < jnp.sum(is_held)
        d_rows = jnp.where(held_rows[:, None], d_rows, 0)
    return d_rows, d_w, None, None, None


_combine_copies.defvjp(_combine_copies_fwd, _combine_copies_bwd)


# -- a share's rows: a static bound, and full width past it ------------------------

#: Rows the bound is rounded up to: what XLA's grouped-matmul kernels want
#: of their row count on a v5e (``tools/probe_ragged_rows.py``, my chip run,
#: PR 40: the same groups over 32,768 rows 0.80 ms, over 8 more 12.4, 128
#: more 1.21, 256 more 0.91, 512 more 0.82).
ROW_TILE = 512


def held_row_bound(n_rows: int, count: int, n_experts: int) -> int:
    """How many of the ``n_rows`` sorted (token, slot) rows a share of
    ``count`` of the router's ``n_experts`` experts runs its row passes
    over: twice what a balanced router sends it, in whole row tiles — and
    all of them for half the experts or more.  A rule of what the code can
    see, not a knob: past it the layer runs at full width
    (:func:`_held_rows`), so it bounds time, never the result."""
    twice_the_share = -(-2 * n_rows * count // n_experts)
    return min(n_rows, -(-twice_the_share // ROW_TILE) * ROW_TILE)


@contextlib.contextmanager
def _phase(name: str, overflow: bool):
    """The routed core's phase ``name``; the full-width fallback's ops
    stand under ``ddl.moe_overflow`` inside it, the innermost scope, so
    that the device trace says which branch ran."""
    with scope(name):
        if overflow:
            with scope("ddl.moe_overflow"):
                yield
        else:
            yield


def _sorted_rows(x, experts, top_w, order, group_sizes, is_held,
                 overflow: bool = False) -> jax.Array:
    """The expert pass over all N·k sorted rows and the combine behind
    it: :func:`ragged_experts` after its sort."""
    N, k = top_w.shape
    with _phase("ddl.moe_combine", overflow):  # the un-permute's index, read by both rules
        inv = jnp.argsort(order)  # flat copy index -> its sorted row

    with _phase("ddl.moe_experts", overflow):
        xs = _take_copies(x, order, inv, k)  # (N*k, D) grouped by expert
        if is_held is not None:
            in_a_group = (jnp.arange(N * k) < jnp.sum(group_sizes))[:, None]
            xs = jnp.where(in_a_group, xs, 0)
        rows = _swiglu_rows(xs, experts, group_sizes)  # still expert-sorted

    with _phase("ddl.moe_combine", overflow):
        return _combine_copies(rows, top_w, order, inv, is_held)


def _swiglu_rows(xs: jax.Array, experts: Params, group_sizes: jax.Array):
    """The experts' SwiGLU on rows grouped by expert: three grouped
    matmuls.  The rows of the result past the last group are UNWRITTEN."""
    dt = xs.dtype
    gate = jax.nn.silu(
        jax.lax.ragged_dot(xs, experts["w_gate"].astype(dt), group_sizes)
    )
    up = jax.lax.ragged_dot(xs, experts["w_up"].astype(dt), group_sizes)
    return jax.lax.ragged_dot(gate * up, experts["w_down"].astype(dt), group_sizes)


# The bounded pass.  Its two row moves are not the full-width ones over
# fewer rows: the N·k slots gathered out of a (B, D) source cost what the
# full permutation costs (``tools/probe_ragged_rows.py``, PR 40: 4.8 ms
# against 4.4 at 131,072 slots — a gather pays by the row it writes), so
# the combine and the copies' cotangent would keep their time.  Both are
# one operation, "sum the B rows by their token", and B rows can be summed
# without a scatter and without the slots: sorted by token (B keys), each
# block of 128 tokens owns a contiguous run of rows, and the run's sums are
# a (128, run) x (run, D) product with a one-hot — XLA's grouped matmul
# with the runs as its groups, the kernel that computes an expert stack's
# weight gradient.

TOKEN_LANES = 128  # tokens a group of the summing matmul: one-hot's width

_SUM_BY_GROUP = jax.lax.RaggedDotDimensionNumbers(
    dot_dimension_numbers=(((0,), (0,)), ((), ())),
    lhs_ragged_dimensions=[0], rhs_group_dimensions=[],
)


def _by_token(tokens: jax.Array, live: jax.Array, n_tokens: int):
    """How to sum B rows by token: (perm, lane, block_sizes) — the stable
    order that sorts the rows by ``tokens`` (B,) with the rows that are
    not ``live`` last, each sorted row's lane in its block of
    ``TOKEN_LANES`` tokens, and the rows a block (the live ones only)."""
    n_blocks = -(-n_tokens // TOKEN_LANES)
    key = jnp.where(live, tokens, n_blocks * TOKEN_LANES)
    perm = jnp.argsort(key)
    key = key[perm]
    # Sorted keys: a block's rows lie between two binary searches (a
    # ``bincount`` is a scatter-add of B ones, 1.1 ms at 131,072 on a v5e).
    edges = jnp.searchsorted(key, jnp.arange(n_blocks + 1) * TOKEN_LANES)
    return perm, key % TOKEN_LANES, jnp.diff(edges).astype(jnp.int32)


def _sum_by_token(rows: jax.Array, by_token, weights: Optional[jax.Array],
                  n_tokens: int) -> jax.Array:
    """``out[n] = sum of weights[i] * rows[i]`` over the live rows ``i`` of
    token ``n`` — (n_tokens, D) out of (B, D), accumulated in float32 and
    rounded once.  ``rows`` is zero (``where``-masked by the caller) in the
    rows that are not live: the kernel's last tile may read them."""
    perm, lane, sizes = by_token
    dt = rows.dtype
    hot = lane[:, None] == jnp.arange(TOKEN_LANES)
    one = jnp.ones((), dt) if weights is None else weights[perm].astype(dt)[:, None]
    sums = jax.lax.ragged_dot_general(
        jnp.where(hot, one, 0), _rows_at(rows, perm), sizes, _SUM_BY_GROUP,
        precision=jax.lax.Precision.HIGHEST if dt == jnp.float32 else None,
        preferred_element_type=jnp.float32,
    )  # (blocks, TOKEN_LANES, D)
    return sums.reshape(-1, rows.shape[1])[:n_tokens].astype(dt)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _head_copies(x: jax.Array, tokens: jax.Array, live: jax.Array, by_token,
                 n_tokens: int):
    """``x[tokens]`` in the live rows and zero in the others: the first B
    sorted copies of the ``n_tokens`` rows of ``x``.  The cotangent sums
    the B rows by token (the others ``where``-masked first: a grouped
    matmul's transpose leaves them unwritten)."""
    return _head_copies_fwd(x, tokens, live, by_token, n_tokens)[0]


def _head_copies_fwd(x, tokens, live, by_token, n_tokens):
    out = jnp.where(live[:, None], jnp.take(x, tokens, axis=0), 0)
    return out, (live, by_token)


def _head_copies_bwd(n_tokens, res, g):
    live, by_token = res
    g = jnp.where(live[:, None], g, 0)
    return _sum_by_token(g, by_token, None, n_tokens), None, None, None


_head_copies.defvjp(_head_copies_fwd, _head_copies_bwd)


@jax.custom_vjp
def _head_combine(rows, top_w, head, live, by_token):
    """``out[n] = sum_j top_w[n, j] * rows[inv[n·k + j]]`` over the held
    slots, from the first B sorted rows alone (``head = order[:B]``, the
    held rows the ``live`` ones): the rows weighted and summed by token.
    Neither ``inv`` nor the (N, k, D) slots exist.  Cotangents: the rows'
    is gathered from the (N, D) cotangent as :func:`_combine_copies`' is;
    ``top_w``'s is each live row's product with its token's cotangent, set
    at the row's copy (B scalars; the other slots' is zero)."""
    return _head_combine_fwd(rows, top_w, head, live, by_token)[0]


def _head_combine_fwd(rows, top_w, head, live, by_token):
    N, k = top_w.shape
    rows = jnp.where(live[:, None], rows, 0)
    w_sorted = _rows_at(top_w.reshape(N * k, 1), head)[:, 0]
    out = _sum_by_token(rows, by_token, w_sorted, N)
    return out, (rows, top_w, w_sorted, head, live)


def _head_combine_bwd(res, d_out):
    rows, top_w, w_sorted, head, live = res
    N, k = top_w.shape
    at = _rows_at(d_out, head // k)  # each row's token's cotangent
    d_rows = jnp.where(live[:, None], w_sorted.astype(at.dtype)[:, None] * at, 0)
    d_w_sorted = jnp.sum(rows.astype(jnp.float32) * at.astype(jnp.float32), axis=1)
    d_w = jnp.zeros((N * k,), top_w.dtype).at[head].set(
        jnp.where(live, d_w_sorted, 0).astype(top_w.dtype), unique_indices=True)
    return d_rows, d_w.reshape(N, k), None, None, None


_head_combine.defvjp(_head_combine_fwd, _head_combine_bwd)


def _head_rows(x, experts, top_w, order, group_sizes, n_rows: int) -> jax.Array:
    """:func:`_sorted_rows`' result from the first ``n_rows`` sorted rows —
    the held rows in expert order, and slack — where ``sum(group_sizes)``
    does not pass ``n_rows``: the copies, the masks, the three grouped
    matmuls (the same groups, the same tiles visited), ``silu * up`` and
    both row moves with their cotangents are (n_rows, ·)."""
    N, k = top_w.shape
    with scope("ddl.moe_route"):
        head = order[:n_rows]
        tokens = head // k
        live = jnp.arange(n_rows) < jnp.sum(group_sizes)
        by_token = _by_token(tokens, live, N)
    with scope("ddl.moe_experts"):
        xs = _head_copies(x, tokens, live, by_token, N)
        rows = _swiglu_rows(xs, experts, group_sizes)
    with scope("ddl.moe_combine"):
        return _head_combine(rows, top_w, head, live, by_token)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _held_rows(n_rows: int, x, experts, top_w, order, group_sizes, is_held):
    """The expert pass and the combine over the first ``n_rows`` sorted
    rows where the held rows fit in them (:func:`_head_rows`), and over all
    N·k where they do not (:func:`_sorted_rows`, the full-width code): one
    ``lax.cond`` a pass, so dropless whatever the router does, and only
    the taken branch runs on the device.

    A ``custom_vjp`` because of what autodiff does to a ``cond``: every
    residual of BOTH branches becomes an output of the forward ``cond``,
    zero-filled by the branch not taken — N·k-row memsets and buffers
    that cost what the bound saves.  So no residual crosses a ``cond``
    here: the rule's residuals are its inputs, and the backward rule
    branches again and differentiates the taken branch inside its branch.
    That runs the copies and two grouped matmuls of the taken branch a
    second time — what the layers' remat policies do anyway; under
    ``selective`` the forward rule's ``cond`` is dead code in the
    recomputation: nothing of the layer reads the result, and what follows
    the layer (AFMoE's post-MLP norm) finds it saved (the forward rule tags
    it as the attention kernels tag theirs)."""
    return _held_rows_fwd(n_rows, x, experts, top_w, order, group_sizes,
                          is_held)[0]


@functools.lru_cache(maxsize=None)
def _held_rows_passes(n_rows: int):
    """(forward branches, backward branches) of :func:`_held_rows` at a
    bound: each pair (the pass over ``n_rows`` rows, the pass over all) as
    functions of the ``cond``'s operands alone.  The SAME function objects
    at every call: ``lax.cond`` keeps a branch's traced form by the
    function, so the routed layers of a model trace each branch once and
    not once a layer and pass — the branches double a routed layer's ops,
    and a train step's set-up is mostly tracing them."""

    def bounded(x, experts, top_w, order, group_sizes, is_held):
        return _head_rows(x, experts, top_w, order, group_sizes, n_rows)

    def full(x, experts, top_w, order, group_sizes, is_held):
        return _sorted_rows(
            x, experts, top_w, order, group_sizes, is_held, overflow=True)

    def whole(branch, overflow):
        # XLA moves what both branches end in out of a conditional, and
        # both end in a sum over a token's rows: the full-width branch's
        # (N, k, D) slots would be written out of it and read again behind
        # it.  The barrier keeps each branch's last ops in the branch.
        def run(*operands):
            out = branch(*operands)
            with _phase("ddl.moe_combine", overflow):
                return jax.lax.optimization_barrier(out)

        return run

    def pull(branch):
        def run(x, experts, top_w, order, group_sizes, is_held, d_out):
            return jax.vjp(
                lambda *primals: branch(*primals, order, group_sizes, is_held),
                x, experts, top_w)[1](d_out)

        return run

    return (whole(bounded, False), whole(full, True)), (pull(bounded), pull(full))


def _held_rows_fit(n_rows, operands):
    return jnp.sum(operands[4]) <= n_rows  # group_sizes: the held rows, counted


def _held_rows_fwd(n_rows, *operands):
    bounded, full = _held_rows_passes(n_rows)[0]
    out = jax.lax.cond(_held_rows_fit(n_rows, operands), bounded, full, *operands)
    # ``selective`` keeps the result where something behind the layer reads
    # it in the backward pass (AFMoE's post-MLP norm: 2·N·D bytes a layer),
    # as it keeps a kernel's: the recomputation then holds no ``cond`` at
    # all - a third of the routed layer's program, and its forward's time.
    return _remat.tag_attn_out(out), operands


def _held_rows_bwd(n_rows, operands, d_out):
    bounded, full = _held_rows_passes(n_rows)[1]
    d_x, d_experts, d_top_w = jax.lax.cond(
        _held_rows_fit(n_rows, operands), bounded, full, *operands, d_out)
    return d_x, d_experts, d_top_w, None, None, None


_held_rows.defvjp(_held_rows_fwd, _held_rows_bwd)


def ragged_experts(
    x: jax.Array,
    experts: Params,
    top_w: jax.Array,
    top_e: jax.Array,
    held: Optional[Tuple[int, ...]] = None,
) -> jax.Array:
    """The dropless routed-expert core: ``sum_k top_w[n, k] *
    expert_{top_e[n, k]}(x[n])`` over flat tokens ``x`` (N, D), for the
    experts held here.

    ``experts`` holds the stacked SwiGLU weights ``w_gate``/``w_up`` (G,
    D, F) and ``w_down`` (G, F, D); ``top_w``/``top_e`` (N, k) are the
    router's weights and expert ids, whatever scoring produced them.
    ``held=None``: the stack is every expert the router can name.
    ``held=(first, count)`` or ``(first, count, n_experts)``: the stack is
    experts ``first .. first + count - 1`` of a wider router (G = count),
    ``n_experts`` wide where stated — the part of the layer's result that
    those experts give is returned, the other choices add nothing (no
    capacity, no dropped held row, no stand-in for the chips that hold the
    rest).

    Each choice is a row; rows are stably sorted by expert so that each
    expert's rows are one contiguous group and the three matmuls run as
    ``jax.lax.ragged_dot``.  With a held range the unheld choices sort
    behind the last group and belong to none.  Shapes are static, and how
    many rows the passes run over is a rule of the static facts
    (:func:`held_row_bound`):

    - ``held=None``, a range of half the router's experts or more, or a
      range whose router's width is not stated: all N·k rows, whatever the
      router chose.  XLA's grouped-matmul kernels visit the grouped rows'
      tiles only (0.57 ms for an eighth of 131,072 rows against 4.10 ms
      for all; ``tools/probe_ragged_rows.py``, my chip run, PR 30, TPU v5
      lite; PERF.md section 6); the gather, the masks and the elementwise
      passes around them run over every row.
    - a narrower range: the first B sorted rows — the held rows in expert
      order and slack — with B twice the balanced share in whole row
      tiles (:func:`_head_rows`).  The copies, the masks, the three
      grouped matmuls (same groups, same tiles visited), ``silu * up`` and
      the rows' cotangent are (B, ·); the combine and the copies'
      cotangent sum those B rows by token in a fourth grouped matmul
      (:func:`_sum_by_token`), so no pass writes N·k rows or slots.
      Where the router sends the range more than B choices the layer
      runs at full width instead (:func:`_held_rows`: one ``lax.cond`` a
      pass, nothing dropped, the fallback's ops under
      ``ddl.moe_overflow``).

    Either way the kernels leave the rows past the last group of their
    result UNWRITTEN — stale values, NaN after a NaN fill, in the
    transposes too — so both ends of the expert pass are ``where``-masked
    (never multiplied by zero), in the forward and, by transposition, in
    the backward pass.

    The two row moves — the copies into expert order
    (:func:`_take_copies`) and the un-permute with the weighted sum
    behind it (:func:`_combine_copies`) — carry backward rules that
    gather by the permutation the forward pass already holds, where
    autodiff would scatter-add; both ends of the expert pass stay
    ``where``-masked in them.

    Its three phases carry profiler scopes (``ddl.moe_route``,
    ``ddl.moe_experts``, ``ddl.moe_combine``): they reach the device
    trace as each op's ``tf_op`` name."""
    N, D = x.shape
    k = top_e.shape[1]
    n_groups = experts["w_gate"].shape[0]
    is_held = None
    n_rows = N * k

    with scope("ddl.moe_route"):
        flat_e = top_e.reshape(-1)  # (N*k,) expert of copy i (token i//k)
        if held is not None:
            first, count, *width = held
            assert count == n_groups, (held, n_groups)
            if width:
                n_rows = held_row_bound(N * k, count, *width)
            is_held = (flat_e >= first) & (flat_e < first + count)
            # Unheld choices get the id past the last group: they sort
            # behind every group and are counted in none.
            flat_e = jnp.where(is_held, flat_e - first, count)
        order = jnp.argsort(flat_e)  # stable: ties keep token order
        group_sizes = jnp.bincount(
            flat_e, length=n_groups + (held is not None)
        ).astype(jnp.int32)[:n_groups]

    rest = (x, experts, top_w, order, group_sizes, is_held)
    if n_rows == N * k:
        return _sorted_rows(*rest)
    return _held_rows(n_rows, *rest)


# -- sigmoid-routed experts, with or without a shared expert (AFMoE, DeepSeek-V3, LFM2) --
#
# One routine for every family that scores by sigmoid, selects under a bias,
# normalises, scales, and adds an ungated shared expert where it has one.
# ``cfg`` is the family's own config; read here: ``topk``, ``route_norm``,
# ``route_eps`` (what the normalisation adds to the picked scores' sum),
# ``route_scale``, ``n_experts`` (the router's width), ``held`` ((first,
# count) of them) and ``n_shared_experts`` (0: no ``shared`` rows, no
# ``ddl.moe_shared`` op).


def sigmoid_expert_rows(cfg: Any) -> List[_decoder.Row]:
    """The parameters :func:`sigmoid_expert_mlp` reads of a layer: the
    router, its selection bias (zeros, float32 whatever the storage dtype:
    it is compared with float32 scores), the shared experts as one SwiGLU
    (none where the family has none) and the held experts' stacks — their
    leading axis is this chip's own and is not sharded."""
    d, shared = cfg.d_model, cfg.d_expert * cfg.n_shared_experts
    return [
        _decoder.Row("w_router", (d, cfg.n_experts), P(None, None)),
        _decoder.Row("expert_bias", (cfg.n_experts,), P(None), fill=0.0,
                     dtype=jnp.float32),
        *(_decoder.swiglu_rows(d, shared, "shared.") if shared else ()),
        *_decoder.swiglu_rows(d, cfg.d_expert, "experts.", lead=(cfg.held[1],),
                              lead_spec=(None,)),
    ]


def sigmoid_route(h: jax.Array, layer: Params, cfg: Any):
    """The router on flat tokens ``h`` (N, D): (weights (N, k) float32,
    expert ids (N, k)).  Scores leave their matmul in float32 (bf16
    operands), as :func:`_router_topk`'s do."""
    logits = jnp.dot(
        h, layer["w_router"].astype(h.dtype),
        preferred_element_type=jnp.float32,
    )
    scores = jax.nn.sigmoid(logits)
    bias = jax.lax.stop_gradient(layer["expert_bias"])  # selection only
    _, top_e = jax.lax.top_k(scores + bias, cfg.topk)
    top_w = jnp.take_along_axis(scores, top_e, axis=-1)
    if cfg.route_norm:
        top_w = top_w / (jnp.sum(top_w, axis=-1, keepdims=True) + cfg.route_eps)
    return top_w * cfg.route_scale, top_e


def sigmoid_expert_tokens(h: jax.Array, layer: Params, cfg: Any):
    """Shared expert (where the family has one) + the held routed experts
    on flat tokens (N, D): (out (N, D), the router's picks (N, k))."""
    with scope("ddl.moe_route"):
        top_w, top_e = sigmoid_route(h, layer, cfg)
    # A share states its router's width: a narrow one bounds its row passes.
    held = None if cfg.held == (0, cfg.n_experts) else (*cfg.held, cfg.n_experts)
    if held is not None:
        # A share cannot train its router: the absent experts add exactly
        # nothing here, so this chip's part of the router's gradient says
        # "send the tokens to them" - and the router obeys (on the chip,
        # PR 30: the held sixteen's 12.5% of the choices is 0.1% after 26
        # adamw steps, PERF.md section 6).  In the deployment the other
        # chips' parts balance it.  So a share routes with its router where
        # it stands, as it selects with expert_bias where it stands.
        top_w = jax.lax.stop_gradient(top_w)
    routed = ragged_experts(h, layer["experts"], top_w, top_e, held=held)
    if not cfg.n_shared_experts:
        return routed, top_e
    with scope("ddl.moe_shared"):
        shared = _decoder.swiglu(layer["shared"], h)
    return shared + routed, top_e


def sigmoid_expert_mlp(h: jax.Array, layer: Params, cfg: Any,
                       mesh: Optional[Any]):
    """:func:`sigmoid_expert_tokens` on the (B, T, D) stream.  On a ``dp``
    mesh each shard routes its own rows under ``shard_map``: routing is per
    token and dropless, so local is global (:func:`_routed_mlp`'s
    argument); the weights cross replicated."""
    B, T, D = h.shape
    names = getattr(mesh, "axis_names", ())
    if not ("dp" in names and mesh.shape["dp"] > 1):
        out, top_e = sigmoid_expert_tokens(h.reshape(B * T, D), layer, cfg)
        return out.reshape(B, T, D), top_e.reshape(B, T, -1)
    from jax import shard_map

    read = {k: layer[k] for k in ("w_router", "expert_bias", "shared", "experts")
            if k in layer}

    def body(hs: jax.Array, lyr: Params):
        b, t, _ = hs.shape
        out, top_e = sigmoid_expert_tokens(hs.reshape(b * t, D), lyr, cfg)
        return out.reshape(b, t, D), top_e.reshape(b, t, -1)

    tokens = P("dp", None, None)
    return shard_map(
        body, mesh=mesh,
        in_specs=(tokens, jax.tree.map(lambda _: P(), read)),
        out_specs=(tokens, tokens), check_vma=False,
    )(h, read)


def _moe_mlp_dispatch(
    x: jax.Array, layer: Params, cfg: MoeConfig
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """(out, (2,) router losses, top_e) by ``cfg.moe_impl`` — resolved
    (:func:`_resolve_impl`) by every entry point before it gets here."""
    if cfg.moe_impl == "ragged":
        return _ragged_mlp(x, layer, cfg)
    if cfg.moe_impl == "einsum":
        return _einsum_mlp(x, layer, cfg)
    raise ValueError(f"unresolved moe_impl {cfg.moe_impl!r}")


def _routed_mlp(
    h: jax.Array, layer: Params, cfg: MoeConfig, mesh: Optional[Any]
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The MoE MLP on the (B, T, D) residual stream, mesh-aware.

    Ragged impl on a token-sharded mesh (``dp``/``sp`` axes): routing is
    per-token and the impl is dropless, so each shard sorts and routes
    its LOCAL tokens under ``shard_map`` — outputs are identical to the
    global computation, with zero collectives in the hot path (the same
    argument ``parallel.ring_attention.sharded_local_attention`` makes
    for batch-sharded attention; left to GSPMD, the global argsort/
    bincount would all-gather every token to every device per layer).
    A ``tp`` axis Megatron-splits the per-expert hidden dimension
    INSIDE the shard_map (gate/up column-sharded, down row-sharded,
    one ``psum`` over tp on the partial outputs) so tp devices divide
    the expert FLOPs rather than replicate them; tp that does not
    divide ``d_ff`` falls back to replicated expert compute.  ``ep``
    stays rejected — :func:`_resolve_impl`.  On an fsdp mesh the
    shard_map boundary gathers a layer's expert stack per step, the
    same traffic fsdp training pays at each use point.  The router
    losses become the shard-mean of the per-shard ones: for the z-loss
    that is the global mean; for the load-balance term the same
    pressure at shard granularity, not numerically equal to the global
    one (it is not linear in token subsets; ``forward_pp`` documents
    the same for microbatch groups).  Returns (out (B, T, D), (2,)
    router losses, the router's picks (B, T, topk)).
    """
    B, T, D = h.shape
    if cfg.moe_impl == "ragged" and mesh is not None:
        names = getattr(mesh, "axis_names", ())
        bax = "dp" if "dp" in names and mesh.shape["dp"] > 1 else None
        sax = "sp" if "sp" in names and mesh.shape["sp"] > 1 else None
        if (bax and B % mesh.shape["dp"] != 0) or (
            sax and T % mesh.shape["sp"] != 0
        ):
            raise ValueError(
                "moe_impl='ragged': dp/sp mesh axes must divide the "
                f"(B={B}, T={T}) token grid"
            )
        tax = (
            "tp"
            if "tp" in names
            and mesh.shape["tp"] > 1
            and cfg.d_ff % mesh.shape["tp"] == 0
            else None
        )
        if bax or sax or tax:
            from jax import shard_map

            token_axes = tuple(a for a in (bax, sax) if a)
            ff_specs = {
                "w_gate": P(None, None, tax),
                "w_up": P(None, None, tax),
                "w_down": P(None, tax, None),
            }
            # Only the entries the routed MLP reads cross the shard_map
            # boundary: passing the whole layer dict gathered the UNUSED
            # attention weights (wq/wk/wv/wo — replicated in_specs) to
            # every device per layer (advisor r5).  The router + expert
            # FFN stacks are the entire read set of moe_mlp_ragged.
            mlp_layer = {
                k: layer[k]
                for k in ("w_router", "w_gate", "w_up", "w_down")
            }
            layer_specs = {
                k: ff_specs.get(k, P()) for k in mlp_layer
            }

            def body(hs: jax.Array, lyr: Params):
                b, t, _ = hs.shape
                out, aux, top_e = _ragged_mlp(
                    hs.reshape(b * t, -1), lyr, cfg
                )
                if tax:
                    # Each tp shard computed its d_ff slice; the down
                    # projections are partial sums over the hidden dim.
                    out = jax.lax.psum(out, tax)
                if token_axes:
                    aux = jax.lax.pmean(aux, token_axes)
                return out.reshape(b, t, -1), aux, top_e.reshape(b, t, -1)

            return shard_map(
                body, mesh=mesh,
                in_specs=(P(bax, sax, None), layer_specs),
                out_specs=(P(bax, sax, None), P(), P(bax, sax, None)),
                check_vma=False,
            )(h, mlp_layer)
    out, aux, top_e = _moe_mlp_dispatch(h.reshape(B * T, -1), layer, cfg)
    return out.reshape(B, T, -1), aux, top_e.reshape(B, T, -1)


def _layer_apply(
    layer: Params,
    x: jax.Array,
    cfg: MoeConfig,
    positions: jax.Array,
    mesh: Optional[Any] = None,
    segment_ids: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One MoE block on the residual stream → (x, the router's picks
    (B, T, topk), (2,) router losses) — the single layer body shared by
    :func:`forward` and the pipelined :func:`forward_pp`.  The attention
    sub-block is llama's ``attn_block`` (one implementation across
    families); only the MLP differs — routed experts instead of
    SwiGLU."""
    x = _llama.attn_block(
        layer, x, cfg, positions, mesh=mesh, segment_ids=segment_ids
    )
    with scope("ddl.moe"):
        h = _decoder.rms_norm(x, layer["mlp_norm"], cfg.norm_eps)
        moe_out, aux, top_e = _routed_mlp(h, layer, cfg, mesh)
        return x + moe_out, top_e, aux


def forward(
    params: Params,
    tokens: jax.Array,
    cfg: MoeConfig,
    mesh: Optional[Any] = None,
    segment_ids: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """(logits (B, T, vocab), mean router load-balance loss).

    ``segment_ids`` (B, T): packed-batch attention masking, as in
    ``models.llama.forward``."""
    logits, losses, _ = _forward(params, tokens, cfg, mesh, segment_ids)
    return logits, losses[0]


def forward_with_choices(
    params: Params,
    tokens: jax.Array,
    cfg: MoeConfig,
    mesh: Optional[Any] = None,
) -> Tuple[jax.Array, jax.Array]:
    """(logits, the expert ids every layer's router picked (L, B, T,
    topk)) of ONE forward pass — for holding the model against a
    reference: with a top-k router a rounding flips some tokens' last
    choice, so logits are comparable only where the sets agree."""
    logits, _, picks = _forward(params, tokens, cfg, mesh, None)
    return logits, picks


def _forward(
    params: Params,
    tokens: jax.Array,
    cfg: MoeConfig,
    mesh: Optional[Any],
    segment_ids: Optional[jax.Array],
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """:func:`forward` with both router losses, each a mean over the
    layers, and every layer's picks: (logits, (2,) [load balance, z],
    (L, B, T, topk)).  A caller that drops the picks pays nothing for
    them: they are the ids the dispatch sorts by anyway."""
    cfg = _resolve_impl(cfg, mesh)
    positions = jnp.arange(tokens.shape[1])

    def layer_fn(x: jax.Array, layer: Params):
        return _layer_apply(
            layer, x, cfg, positions, mesh=mesh, segment_ids=segment_ids
        )

    # Under the remat policy "full" recomputes the routing/dispatch/expert
    # internals in the backward pass; "selective" additionally keeps the
    # attention outputs saved.
    logits, picks, losses = _decoder.forward(
        params, tokens, cfg, _TABLE, lambda _: layer_fn, n_aux=2
    )
    with scope("ddl.head"):
        losses = losses / cfg.n_layers
    return logits, losses, jnp.stack(picks)


def _router_penalty(cfg: MoeConfig, losses: jax.Array) -> jax.Array:
    """What the router losses add to the train loss."""
    return cfg.router_aux_weight * losses[0] + cfg.router_z_weight * losses[1]


# -- pipeline parallelism ----------------------------------------------------


#: ``stage_params(params, n_stages, n_chunks=1)``: an :func:`init_params`
#: pytree regrouped for pipeline parallelism.  Expert stacks keep their
#: leading E axis inside each stage leaf: ``(S, [V,] L/S, E, ...)``.
stage_params = _decoder.stage_params


def pp_param_specs(
    cfg: MoeConfig, axis: str = "pp", n_chunks: int = 1
) -> Params:
    """PartitionSpecs for the :func:`stage_params` layout — ``pp``
    shards stages; within a stage the expert/Megatron layout of
    :func:`param_specs` applies (``ep`` still shards the expert axis of
    the at-rest storage)."""
    return _decoder.pp_param_specs(param_specs(cfg), axis, n_chunks)


def forward_pp(
    params: Params,
    tokens: jax.Array,
    cfg: MoeConfig,
    mesh: Any,
    n_microbatches: int,
    axis: str = "pp",
    schedule: str = "gpipe",
    n_chunks: "int | None" = None,
) -> Tuple[jax.Array, jax.Array]:
    """(logits, mean router load-balance loss) with the MoE blocks
    pipelined over ``axis`` (``schedule``: gpipe, or interleaved 1f1b with
    ``stage_params(..., n_chunks=)`` weights).

    The router aux loss accumulates THROUGH the pipe: the activation
    pytree carries a per-row accumulator alongside the residual stream
    (``pipeline_apply`` hops every leaf together), each stage adds its
    layers' aux, and the caller averages over rows.  Capacity
    semantics: routing groups are the token sets ``moe_mlp`` sees —
    one dp shard of one microbatch under the auto dp batch spec
    (``C = ceil(topk·(mb/dp)·T/E·cf)``), the whole microbatch when dp
    does not shard it.  Logits match the non-pp forward exactly
    whenever capacity does not bind (routing is per-token); the aux is
    the mean of per-group aux — the same load-balance pressure at
    group granularity, not numerically equal to the full-batch aux
    (it is not linear in token subsets).
    """
    logits, losses = _forward_pp(
        params, tokens, cfg, mesh, n_microbatches, axis, schedule, n_chunks
    )
    return logits, losses[0]


def _forward_pp(
    params: Params,
    tokens: jax.Array,
    cfg: MoeConfig,
    mesh: Any,
    n_microbatches: int,
    axis: str,
    schedule: str,
    n_chunks: "int | None",
) -> Tuple[jax.Array, jax.Array]:
    """:func:`forward_pp` with both router losses: (logits, (2,))."""
    names = getattr(mesh, "axis_names", ())
    # Without a real pp axis, pipeline_apply falls back to a sequential
    # lax.map OUTSIDE shard_map (pipeline.py), where the layer body runs
    # with mesh=None — a token-sharded dp/sp axis would then hit the
    # ragged impl's global argsort under GSPMD and all-gather every
    # token per layer.  (With pp>1 the pipeline's shard_map makes dp
    # manual, so local routing is correct and fast — same argument as
    # _routed_mlp.)
    gathers_tokens = not (axis in names and mesh.shape[axis] > 1) and any(
        ax in names and mesh.shape[ax] > 1 for ax in ("dp", "sp")
    )
    if cfg.moe_impl == "auto" and gathers_tokens:
        cfg = dataclasses.replace(cfg, moe_impl="einsum")
    cfg = _resolve_impl(cfg, mesh)
    if cfg.moe_impl == "ragged" and gathers_tokens:
        raise ValueError(
            f"moe_impl='ragged' with forward_pp needs a real {axis}>1 "
            "mesh axis when dp or sp > 1 (the sequential fallback would "
            "gather token shards); use the einsum impl or a pipelined "
            "mesh"
        )
    B, T = tokens.shape
    dt = cfg.dtype
    positions = jnp.arange(T)
    with scope("ddl.embed"):
        x = params["embed"].astype(dt)[tokens]

    def one_layer(state, layer):
        h, loss_rows = state
        h, _, losses = _layer_apply(layer, h, cfg, positions, mesh=None)
        return h, loss_rows + losses.astype(loss_rows.dtype)

    layer_fn = _remat.wrap(one_layer, cfg.remat)

    def stage_fn(stage: Params, state: Any) -> Any:
        out, _ = jax.lax.scan(
            lambda c, lyr: (layer_fn(c, lyr), None), state, stage
        )
        return out

    from ddl_tpu.parallel.pipeline import pipeline_apply

    x, loss_rows = pipeline_apply(
        params["stages"],
        (x, jnp.zeros((B, 2), jnp.float32)),
        stage_fn, mesh, n_microbatches, axis=axis,
        schedule=schedule, n_chunks=n_chunks,
    )
    logits = _decoder.lm_head(params, x, cfg)
    # Every row of a microbatch carries that microbatch's summed router
    # losses; the row-mean is the microbatch-mean, normalized per layer
    # as in the non-pp forward.
    return logits, jnp.mean(loss_rows, axis=0) / cfg.n_layers


def next_token_loss_pp(
    params: Params,
    tokens: jax.Array,
    cfg: MoeConfig,
    mesh: Any,
    n_microbatches: int,
    axis: str = "pp",
    schedule: str = "gpipe",
    n_chunks: "int | None" = None,
) -> jax.Array:
    """Cross-entropy + weighted router losses over the pipelined
    forward."""
    logits, losses = _forward_pp(
        params, tokens, cfg, mesh, n_microbatches, axis, schedule, n_chunks
    )
    ce = next_token_cross_entropy(logits, tokens)
    return ce + _router_penalty(cfg, losses)


# -- inference: KV-cache decode + generate -----------------------------------


def init_cache(cfg: MoeConfig, batch: int, max_len: int) -> Params:
    """Per-layer KV cache buffers for autoregressive decoding — THE
    llama cache layout (one delegation, so the layout backing the shared
    ``attn_with_cache`` math cannot drift between families); the routed
    MLP needs no cache of its own, routing re-decides per decoded
    token."""
    return _llama.init_cache(cfg, batch, max_len)


def forward_with_cache(
    params: Params,
    tokens: jax.Array,
    cfg: MoeConfig,
    cache: Params,
    pos: jax.Array,
    last_only: bool = False,
) -> Tuple[jax.Array, Params]:
    """Cached MoE forward (prefill: T = prompt length; decode: T = 1).

    The attention sub-block is the shared cache math
    (``llama.attn_with_cache``: compact GQA cache, causal-position
    mask); each decoded token then routes through the SAME top-k gate
    and dispatch impl as training (``cfg.moe_impl``; ``auto`` is
    dropless ragged here, there being no mesh — via
    ``_moe_mlp_dispatch`` on the flat (B*T, D) tokens).

    Impl semantics.  ``ragged``: dropless — decode matches the full
    forward exactly, always.  ``einsum``: expert capacity is computed
    from the call's OWN token count; prefill routes the whole prompt
    jointly (identical N to the training forward, so prefill logits
    match it exactly, drops included), while stepwise decode routes B
    tokens per step with fresh capacity, matching the full forward
    exactly whenever capacity does not bind — under capacity pressure
    the decode path DROPS LESS than teacher forcing, never more.
    Returns (logits, updated cache); router aux loss is a training
    quantity and is not computed here.
    """
    cfg = _resolve_impl(cfg, None)
    B, T = tokens.shape
    dt = cfg.dtype
    positions = pos + jnp.arange(T)
    cache_idx = jnp.arange(cache["k"].shape[2])
    x = params["embed"].astype(dt)[tokens]

    # Stacked-cache value chain, as in llama.forward_with_cache: each
    # layer writes only its new-token slot so the scan updates in place.
    k_all, v_all = cache["k"], cache["v"]
    for li, layer in enumerate(params["layers"]):
        x, k_all, v_all = _llama.attn_with_cache(
            layer, x, cfg, k_all, v_all, li, pos, positions, cache_idx,
        )
        h = _decoder.rms_norm(x, layer["mlp_norm"], cfg.norm_eps)
        moe_out = _moe_mlp_dispatch(h.reshape(B * T, -1), layer, cfg)[0]
        x = x + moe_out.reshape(B, T, -1)

    logits = _llama.cached_head(params, x, cfg, last_only)
    return logits, {"k": k_all, "v": v_all}


def generate(
    params: Params,
    prompt: jax.Array,
    cfg: MoeConfig,
    max_new_tokens: int,
    temperature: float = 0.0,
    key: Optional[jax.Array] = None,
    top_k: Optional[int] = None,
    top_p: Optional[float] = None,
    eos_id: Optional[int] = None,
) -> jax.Array:
    """Autoregressive MoE generation — same contract as
    ``models.llama.generate`` (greedy or explicit-key sampling with
    optional top-k / nucleus top-p filtering and EOS masking; prefill
    in one cached forward, scanned decode steps), completing inference
    parity across the model families."""
    return _llama.generate_with(
        forward_with_cache, init_cache, params, prompt, cfg,
        max_new_tokens, temperature, key, top_k=top_k, top_p=top_p,
        eos_id=eos_id,
    )


def next_token_loss(
    params: Params,
    tokens: jax.Array,
    cfg: MoeConfig,
    mesh: Optional[Any] = None,
    segment_ids: Optional[jax.Array] = None,
) -> jax.Array:
    """Cross-entropy + the weighted router losses (load balance, and
    the z-loss where ``cfg.router_z_weight`` is set).

    With ``segment_ids`` (packed batches), attention is segment-masked
    and cross-document boundary predictions drop from the CE, matching
    ``models.llama.next_token_loss``."""
    logits, losses, _ = _forward(params, tokens, cfg, mesh, segment_ids)
    ce = next_token_cross_entropy(logits, tokens, segment_ids=segment_ids)
    with scope("ddl.head"):
        return ce + _router_penalty(cfg, losses)
