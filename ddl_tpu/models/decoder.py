"""What every decoder family shares, stated once: the primitives (RMSNorm,
RoPE, SwiGLU, the head, the seeded initialiser), the walk over a stack of
layers, and the declaration of a stack's parameters.

A family (``llama``, ``moe``, ``afmoe``, ``deepseek_v3``, ``olmo_hybrid``,
``minicpm_sala``, ``lfm2_moe``) is a config, a parameter table, its mixers and a tuple of
layer KINDS — one hashable a layer, whatever tells its layers apart
(``"sliding_attention"`` and dense; ``"linear_attention"``):

- :func:`forward` owns the embedding lookup, the walk over the layers with
  each kind's block under the remat policy, what a routed layer returns
  beside the stream (its router's picks, ``moe``'s auxiliary losses) and
  the head.  Like layers are NOT scanned: every layer stands in the
  program (ROADMAP S10).
- :class:`Row` declares one parameter — name, shape, ``PartitionSpec`` and
  how it is initialised; a family's :class:`Table` derives ``init_params``,
  ``param_specs`` and ``param_shapes`` from its rows, so the three trees
  cannot drift apart.

The attention blocks and the hybrids' mixers compute different things and
stay in their families.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ddl_tpu.models import remat as _remat
from ddl_tpu.models.losses import next_token_cross_entropy
from ddl_tpu.ops.naming import scope

Params = Dict[str, Any]


# -- the primitives ------------------------------------------------------------------


def dense_init(k: jax.Array, fan_in: Any, shape: Any, pdt: Any) -> jax.Array:
    """1/sqrt(fan_in)-scaled normal init in ``pdt`` storage."""
    return (
        jax.random.normal(k, shape, jnp.float32) / jnp.sqrt(fan_in)
    ).astype(pdt)


def rms_norm(x: jax.Array, gain: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * scale * gain).astype(x.dtype)


def rope(x: jax.Array, positions: jax.Array, theta: float,
         freqs: Optional[jax.Array] = None) -> jax.Array:
    """Rotary embedding; x: (B, T, H, D), positions: (T,).  ``freqs`` (D/2,)
    stands in for ``theta``'s own frequencies (a scaled RoPE: YaRN)."""
    d_half = x.shape[-1] // 2
    if freqs is None:
        freqs = theta ** (-jnp.arange(0, d_half, dtype=jnp.float32) / d_half)
    angles = positions[:, None].astype(jnp.float32) * freqs[None, :]  # (T, Dh)
    cos = jnp.cos(angles)[None, :, None, :].astype(x.dtype)
    sin = jnp.sin(angles)[None, :, None, :].astype(x.dtype)
    x1, x2 = x[..., :d_half], x[..., d_half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def swiglu(layer: Params, h: jax.Array) -> jax.Array:
    """The SwiGLU core (no norm, no residual) — shared by the plain
    block, a routed layer's shared expert and the tp-resident stage (whose
    row-sharded ``w_down`` makes this a PARTIAL sum completed by a psum)."""
    dt = h.dtype
    gate = jax.nn.silu(_remat.tag(h @ layer["w_gate"].astype(dt), _remat.SWIGLU))
    up = _remat.tag(h @ layer["w_up"].astype(dt), _remat.SWIGLU)
    return (gate * up) @ layer["w_down"].astype(dt)


def mlp_block(layer: Params, x: jax.Array, cfg: Any) -> jax.Array:
    """Pre-norm SwiGLU MLP sub-block with residual (train and decode)."""
    with scope("ddl.mlp"):
        return x + swiglu(layer, rms_norm(x, layer["mlp_norm"], cfg.norm_eps))


def taps_sum(padded: jax.Array, taps: jax.Array, T: int, flip: bool) -> jax.Array:
    """The causal depthwise convolution's sum, shared by the families that
    have one (``olmo_hybrid``'s SiLU convolutions, ``lfm2_moe``'s gated short
    convolution) and by their backward passes: ``sum_j taps[j] * padded[:,
    j' : j' + T]`` in float32, ``j' = j`` (or ``K - 1 - j`` with ``flip``):
    one pass over ``padded`` (B, T + K - 1, C) read at K offsets."""
    K = taps.shape[0]
    taps = taps.astype(jnp.float32)
    return sum(
        padded[:, (K - 1 - j if flip else j):][:, :T].astype(jnp.float32) * taps[j]
        for j in range(K)
    )


def lm_head(
    params: Params, x: jax.Array, cfg: Any, scale: Optional[float] = None
) -> jax.Array:
    """Final norm + vocabulary matmul, float32 logits — the one head of
    every decoder family.  ``scale`` multiplies the normed stream (MiniCPM's
    ``dim_model_base / d_model``): it rides the norm's weight.  A TIED head
    (a :class:`Table` with ``tied``: no ``lm_head`` among the parameters)
    reads the embedding's own rows, transposed; the embedding's gradient is
    then the sum of its two uses, the lookup's scatter-add and this matmul's."""
    gain = params["final_norm"]
    if scale is not None:
        gain = gain.astype(jnp.float32) * scale
    with scope("ddl.head"):
        x = rms_norm(x, gain, cfg.norm_eps)
        head = params["lm_head"] if "lm_head" in params else params["embed"].T
        return (x @ head.astype(x.dtype)).astype(jnp.float32)


# -- the stack -----------------------------------------------------------------------


def forward(
    params: Params,
    tokens: jax.Array,
    cfg: Any,
    table: Table,
    block: Callable[[Any], Callable[..., Any]],
    embed_scale: Any = None,
    head_scale: Optional[float] = None,
    n_aux: int = 0,
) -> Tuple[jax.Array, List[jax.Array], Optional[jax.Array]]:
    """One decoder stack over (B, T) ``tokens`` → (logits (B, T, vocab)
    float32, the routed layers' picks, the summed auxiliary losses).

    ``table`` is the family's :class:`Table`: its ``kinds(cfg)`` names each
    layer's kind — the tuple its parameters were laid out by, stated there
    and nowhere else — and ``block(kind)`` is that kind's body
    ``(x, layer) -> x`` — or ``-> (x, picks, aux)`` in a stack that
    routes: the router's picks (B, T, topk), ``None`` from a layer without
    a router, and ``n_aux`` float32 auxiliary losses (or ``None``), which
    are summed over the layers under ``ddl.head``.  Every layer's body runs
    under the config's remat policy (:mod:`ddl_tpu.models.remat`: what the
    backward pass saves and what it recomputes; under ``selective`` inside
    a step factory whose device reports its HBM, each layer also saves the
    kinds :func:`ddl_tpu.models.remat.planned` finds room for — no layer is
    scanned, so each carries a policy of its own), wrapped once a BODY and
    set of kinds: JAX keeps a body's trace, and what it derives from it, by
    the function and policy objects, and ``block`` is asked once a layer.  Where it answers
    with the SAME function for every layer of a kind (``llama``, ``moe``)
    the kind is traced, differentiated and lowered once; where it builds
    the body anew, every layer is for itself — what the four newer families
    always did, and what their pinned program texts record (a shared body
    is the same program, printed and lowered once: ROADMAP D1 (b)).
    ``embed_scale`` multiplies the embedded rows, ``head_scale`` the normed
    stream in front of the head (:func:`lm_head`).
    """
    x = embed(params, tokens, cfg, embed_scale)
    bodies = [block(kind) for kind in table.kinds(cfg)]
    logits_bytes = 4 * tokens.size * cfg.vocab
    with _remat.planned(cfg.remat, bodies, x, params["layers"], logits_bytes) as plan:
        x, picks, aux = walk(x, params["layers"], bodies, plan, cfg, n_aux)
    return lm_head(params, x, cfg, head_scale), picks, aux


def embed(params: Params, tokens: jax.Array, cfg: Any,
          embed_scale: Any = None) -> jax.Array:
    """The embedded rows (B, T, D) in the config's dtype, under ``ddl.embed``."""
    with scope("ddl.embed"):
        x = params["embed"].astype(cfg.dtype)[tokens]  # (B, T, D)
        if embed_scale is not None:
            x = x * embed_scale
    return x


def walk(
    x: Any,
    layers: Sequence[Params],
    bodies: Sequence[Callable[..., Any]],
    plan: Sequence[Tuple[str, ...]],
    cfg: Any,
    n_aux: int = 0,
) -> Tuple[Any, List[jax.Array], Optional[jax.Array]]:
    """The walk over a stack's layers: ``bodies[i](x, layers[i])`` under the
    config's remat policy with ``plan[i]``'s kinds saved -> (what the last
    layer hands on, the routed layers' picks, the summed auxiliary losses).
    ``x`` is whatever the family's layers carry - the embedded row (B, T, D)
    of a plain residual path, the four-row stream of a hyper-connected one
    (``models/xing4.py``, which opens it behind :func:`embed`, closes it in
    front of :func:`lm_head` and keeps the closed, un-normed stream for its
    second head) - and the walk never looks inside it.  Trace it inside the
    :func:`ddl_tpu.models.remat.planned` context the plan came from."""
    aux = jnp.zeros((n_aux,), jnp.float32) if n_aux else None
    wrap = functools.lru_cache(maxsize=None)(
        lambda body, saved: _remat.wrap(body, cfg.remat, saved))
    picks = []
    for body, saved, layer in zip(bodies, plan, layers):
        out = wrap(body, saved)(x, layer)
        x, top_e, layer_aux = out if isinstance(out, tuple) else (out, None, None)
        if layer_aux is not None:
            with scope("ddl.head"):  # the auxiliary losses' reduction
                aux = aux + layer_aux
        if top_e is not None:
            picks.append(top_e)
    return x, picks, aux


def stack_picks(picks: List[jax.Array], tokens: jax.Array, topk: int) -> jax.Array:
    """The routed layers' picks as one (L_routed, B, T, topk) array — empty
    where no layer routes."""
    return jnp.stack(picks) if picks else jnp.zeros(
        (0,) + tokens.shape + (topk,), jnp.int32
    )


def loss_of(forward: Callable[..., jax.Array]) -> Callable[..., jax.Array]:
    """``next_token_loss(params, tokens, cfg, mesh=None)`` of a family whose
    loss is the cross-entropy of its ``forward`` and nothing else.  The
    argument is NAMED for what it is (and shadows this module's stack
    driver here): the benchmark's suites read the loss's source for
    ``next_token_cross_entropy(forward(`` — the check's loss is the model's
    train loss — and ``tests/test_ops.py`` holds the text and the cell."""

    def next_token_loss(
        params: Params, tokens: jax.Array, cfg: Any, mesh: Optional[Any] = None
    ) -> jax.Array:
        """Mean next-token cross-entropy over (B, T) tokens."""
        return next_token_cross_entropy(forward(params, tokens, cfg, mesh), tokens)

    return next_token_loss


def no_decode(family: str, lacks: str) -> Tuple[Callable[..., Any], Callable[..., Any]]:
    """``(forward_with_cache, generate)`` of a family that has no decode
    path: both raise by name, saying what the family's cache ``lacks``."""

    def refuse(entry: str) -> Callable[..., Any]:
        def stub(*args: Any, **kwargs: Any) -> Any:
            raise NotImplementedError(f"{family}.{entry}: serving needs {lacks}")

        stub.__name__ = stub.__qualname__ = entry
        return stub

    return refuse("forward_with_cache"), refuse("generate")


# -- a stack's parameters --------------------------------------------------------------

COL, ROW = P("fsdp", "tp"), P("tp", "fsdp")  # the Megatron layout's two matrices


class Row(NamedTuple):
    """One parameter.  Rows are listed in the order their keys are drawn."""

    #: ``"wq"``; ``"shared.w_gate"`` lies in the dict under ``"shared"``.
    name: str
    shape: Tuple[int, ...]
    spec: P
    #: A constant (norm weights 1, a selection bias 0): no key is drawn.
    fill: Optional[float] = None
    #: ``(key, shape) -> array`` of the row's dtype, for what is not a
    #: matrix.  Neither: seeded normal / sqrt(``fan_in``).
    draw: Optional[Callable[..., jax.Array]] = None
    #: ``None``: the matrix's input width, ``shape[-2]``.
    fan_in: Optional[int] = None
    #: ``None``: the config's ``param_dtype``.
    dtype: Any = None


def ones(name: str, n: int) -> Row:
    """A norm's weight vector, replicated."""
    return Row(name, (n,), P(None), fill=1.0)


def attn_rows(d: int, q_out: int, kv_out: int, gated: bool = False) -> List[Row]:
    """``wq``, ``wk``, ``wv`` and ``wo`` of an attention block whose heads
    are ``q_out`` / ``kv_out`` wide together — and ``wg``, the output gate's,
    where it is ``gated``."""
    rows = [Row("wq", (d, q_out), COL), Row("wk", (d, kv_out), COL),
            Row("wv", (d, kv_out), COL)]
    if gated:
        rows.append(Row("wg", (d, q_out), COL))
    return rows + [Row("wo", (q_out, d), ROW)]


def swiglu_rows(d_in: int, width: int, prefix: str = "", lead: Tuple[int, ...] = (),
                lead_spec: Tuple[Any, ...] = ()) -> List[Row]:
    """``w_gate``, ``w_up`` (d_in, width) and ``w_down`` (width, d_in) of a
    SwiGLU; ``lead`` stacks experts in front, sharded as ``lead_spec``."""
    up, down = lead + (d_in, width), lead + (width, d_in)
    return [
        Row(prefix + "w_gate", up, P(*lead_spec, *COL)),
        Row(prefix + "w_up", up, P(*lead_spec, *COL)),
        Row(prefix + "w_down", down, P(*lead_spec, *ROW)),
    ]


def _top_rows(cfg: Any, embed_fan_in: Optional[int], tied: bool) -> List[Row]:
    d = cfg.d_model
    rows = [
        Row("embed", (cfg.vocab, d), P(None, "fsdp"), fan_in=embed_fan_in or d),
        ones("final_norm", d),
    ]
    return rows if tied else rows + [Row("lm_head", (d, cfg.vocab), COL)]


def _tree(rows: Sequence[Row], leaf: Callable[[Row], Any]) -> Params:
    tree: Params = {}
    for row in rows:
        *path, name = row.name.split(".")
        node = tree
        for part in path:
            node = node.setdefault(part, {})
        node[name] = leaf(row)
    return tree


class Table(NamedTuple):
    """A family's parameters, declared once: :meth:`init_params`,
    :meth:`param_specs` and :meth:`param_shapes` are the same tree of the
    same rows, so the three cannot drift apart.  A family binds them as its
    module-level ``init_params(cfg, key)``, ``param_specs(cfg)`` and
    ``param_shapes(cfg)``."""

    #: ``cfg ->`` one kind a layer.
    kinds: Callable[[Any], Sequence[Any]]
    #: ``(cfg, kind) ->`` the rows of a layer of that kind.
    layer_rows: Callable[[Any, Any], Sequence[Row]]
    #: ``(base, a layer)``: the key is split ``base + n_layers * a layer``
    #: ways — a family's own count, and the split's width is part of every
    #: key, so it stays what it was when the family's weights were first
    #: drawn.
    n_keys: Tuple[int, int]
    #: The embedding's rows are normal / sqrt(this); ``None``: ``d_model``.
    embed_fan_in: Optional[int] = None
    #: The head is the embedding's rows (:func:`lm_head`): no ``lm_head`` row.
    tied: bool = False
    #: ``cfg ->`` rows beside the stack (a multi-token-prediction module's:
    #: ``"mtp.w_eh"`` lies under ``"mtp"``), drawn last; their keys count
    #: into ``n_keys``' base.
    extra_rows: Optional[Callable[[Any], Sequence[Row]]] = None

    def _tree(self, cfg: Any, leaf: Callable[[Row], Any]) -> Params:
        layers = [_tree(self.layer_rows(cfg, k), leaf) for k in self.kinds(cfg)]
        top = _top_rows(cfg, self.embed_fan_in, self.tied)
        extra = self.extra_rows(cfg) if self.extra_rows else []
        return {**_tree([*top, *extra], leaf), "layers": layers}

    def init_params(self, cfg: Any, key: jax.Array) -> Params:
        """The params pytree (``cfg.param_dtype`` storage but where a row
        says otherwise): the keys are drawn row after row, layer after
        layer, then for the embedding and the head."""
        base, a_layer = self.n_keys
        keys = iter(jax.random.split(key, base + cfg.n_layers * a_layer))

        def make(row: Row) -> jax.Array:
            dtype = row.dtype or cfg.param_dtype
            if row.fill is not None:
                return jnp.full(row.shape, row.fill, dtype)
            if row.draw is not None:
                return row.draw(next(keys), row.shape)
            return dense_init(next(keys), row.fan_in or row.shape[-2], row.shape, dtype)

        return self._tree(cfg, make)

    def param_specs(self, cfg: Any) -> Params:
        """``PartitionSpec`` s in :meth:`init_params`' tree: fsdp shards the
        d_model-ish axis, tp heads / ffn-hidden (the Megatron layout realised
        declaratively: GSPMD inserts the collectives; axes absent from the
        mesh are dropped by the train-step factory)."""
        return self._tree(cfg, lambda row: row.spec)

    def param_shapes(self, cfg: Any) -> Params:
        """``ShapeDtypeStruct`` s in :meth:`init_params`' tree — the
        zero-FLOP input of the optimizer's HBM accounting
        (:func:`ddl_tpu.parallel.optimizer.hbm_accounting`): a 4B-parameter
        layout prices without a weight being made."""
        return self._tree(cfg, lambda row: jax.ShapeDtypeStruct(
            row.shape, row.dtype or cfg.param_dtype))


# -- pipeline staging (llama, moe) ------------------------------------------------------


def stage_params(params: Params, n_stages: int, n_chunks: int = 1) -> Params:
    """Rearrange a :meth:`Table.init_params` pytree for pipeline parallelism.

    The per-layer dicts regroup into ``n_stages`` equal stages and stack
    into leaves with leading ``(S, L/S)`` axes —
    :func:`ddl_tpu.parallel.pipeline_apply`'s stacked-stage layout, with
    the S axis sharded over ``pp`` so each device stores only its own
    stage's layers (an expert stack keeps its E axis inside each stage
    leaf).  ``n_chunks > 1`` builds the interleaved ``(S, V, L/(S·V))``
    layout for ``schedule="1f1b"`` (device d chunk c holds global stage
    c·S+d).  Embedding, final norm and lm head stay outside the pipe (they
    run replicated over pp, before/after the schedule).

    Inverse-free by design: training checkpoints save THIS layout; the
    non-pp layout is only an initialization convenience.
    """
    from ddl_tpu.parallel.pipeline import stack_layer_stages

    staged = {k: v for k, v in params.items() if k != "layers"}
    staged["stages"] = stack_layer_stages(
        params["layers"], n_stages, n_chunks=n_chunks
    )
    return staged


def pp_param_specs(specs: Params, axis: str = "pp", n_chunks: int = 1) -> Params:
    """A family's :func:`param_specs` for the :func:`stage_params` layout:
    ``axis`` shards the stage axis (at-rest storage is one stage per pp
    group), the chunk (1f1b only) and per-stage layer axes are unsharded,
    and the trailing axes keep the layer's own layout."""
    from ddl_tpu.parallel.pipeline import stage_spec_tree

    staged = {k: v for k, v in specs.items() if k != "layers"}
    staged["stages"] = stage_spec_tree(specs["layers"][0], axis, n_chunks=n_chunks)
    return staged
