"""Llama-style decoder LM — the flagship model fed by the ddl_tpu loader.

The reference framework carried no models (SURVEY §0: "no model code"); the
driver's pod-scale config ("Llama-3-8B pretrain loop fed solely by the ddl
TPU backend", BASELINE.json configs[4]) requires a real transformer training
loop on the consumer side.  This is a TPU-first functional implementation:

- pure init/apply functions over a params pytree (jit/grad/shard friendly,
  no framework state),
- bfloat16 activations by default (MXU-native), fp32 RMSNorm accumulations,
- RoPE, grouped-query attention, SwiGLU — the Llama-3 block structure,
- sequence parallelism via ring attention when the mesh has an ``sp`` axis,
- parameter PartitionSpecs for fsdp/tp sharding (GSPMD inserts the
  collectives).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ddl_tpu.models import decoder as _decoder
from ddl_tpu.models import remat as _remat
from ddl_tpu.models.losses import next_token_cross_entropy
from ddl_tpu.ops.naming import scope

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab: int = 256
    d_model: int = 128
    n_layers: int = 2
    n_heads: int = 4
    n_kv_heads: int = 2
    d_ff: int = 352
    max_seq: int = 512
    rope_theta: float = 500000.0  # Llama-3 base frequency
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    #: Storage dtype of the params pytree (fp32 master weights by
    #: default; bf16 halves param+optimizer HBM for memory-bound
    #: geometries — gradient accumulation stays exact either way, the
    #: train step accumulates in fp32).
    param_dtype: Any = jnp.float32
    #: Rematerialisation policy for the backward pass
    #: (:mod:`ddl_tpu.models.remat`): ``"none"`` | ``"full"`` (save only
    #: each layer's residual-stream input, recompute everything — the
    #: classic FLOPs-for-HBM trade that lets long-sequence/big-model
    #: configs fit a single chip) | ``"selective"`` (additionally save
    #: the attention outputs, and the flash kernels' logsumexp beside
    #: them, so the backward never re-runs the attention kernel — buys
    #: back most of full-remat's MFU loss) | ``"dots"`` (save all
    #: non-batched matmul outputs; re-runs it).  Bools accepted for back
    #: compat: ``True`` == ``"full"``, ``False`` == ``"none"``.
    remat: Any = False
    # "auto": Pallas flash attention on TPU, dense elsewhere; "flash"/"dense"
    # force one path.  Sequence-parallel meshes always use ring attention.
    attn_impl: str = "auto"
    #: QK-norm as OLMoE has it (``model_type: olmoe``): RMSNorm with a
    #: learned weight over the WHOLE projected query (``n_heads *
    #: head_dim`` values) and key (``n_kv_heads * head_dim``), before the
    #: head split and RoPE.  States the architecture; off, the params
    #: tree and the program are exactly what they were without it.
    qk_norm: bool = False

    def __post_init__(self) -> None:
        if self.attn_impl not in ("auto", "flash", "dense"):
            raise ValueError(
                f"attn_impl must be 'auto', 'flash', or 'dense', "
                f"got {self.attn_impl!r}"
            )
        _remat.resolve(self.remat)  # fail on junk at config build time

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @staticmethod
    def llama3_8b() -> "LlamaConfig":
        """The reference-scale config (BASELINE.json configs[4])."""
        return LlamaConfig(
            vocab=128256, d_model=4096, n_layers=32, n_heads=32,
            n_kv_heads=8, d_ff=14336, max_seq=8192,
        )

    @staticmethod
    def llama_4b() -> "LlamaConfig":
        """The ≥4B fits-only-with-zero1 geometry (ISSUE 8): ~4.6B params
        at the 8B config's layer shape, fp32 master weights.  On the
        v5e-32 layout (dp=8 × fsdp=4, 16 GiB/chip) the persistent
        residents (params + grads + adam moments) bust the per-chip HBM
        with the optimizer state replicated over dp and fit with ~6 GiB
        of activation headroom under ``optimizer_sharding="zero1"`` —
        the accounting test (tests/test_optimizer.py) prices exactly
        this config."""
        return LlamaConfig(
            vocab=32768, d_model=4096, n_layers=20, n_heads=32,
            n_kv_heads=8, d_ff=14336, max_seq=4096,
        )

    @staticmethod
    def tiny() -> "LlamaConfig":
        return LlamaConfig()


def qk_norm_rows(cfg: Any) -> List[_decoder.Row]:
    """A layer's two QK-norm weight vectors — present only where
    ``cfg.qk_norm`` is on (shared by the llama and moe trees)."""
    if not cfg.qk_norm:
        return []
    hd = cfg.head_dim
    return [
        _decoder.ones("q_norm", cfg.n_heads * hd),
        _decoder.ones("k_norm", cfg.n_kv_heads * hd),
    ]


def attn_rows(cfg: Any) -> List[_decoder.Row]:
    """The attention sub-block's norm and four projections (the llama and
    moe trees)."""
    d, hd = cfg.d_model, cfg.head_dim
    return [
        _decoder.ones("attn_norm", d),
        *_decoder.attn_rows(d, cfg.n_heads * hd, cfg.n_kv_heads * hd),
    ]


def _layer_rows(cfg: LlamaConfig, kind: Any = None) -> List[_decoder.Row]:
    """The parameter table of a layer."""
    return [
        *attn_rows(cfg),
        _decoder.ones("mlp_norm", cfg.d_model),
        *_decoder.swiglu_rows(cfg.d_model, cfg.d_ff),
        *qk_norm_rows(cfg),
    ]


#: ``init_params(cfg, key)`` (``cfg.param_dtype`` storage; fp32 master
#: weights by default), ``param_specs(cfg)`` and ``param_shapes(cfg)`` of one
#: table (:class:`ddl_tpu.models.decoder.Table`); one kind of layer.
_TABLE = _decoder.Table(lambda cfg: (None,) * cfg.n_layers, _layer_rows, (4, 7))
init_params, param_specs, param_shapes = (
    _TABLE.init_params, _TABLE.param_specs, _TABLE.param_shapes
)


def forward(
    params: Params,
    tokens: jax.Array,
    cfg: LlamaConfig,
    mesh: Optional[Any] = None,
    segment_ids: Optional[jax.Array] = None,
) -> jax.Array:
    """Next-token logits, (B, T, vocab).

    With a mesh carrying an ``sp`` axis of size > 1, attention runs as
    sequence-parallel ring attention (K/V rotating over ICI); otherwise
    dense causal attention.  RoPE positions are global either way (the
    token axis is only *sharded*, never re-indexed).

    ``segment_ids`` (B, T): packed-pretraining batches — attention stays
    within each packed document (kernel-level masking; RoPE positions
    remain row-global, the common packed-training convention).
    """
    positions = jnp.arange(tokens.shape[1])

    def layer_fn(x: jax.Array, layer: Params) -> jax.Array:
        return _layer_apply(
            layer, x, cfg, positions, mesh=mesh, segment_ids=segment_ids
        )

    return _decoder.forward(params, tokens, cfg, _TABLE, lambda _: layer_fn)[0]


def attn_block(
    layer: Params,
    x: jax.Array,
    cfg: Any,
    positions: jax.Array,
    mesh: Optional[Any] = None,
    segment_ids: Optional[jax.Array] = None,
) -> jax.Array:
    """Attention sub-block (norm → qkv/rope → attention → wo residual)
    on the residual stream — the train-side twin of
    :func:`attn_with_cache`, shared by the llama AND moe blocks (only
    the MLP that follows differs, so attention semantics cannot drift
    between families)."""
    from ddl_tpu.parallel.ring_attention import attention

    B, T = x.shape[:2]
    dt = x.dtype
    with scope("ddl.attn"):
        h = _decoder.rms_norm(x, layer["attn_norm"], cfg.norm_eps)
        q, k, v = _attn_qkv(layer, h, cfg, positions)
        # GQA k/v stay compact: expansion happens inside the attention
        # block, so ring attention rotates 1/rep of the bytes over ICI.
        rep = cfg.n_heads // cfg.n_kv_heads
        attn = attention(
            q, k, v, mesh=mesh, impl=cfg.attn_impl, causal=True,
            kv_repeat=rep, segment_ids=segment_ids,
        )
        return x + attn.reshape(B, T, -1) @ layer["wo"].astype(dt)


def _layer_apply(
    layer: Params,
    x: jax.Array,
    cfg: LlamaConfig,
    positions: jax.Array,
    mesh: Optional[Any] = None,
    segment_ids: Optional[jax.Array] = None,
) -> jax.Array:
    """One transformer block on the residual stream — the single layer
    body shared by :func:`forward` and the pipeline-parallel
    :func:`forward_pp` (same math, so pp/non-pp cannot diverge)."""
    x = attn_block(
        layer, x, cfg, positions, mesh=mesh, segment_ids=segment_ids
    )
    return _decoder.mlp_block(layer, x, cfg)


def _attn_qkv(layer: Params, h: jax.Array, cfg: LlamaConfig,
              positions: jax.Array, n_heads: Optional[int] = None,
              n_kv_heads: Optional[int] = None):
    """Project + rope one block's q/k/v (shared by train, decode and the
    tp-resident pipeline stage, which passes its LOCAL head counts —
    column-sharded projections yield contiguous head blocks).

    With ``cfg.qk_norm`` the projected query and key are RMS-normalised
    over ALL their values before the head split — which a tp-local head
    block cannot do, so local head counts are refused there."""
    B, T = h.shape[:2]
    dt = h.dtype
    nh = cfg.n_heads if n_heads is None else n_heads
    nkv = cfg.n_kv_heads if n_kv_heads is None else n_kv_heads
    if cfg.qk_norm and (nh, nkv) != (cfg.n_heads, cfg.n_kv_heads):
        raise NotImplementedError(
            "qk_norm normalises over the whole projection; a "
            "tp-resident stage holds only its own heads"
        )

    def project(w: str, heads: int, norm: Optional[str] = None) -> jax.Array:
        y = h @ layer[w].astype(dt)
        if norm:
            y = _decoder.rms_norm(y, layer[norm], cfg.norm_eps)
        return y.reshape(B, T, heads, cfg.head_dim)

    q = project("wq", nh, "q_norm" if cfg.qk_norm else None)
    k = project("wk", nkv, "k_norm" if cfg.qk_norm else None)
    v = project("wv", nkv)
    return (
        _decoder.rope(q, positions, cfg.rope_theta),
        _decoder.rope(k, positions, cfg.rope_theta),
        v,
    )


def init_cache(cfg: LlamaConfig, batch: int, max_len: int) -> Params:
    """Per-layer KV cache buffers for autoregressive decoding."""
    shape = (batch, max_len, cfg.n_kv_heads, cfg.head_dim)
    return {
        "k": jnp.zeros((cfg.n_layers,) + shape, cfg.dtype),
        "v": jnp.zeros((cfg.n_layers,) + shape, cfg.dtype),
    }


def forward_with_cache(
    params: Params,
    tokens: jax.Array,
    cfg: LlamaConfig,
    cache: Params,
    pos: jax.Array,
    last_only: bool = False,
) -> tuple[jax.Array, Params]:
    """Process ``tokens`` (B, T) starting at position ``pos`` against a KV
    cache (prefill: T = prompt length at pos 0; decode: T = 1).

    Returns (logits, updated cache); logits are (B, T, vocab), or
    (B, 1, vocab) with ``last_only`` (prefill wants only the frontier —
    full-prompt fp32 logits are ~4 GB at llama3_8b/8k).  Attention is
    dense over the cache with a causal-position mask — decode steps are
    matmul-thin so flash buys nothing there — and attends the COMPACT
    GQA cache via a grouped einsum (no rep-expanded cache copy in the
    bandwidth-bound decode hot path).  The cache length is static
    (``init_cache`` max_len) for jit-stable shapes.
    """
    dt = cfg.dtype
    positions = pos + jnp.arange(tokens.shape[1])
    cache_idx = jnp.arange(cache["k"].shape[2])
    x = params["embed"].astype(dt)[tokens]

    # The stacked cache buffers thread through the layers as one value
    # chain (each layer writes only its new-token slot), so XLA keeps
    # the update in place inside the decode scan — see attn_with_cache.
    k_all, v_all = cache["k"], cache["v"]
    for li, layer in enumerate(params["layers"]):
        x, k_all, v_all = attn_with_cache(
            layer, x, cfg, k_all, v_all, li, pos, positions, cache_idx,
        )
        x = _decoder.mlp_block(layer, x, cfg)

    return cached_head(params, x, cfg, last_only), {"k": k_all, "v": v_all}


def cached_head(
    params: Params, x: jax.Array, cfg: Any, last_only: bool
) -> jax.Array:
    """The head of the two cache paths (here and ``moe``): the final norm
    over every position, THEN the frontier's row where only it is asked
    for.  Not :func:`ddl_tpu.models.decoder.lm_head` on the sliced stream:
    XLA sinks a slice in front of the norm through the residual into the
    last layer's matmuls, which then round in bf16 as one row's and not as
    the prompt's — ``last_only`` must not change the frontier's logits."""
    x = _decoder.rms_norm(x, params["final_norm"], cfg.norm_eps)
    if last_only:
        x = x[:, -1:]
    return (x @ params["lm_head"].astype(x.dtype)).astype(jnp.float32)


def attn_with_cache(
    layer: Params,
    x: jax.Array,
    cfg: Any,
    k_all: jax.Array,
    v_all: jax.Array,
    li: int,
    pos: jax.Array,
    positions: jax.Array,
    cache_idx: jax.Array,
):
    """Attention sub-block (norm → qkv → cache update → GQA attention →
    residual) against a static-length KV cache — shared by llama and moe
    decode (same cache math, different MLP sub-block).  Returns
    (x_after_attn, k_all, v_all).

    ``k_all``/``v_all`` are the STACKED (L, B, len, kv, hd) cache
    buffers; the update writes ONLY the (B, T, kv, hd) new-token slot
    at (li, :, pos) and the buffers thread through layer after layer as
    one value chain, so inside the decode scan XLA updates the cache
    in place instead of materializing a fresh full cache per step — at
    B=64/1.4B-params the stack-per-step layout cost ~4x the mandatory
    HBM traffic and throughput stopped scaling with batch.

    Grouped-query attention attends the COMPACT cache via a grouped
    einsum (q regrouped per KV head, scores (B, Hkv, rep, T, L)) — no
    rep-expanded cache copy in the bandwidth-bound decode hot path.
    """
    B, T = x.shape[:2]
    dt = x.dtype
    rep = cfg.n_heads // cfg.n_kv_heads
    scale = 1.0 / (cfg.head_dim**0.5)
    h = _decoder.rms_norm(x, layer["attn_norm"], cfg.norm_eps)
    q, k, v = _attn_qkv(layer, h, cfg, positions)
    k_all = jax.lax.dynamic_update_slice(
        k_all, k.astype(dt)[None], (li, 0, pos, 0, 0)
    )
    v_all = jax.lax.dynamic_update_slice(
        v_all, v.astype(dt)[None], (li, 0, pos, 0, 0)
    )
    ck, cv = k_all[li], v_all[li]  # fused slice reads of the updated chain
    qg = q.reshape(B, T, cfg.n_kv_heads, rep, cfg.head_dim)
    s = jnp.einsum("bqkrd,bskd->bkrqs", qg, ck) * scale
    # Causal over absolute positions; cache slots past the frontier
    # (zeros) are masked the same way.
    mask = cache_idx[None, :] > positions[:, None]  # (T, L)
    s = jnp.where(mask[None, None, None], -1e30, s)
    p = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(dt)
    attn = jnp.einsum("bkrqs,bskd->bqkrd", p, cv)
    return x + attn.reshape(B, T, -1) @ layer["wo"].astype(dt), k_all, v_all


def generate(
    params: Params,
    prompt: jax.Array,
    cfg: LlamaConfig,
    max_new_tokens: int,
    temperature: float = 0.0,
    key: Optional[jax.Array] = None,
    top_k: Optional[int] = None,
    top_p: Optional[float] = None,
    eos_id: Optional[int] = None,
) -> jax.Array:
    """Autoregressive generation: greedy (``temperature == 0``) or
    temperature sampling, optionally filtered by ``top_k`` and/or
    nucleus ``top_p`` (temperature applied first, then the filters).
    With ``eos_id``, a row that emits it keeps emitting ``eos_id`` for
    the remaining positions (static shapes; truncate at the first EOS).
    Returns (B, prompt_len + max_new_tokens).

    Sampling (``temperature > 0``) REQUIRES an explicit ``key`` — a
    silent default would make "sampled" generation deterministically
    identical across calls, an easy misuse trap for an inference API.

    Prefill runs the whole prompt in ONE cached forward (full-width
    matmuls on the MXU); decode steps run under ``lax.scan`` with a
    static-shape KV cache — no recompilation per step, no Python loop.
    """
    return generate_with(
        forward_with_cache, init_cache, params, prompt, cfg,
        max_new_tokens, temperature, key, top_k=top_k, top_p=top_p,
        eos_id=eos_id,
    )


def _sample_filter(
    logits_t: jax.Array, top_k: Optional[int], top_p: Optional[float]
) -> jax.Array:
    """Mask logits for top-k / nucleus (top-p) sampling — static-shape
    ops only, safe inside the decode scan.

    top-k keeps the k highest logits; top-p keeps the smallest prefix
    of the probability-sorted vocab whose mass reaches ``top_p`` (the
    first token is always kept, so the filter can never empty the
    support).  Both filters compose (applied in that order, the
    conventional stacking)."""
    if top_k is not None:
        kth = jax.lax.top_k(logits_t, top_k)[0][..., -1:]
        logits_t = jnp.where(logits_t < kth, -jnp.inf, logits_t)
    if top_p is not None:
        sorted_logits = jnp.sort(logits_t, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # Keep ranks whose PRECEDING mass is < top_p (rank 0 always).
        keep_sorted = jnp.concatenate(
            [jnp.ones_like(cum[..., :1], bool), cum[..., :-1] < top_p],
            axis=-1,
        )
        # Threshold logit: the smallest kept logit per row.
        cutoff = jnp.min(
            jnp.where(keep_sorted, sorted_logits, jnp.inf),
            axis=-1, keepdims=True,
        )
        logits_t = jnp.where(logits_t < cutoff, -jnp.inf, logits_t)
    return logits_t


def generate_with(
    fwd_cache: Any,
    init_cache_fn: Any,
    params: Params,
    prompt: jax.Array,
    cfg: Any,
    max_new_tokens: int,
    temperature: float,
    key: Optional[jax.Array],
    top_k: Optional[int] = None,
    top_p: Optional[float] = None,
    eos_id: Optional[int] = None,
) -> jax.Array:
    """Family-agnostic generation core (llama and moe share it): prefill
    via one cached forward, then ``lax.scan`` decode steps over a
    static-shape cache.  ``fwd_cache(params, tokens, cfg, cache, pos,
    last_only=...) -> (logits, cache)`` and ``init_cache_fn(cfg, B, L)``
    are the family's decode hooks.  ``top_k``/``top_p`` filter the
    sampling distribution (:func:`_sample_filter`); both require
    ``temperature > 0``.

    ``eos_id``: once a row emits it, every later position in that row
    is ``eos_id`` too (the scan's shapes are static so the compute
    still runs; finished rows are masked, the standard TPU serving
    semantics — the caller truncates at the first EOS)."""
    if (top_k is not None or top_p is not None) and temperature <= 0.0:
        raise ValueError(
            "top_k/top_p filter the SAMPLING distribution — they have "
            "no effect on greedy decoding; pass temperature > 0"
        )
    # Validate filter values eagerly (static Python ints), before any
    # prefill compute or scan tracing is spent.
    if top_k is not None and top_k < 1:
        raise ValueError(f"top_k must be >= 1, got {top_k}")
    if top_p is not None and not 0.0 < top_p <= 1.0:
        raise ValueError(f"top_p must be in (0, 1], got {top_p}")
    if eos_id is not None and not 0 <= eos_id < cfg.vocab:
        # An out-of-range id can never be emitted, silently disabling
        # EOS handling (tokenizer/model vocab mismatch) — fail loudly.
        raise ValueError(
            f"eos_id {eos_id} outside the model vocab [0, {cfg.vocab})"
        )
    B, P_len = prompt.shape
    if max_new_tokens <= 0:
        return prompt
    total = P_len + max_new_tokens
    cache = init_cache_fn(cfg, B, total)
    logits, cache = fwd_cache(
        params, prompt, cfg, cache, jnp.int32(0), last_only=True
    )
    last = logits[:, -1]
    if key is None:
        if temperature > 0.0:
            raise ValueError(
                "temperature sampling requires an explicit PRNG key: "
                "pass key=jax.random.key(seed) (every call with the "
                "default key would sample the SAME tokens)"
            )
        key = jax.random.key(0)  # greedy path: keys are structural only

    def pick(logits_t, k):
        if temperature <= 0.0:
            return jnp.argmax(logits_t, axis=-1).astype(prompt.dtype)
        # Temperature first, then filters — top-p measures mass of the
        # TEMPERED distribution (the conventional ordering).
        filtered = _sample_filter(logits_t / temperature, top_k, top_p)
        return jax.random.categorical(k, filtered, axis=-1).astype(
            prompt.dtype
        )

    def step(carry, k):
        cache, last_logits, pos, done = carry
        tok = pick(last_logits, k)
        if eos_id is not None:
            tok = jnp.where(done, jnp.asarray(eos_id, tok.dtype), tok)
            done = done | (tok == eos_id)
        logits_t, cache = fwd_cache(
            params, tok[:, None], cfg, cache, pos
        )
        return (cache, logits_t[:, 0], pos + 1, done), tok

    # Scan max_new_tokens - 1 steps; the final token needs no forward of
    # its own (its logits would be discarded).
    keys = jax.random.split(key, max_new_tokens)
    done0 = jnp.zeros((B,), bool)
    (_, last, _, done), new_tokens = jax.lax.scan(
        step, (cache, last, jnp.int32(P_len), done0), keys[:-1],
    )
    final = pick(last, keys[-1])
    if eos_id is not None:
        final = jnp.where(done, jnp.asarray(eos_id, final.dtype), final)
    new = jnp.concatenate(
        [new_tokens.swapaxes(0, 1), final[:, None]], axis=1
    ) if max_new_tokens > 1 else final[:, None]
    return jnp.concatenate([prompt, new], axis=1)


def next_token_loss(
    params: Params,
    tokens: jax.Array,
    cfg: LlamaConfig,
    mesh: Optional[Any] = None,
    segment_ids: Optional[jax.Array] = None,
) -> jax.Array:
    """Mean cross-entropy of next-token prediction over (B, T) tokens.

    Targets are ``roll(tokens, -1)`` with the final position masked rather
    than a ``[:-1]`` slice — the sequence axis keeps its full length, so it
    stays evenly shardable over ``sp``.

    With ``segment_ids`` (packed batches), attention is segment-masked
    and the loss additionally drops positions whose next token belongs to
    a different document (the cross-document boundary predictions).
    """
    logits = forward(params, tokens, cfg, mesh, segment_ids=segment_ids)
    return next_token_cross_entropy(logits, tokens, segment_ids=segment_ids)


# -- pipeline parallelism ----------------------------------------------------


#: ``stage_params(params, n_stages, n_chunks=1)``: an :func:`init_params`
#: pytree regrouped for pipeline parallelism (stacked stages with leading
#: ``(S, L/S)`` axes, ``(S, V, L/(S·V))`` for 1f1b; embedding, final norm
#: and lm head stay outside the pipe).
stage_params = _decoder.stage_params


def pp_param_specs(
    cfg: LlamaConfig, axis: str = "pp", n_chunks: int = 1
) -> Params:
    """PartitionSpecs for the :func:`stage_params` layout: ``pp`` shards
    the stage axis, the trailing axes keep the Megatron fsdp/tp layout of
    :func:`param_specs`."""
    return _decoder.pp_param_specs(param_specs(cfg), axis, n_chunks)


def _layer_apply_tp_local(
    layer: Params,
    x: jax.Array,
    cfg: LlamaConfig,
    positions: jax.Array,
    tp_axis: str,
    n_tp: int,
) -> jax.Array:
    """One transformer block on LOCAL tensor-parallel weight shards
    (Megatron layout, explicit collectives) — the tp-resident pipeline
    stage body.  ``wq/wk/wv`` are column-sharded (each device computes
    its ``n_heads/tp`` heads end-to-end), ``wo`` row-sharded (partial
    residual contributions summed with ``psum``); ``w_gate/w_up``
    column-sharded (``d_ff/tp`` hidden), ``w_down`` row-sharded
    (``psum``).  Two psums per layer — the classic Megatron count —
    riding ICI inside the pipeline's shard_map.
    """
    from jax import lax

    from ddl_tpu.parallel.ring_attention import attention

    B, T = x.shape[:2]
    dt = x.dtype
    lh = cfg.n_heads // n_tp  # local query heads
    lkv = cfg.n_kv_heads // n_tp  # local KV heads
    h = _decoder.rms_norm(x, layer["attn_norm"], cfg.norm_eps)
    # The SAME projection/rope/SwiGLU helpers as the plain block — only
    # the head counts and the two completing psums differ, so tp-resident
    # numerics cannot drift from forward's.
    q, k, v = _attn_qkv(
        layer, h, cfg, positions, n_heads=lh, n_kv_heads=lkv
    )
    attn = attention(
        q, k, v, mesh=None, impl=cfg.attn_impl, causal=True,
        kv_repeat=lh // lkv,
    )
    # Row-sharded wo: each device's head block contributes a PARTIAL
    # output projection; the psum completes the sum over heads.
    x = x + lax.psum(
        attn.reshape(B, T, -1) @ layer["wo"].astype(dt), tp_axis
    )
    h = _decoder.rms_norm(x, layer["mlp_norm"], cfg.norm_eps)
    return x + lax.psum(_decoder.swiglu(layer, h), tp_axis)


#: Per-stage inner PartitionSpecs for tp-RESIDENT pipeline stages
#: (leading per-stage layer axis unsharded; Megatron column/row layout
#: on the weight dims).  Only ``tp`` appears: fsdp still gathers at the
#: shard_map boundary (compute needs full d_model rows), it shards
#: at-rest storage only.
_TP_STAGE_SPECS = {
    "attn_norm": P(None, None),
    "wq": P(None, None, "tp"),
    "wk": P(None, None, "tp"),
    "wv": P(None, None, "tp"),
    "wo": P(None, "tp", None),
    "mlp_norm": P(None, None),
    "w_gate": P(None, None, "tp"),
    "w_up": P(None, None, "tp"),
    "w_down": P(None, "tp", None),
}


def forward_pp(
    params: Params,
    tokens: jax.Array,
    cfg: LlamaConfig,
    mesh: Any,
    n_microbatches: int,
    axis: str = "pp",
    schedule: str = "gpipe",
    n_chunks: "int | None" = None,
) -> jax.Array:
    """Next-token logits with the transformer blocks pipelined over the
    mesh's ``axis`` (microbatch schedule per ``schedule`` — gpipe, or
    the lower-bubble interleaved 1f1b with ``stage_params(...,
    n_chunks=)`` weights; :func:`ddl_tpu.parallel.pipeline_apply`).

    ``params`` is the :func:`stage_params` layout.  Each pipeline stage
    scans its ``L/S`` layers over the residual stream; attention inside a
    stage is single-device (dense or flash) — sequence parallelism does
    not compose with pp in this schedule (``segment_ids`` likewise
    unsupported here; use :func:`forward` for packed batches).

    Working-memory model (the honest cost account): each device holds
    its own stage's weights for the whole step, plus one microbatch's
    activations times the live scan depth.  With a ``tp`` axis in the
    mesh (and head counts divisible by it), stages run TENSOR-PARALLEL
    RESIDENT: weight shards stay local inside the shard_map and each
    layer completes with two explicit psums over tp (Megatron), so peak
    per-device weight memory is ``params/(S·tp)``.  Without tp it is
    ``params/S`` — fsdp on the trailing axes shards at-rest STORAGE
    only (compute needs full d_model rows, so it gathers at the
    shard_map boundary once per step).  At 8B, S=4: ~4 GiB bf16
    resident per device; S=4 × tp=4: ~1 GiB.
    """
    B, T = tokens.shape
    dt = cfg.dtype
    positions = jnp.arange(T)
    with scope("ddl.embed"):
        x = params["embed"].astype(dt)[tokens]

    n_tp = (
        mesh.shape["tp"]
        if "tp" in mesh.axis_names
        and axis in mesh.axis_names
        and mesh.shape.get(axis, 1) > 1  # pp=1 takes the sequential
        # fallback, which runs stage_fn outside shard_map where the
        # tp psums cannot resolve
        else 1
    )
    tp_resident = (
        n_tp > 1
        and cfg.n_heads % n_tp == 0
        and cfg.n_kv_heads % n_tp == 0
        and cfg.d_ff % n_tp == 0
    )

    if tp_resident:
        def one_layer(x: jax.Array, layer: Params) -> jax.Array:
            return _layer_apply_tp_local(
                layer, x, cfg, positions, "tp", n_tp
            )
    else:
        def one_layer(x: jax.Array, layer: Params) -> jax.Array:
            return _layer_apply(layer, x, cfg, positions, mesh=None)

    layer_fn = _remat.wrap(one_layer, cfg.remat)

    def stage_fn(stage: Params, h: jax.Array) -> jax.Array:
        out, _ = jax.lax.scan(
            lambda c, lyr: (layer_fn(c, lyr), None), h, stage
        )
        return out

    from ddl_tpu.parallel.pipeline import pipeline_apply

    x = pipeline_apply(
        params["stages"], x, stage_fn, mesh, n_microbatches, axis=axis,
        stage_param_specs=_TP_STAGE_SPECS if tp_resident else None,
        schedule=schedule, n_chunks=n_chunks,
    )
    return _decoder.lm_head(params, x, cfg)


def next_token_loss_pp(
    params: Params,
    tokens: jax.Array,
    cfg: LlamaConfig,
    mesh: Any,
    n_microbatches: int,
    axis: str = "pp",
    schedule: str = "gpipe",
    n_chunks: "int | None" = None,
) -> jax.Array:
    """:func:`next_token_loss` over the pipelined forward — the loss to
    hand :func:`ddl_tpu.parallel.train.make_train_step` (or the Trainer)
    for a pp-axis mesh; backward runs the reverse schedule through
    ``jax.grad`` automatically."""
    logits = forward_pp(
        params, tokens, cfg, mesh, n_microbatches, axis=axis,
        schedule=schedule, n_chunks=n_chunks,
    )
    return next_token_cross_entropy(logits, tokens)
