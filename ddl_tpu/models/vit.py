"""Vision Transformer — the image-classification model family.

Closes the loop on the ImageNet/WebDataset ingest configs (BASELINE
configs[1-2]): :class:`ddl_tpu.readers.WebDatasetProducer` serves
``[pixels..., label]`` rows and this model trains on them through the
same GSPMD train-step factory and attention dispatcher as the language
models (non-causal attention — flash on TPU, dense elsewhere, ring
attention under an ``sp`` mesh axis for very long patch sequences).

TPU-first like ``models/llama.py``: pure init/apply over a params pytree,
bf16 activations with fp32 norm accumulations, convolution-free patch
embedding (reshape + one matmul — MXU-native), learned position
embeddings, mean-pooled head.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ddl_tpu.ops.naming import scope

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class ViTConfig:
    image_size: int = 32
    patch_size: int = 4
    n_channels: int = 3
    d_model: int = 128
    n_layers: int = 4
    n_heads: int = 4
    d_ff: int = 512
    n_classes: int = 10
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    attn_impl: str = "auto"  # "auto" | "flash" | "dense"

    def __post_init__(self) -> None:
        if self.image_size % self.patch_size:
            raise ValueError(
                f"patch_size {self.patch_size} must divide image_size "
                f"{self.image_size}"
            )
        if self.attn_impl not in ("auto", "flash", "dense"):
            raise ValueError(f"bad attn_impl {self.attn_impl!r}")

    @property
    def n_patches(self) -> int:
        return (self.image_size // self.patch_size) ** 2

    @property
    def patch_dim(self) -> int:
        return self.patch_size * self.patch_size * self.n_channels

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads


def init_params(cfg: ViTConfig, key: jax.Array) -> Params:
    keys = iter(jax.random.split(key, 4 + cfg.n_layers * 7))

    def dense(k, fan_in, shape):
        return jax.random.normal(k, shape, jnp.float32) / jnp.sqrt(fan_in)

    d = cfg.d_model
    layers = []
    for _ in range(cfg.n_layers):
        layers.append(
            {
                "attn_norm": jnp.ones((d,), jnp.float32),
                "wq": dense(next(keys), d, (d, d)),
                "wk": dense(next(keys), d, (d, d)),
                "wv": dense(next(keys), d, (d, d)),
                "wo": dense(next(keys), d, (d, d)),
                "mlp_norm": jnp.ones((d,), jnp.float32),
                "w_up": dense(next(keys), d, (d, cfg.d_ff)),
                "w_down": dense(next(keys), cfg.d_ff, (cfg.d_ff, d)),
            }
        )
    return {
        "patch_embed": dense(next(keys), cfg.patch_dim, (cfg.patch_dim, d)),
        "pos_embed": 0.02
        * jax.random.normal(next(keys), (cfg.n_patches, d), jnp.float32),
        "layers": layers,
        "final_norm": jnp.ones((d,), jnp.float32),
        "head": dense(next(keys), d, (d, cfg.n_classes)),
    }


def param_shapes(cfg: ViTConfig) -> Params:
    """Abstract params pytree via ``eval_shape`` — the optimizer HBM
    accounting input (``parallel.optimizer.hbm_accounting``)."""
    return jax.eval_shape(lambda: init_params(cfg, jax.random.key(0)))


def param_specs(cfg: ViTConfig) -> Params:
    """fsdp shards the model axis, tp shards heads/ffn (Megatron layout)."""
    layer = {
        "attn_norm": P(None),
        "wq": P("fsdp", "tp"),
        "wk": P("fsdp", "tp"),
        "wv": P("fsdp", "tp"),
        "wo": P("tp", "fsdp"),
        "mlp_norm": P(None),
        "w_up": P("fsdp", "tp"),
        "w_down": P("tp", "fsdp"),
    }
    return {
        "patch_embed": P(None, "fsdp"),
        "pos_embed": P(None, "fsdp"),
        "layers": [dict(layer) for _ in range(cfg.n_layers)],
        "final_norm": P(None),
        "head": P("fsdp", None),
    }


def _rms_norm(x: jax.Array, gain: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * scale * gain).astype(x.dtype)


def patchify(images: jax.Array, cfg: ViTConfig) -> jax.Array:
    """(B, H, W, C) → (B, n_patches, patch_dim) by pure reshapes."""
    B = images.shape[0]
    p = cfg.patch_size
    g = cfg.image_size // p
    x = images.reshape(B, g, p, g, p, cfg.n_channels)
    x = x.transpose(0, 1, 3, 2, 4, 5)  # (B, g, g, p, p, C)
    return x.reshape(B, g * g, cfg.patch_dim)


def _layer_apply(
    layer: Params,
    x: jax.Array,
    cfg: ViTConfig,
    mesh: Optional[Any] = None,
) -> jax.Array:
    """One encoder block on the residual stream — shared by
    :func:`forward` and the pipelined :func:`forward_pp` (one body, so
    the two paths cannot diverge)."""
    from ddl_tpu.parallel.ring_attention import attention

    B, T = x.shape[:2]
    dt = x.dtype
    with scope("ddl.attn"):
        h = _rms_norm(x, layer["attn_norm"], cfg.norm_eps)
        q = (h @ layer["wq"].astype(dt)).reshape(B, T, cfg.n_heads,
                                                 cfg.head_dim)
        k = (h @ layer["wk"].astype(dt)).reshape(B, T, cfg.n_heads,
                                                 cfg.head_dim)
        v = (h @ layer["wv"].astype(dt)).reshape(B, T, cfg.n_heads,
                                                 cfg.head_dim)
        attn = attention(
            q, k, v, mesh=mesh, impl=cfg.attn_impl, causal=False
        )
        x = x + attn.reshape(B, T, -1) @ layer["wo"].astype(dt)

    with scope("ddl.mlp"):
        h = _rms_norm(x, layer["mlp_norm"], cfg.norm_eps)
        return x + jax.nn.gelu(h @ layer["w_up"].astype(dt)) @ layer[
            "w_down"
        ].astype(dt)


def _embed(params: Params, images: jax.Array, cfg: ViTConfig) -> jax.Array:
    """Patchify + project + position-embed (shared by both forwards)."""
    dt = cfg.dtype
    with scope("ddl.patchify"):
        if images.ndim == 2:  # the loader's flattened pixel rows
            images = images.reshape(
                -1, cfg.image_size, cfg.image_size, cfg.n_channels
            )
        x = patchify(images.astype(dt), cfg) @ params["patch_embed"].astype(dt)
        return x + params["pos_embed"].astype(dt)[None]


def _head(params: Params, x: jax.Array, cfg: ViTConfig) -> jax.Array:
    """Final norm + mean pool + classification head (shared)."""
    with scope("ddl.head"):
        x = _rms_norm(x, params["final_norm"], cfg.norm_eps)
        pooled = jnp.mean(x.astype(jnp.float32), axis=1)  # (B, d)
        return pooled @ params["head"]


def forward(
    params: Params,
    images: jax.Array,
    cfg: ViTConfig,
    mesh: Optional[Any] = None,
) -> jax.Array:
    """Class logits (B, n_classes); images (B, H, W, C) or flat
    (B, H*W*C)."""
    x = _embed(params, images, cfg)
    for layer in params["layers"]:
        x = _layer_apply(layer, x, cfg, mesh=mesh)
    return _head(params, x, cfg)


# -- pipeline parallelism ----------------------------------------------------


def stage_params(params: Params, n_stages: int) -> Params:
    """Regroup an :func:`init_params` pytree for pipeline parallelism —
    the same ``(S, L/S)`` stage layout as
    ``models.llama.stage_params`` (shared
    ``parallel.pipeline.stack_layer_stages``); embed and head stay
    outside the pipe."""
    from ddl_tpu.parallel.pipeline import stack_layer_stages

    return {
        "patch_embed": params["patch_embed"],
        "pos_embed": params["pos_embed"],
        "stages": stack_layer_stages(params["layers"], n_stages),
        "final_norm": params["final_norm"],
        "head": params["head"],
    }


def pp_param_specs(cfg: ViTConfig, axis: str = "pp") -> Params:
    """PartitionSpecs for the :func:`stage_params` layout."""
    from ddl_tpu.parallel.pipeline import stage_spec_tree

    return {
        "patch_embed": P(None, "fsdp"),
        "pos_embed": P(None, "fsdp"),
        "stages": stage_spec_tree(param_specs(cfg)["layers"][0], axis),
        "final_norm": P(None),
        "head": P("fsdp", None),
    }


def forward_pp(
    params: Params,
    images: jax.Array,
    cfg: ViTConfig,
    mesh: Any,
    n_microbatches: int,
    axis: str = "pp",
) -> jax.Array:
    """Class logits with the encoder blocks pipelined over ``axis``
    (GPipe schedule) — the image-family twin of
    ``models.llama.forward_pp``; attention inside a stage is
    single-device."""
    from ddl_tpu.parallel.pipeline import pipeline_apply

    x = _embed(params, images, cfg)

    def stage_fn(stage: Params, h: jax.Array) -> jax.Array:
        out, _ = jax.lax.scan(
            lambda c, lyr: (_layer_apply(lyr, c, cfg), None), h, stage
        )
        return out

    x = pipeline_apply(
        params["stages"], x, stage_fn, mesh, n_microbatches, axis=axis
    )
    return _head(params, x, cfg)


def classification_loss_pp(
    params: Params,
    batch: Any,
    cfg: ViTConfig,
    mesh: Any,
    n_microbatches: int,
    axis: str = "pp",
) -> jax.Array:
    """:func:`classification_loss` over the pipelined forward."""
    from ddl_tpu.models.losses import cross_entropy

    pixels, labels = batch[0], batch[1]
    logits = forward_pp(params, pixels, cfg, mesh, n_microbatches, axis=axis)
    return cross_entropy(logits, labels.reshape(-1))


def classification_loss(
    params: Params,
    batch: Any,
    cfg: ViTConfig,
    mesh: Optional[Any] = None,
) -> jax.Array:
    """Mean cross-entropy over the loader's ``(pixels, label)`` columns."""
    from ddl_tpu.models.losses import cross_entropy

    pixels, labels = batch[0], batch[1]
    logits = forward(params, pixels, cfg, mesh)
    return cross_entropy(logits, labels.reshape(-1))


def accuracy(
    params: Params, batch: Any, cfg: ViTConfig,
    mesh: Optional[Any] = None,
) -> jax.Array:
    pixels, labels = batch[0], batch[1]
    pred = jnp.argmax(forward(params, pixels, cfg, mesh), axis=-1)
    return jnp.mean((pred == labels.reshape(-1).astype(jnp.int32)))
