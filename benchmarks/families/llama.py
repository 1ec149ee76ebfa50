"""Family adapter: Llama-shaped decoders through ``ddl_tpu/models/llama.py``
(pre-RMSNorm, GQA, RoPE, SwiGLU, no biases, untied head)."""

from __future__ import annotations

from benchmarks.lib import flops

#: This family's rate metric (its unit is BENCHMARK.json's).
RATE_METRIC = "tokens_per_s"


def sizes(c: dict, mix: dict) -> dict:
    """What the traffic generator needs to know of the model."""
    return {"seq": mix["seq"], "vocab": c["vocab_size"]}


def samples_per_row(c: dict, mix: dict) -> int:
    return mix["seq"]


def flops_per_sample(c: dict, mix: dict) -> float:
    return flops.decoder_flops_per_token(c, mix["seq"])


def model_config(c: dict, mix: dict):
    import jax.numpy as jnp

    from ddl_tpu.config import TrainConfig
    from ddl_tpu.models import llama

    t = c["training"]
    if c["hidden_size"] != c["num_attention_heads"] * c["head_dim"]:
        raise ValueError("models/llama.py derives head_dim as hidden/heads")
    return TrainConfig(remat=t["remat"]).model_config(llama.LlamaConfig(
        vocab=c["vocab_size"], d_model=c["hidden_size"],
        n_layers=c["num_hidden_layers"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        max_seq=mix["seq"], rope_theta=c["rope_theta"],
        norm_eps=c["rms_norm_eps"], param_dtype=jnp.dtype(t["param_dtype"]),
        attn_impl=t["attn_impl"],
    ))


def init_params(cfg, key):
    from ddl_tpu.models import llama

    return llama.init_params(cfg, key)


def param_specs(cfg):
    from ddl_tpu.models import llama

    return llama.param_specs(cfg)


def loss_fn(cfg, mesh):
    """The train loss over the loader's column tuple.  One chip: plain
    attention; a mesh: batch-sharded local attention over it."""
    from ddl_tpu.models import llama

    attn_mesh = mesh if mesh.devices.size > 1 else None
    return lambda p, b: llama.next_token_loss(p, b[0], cfg, mesh=attn_mesh)
