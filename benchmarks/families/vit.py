"""Family adapter: Vision Transformers through ``ddl_tpu/models/vit.py``."""

from __future__ import annotations

from benchmarks.lib import flops

RATE_METRIC = "images_per_s"


def sizes(c: dict, mix: dict) -> dict:
    pixels = c["image_size"] ** 2 * c["num_channels"]
    return {"row_values": pixels + 1, "n_classes": c["num_labels"]}


def samples_per_row(c: dict, mix: dict) -> int:
    return 1


def flops_per_sample(c: dict, mix: dict) -> float:
    return flops.vit_flops_per_image(c)


def model_config(c: dict, mix: dict):
    from ddl_tpu.models import vit

    return vit.ViTConfig(
        image_size=c["image_size"], patch_size=c["patch_size"],
        n_channels=c["num_channels"], d_model=c["hidden_size"],
        n_layers=c["num_hidden_layers"], n_heads=c["num_attention_heads"],
        d_ff=c["intermediate_size"], n_classes=c["num_labels"],
        attn_impl=c["training"]["attn_impl"],
    )


def init_params(cfg, key):
    from ddl_tpu.models import vit

    return vit.init_params(cfg, key)


def param_specs(cfg):
    from ddl_tpu.models import vit

    return vit.param_specs(cfg)


def loss_fn(cfg, mesh):
    from ddl_tpu.models import vit

    attn_mesh = mesh if mesh.devices.size > 1 else None
    return lambda p, b: vit.classification_loss(p, b, cfg, mesh=attn_mesh)
