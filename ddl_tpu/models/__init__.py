"""Model zoo for the consumer-side training loops the loader feeds."""

from ddl_tpu.models import afmoe, llama, moe, pointnet, vit

__all__ = ["afmoe", "llama", "moe", "pointnet", "vit"]
