"""End-to-end image classification: WebDataset tar shards → Trainer → ViT.

The ImageNet-config story (BASELINE configs[1-2]) at laptop scale:
synthetic tar shards in the WebDataset layout (``<key>.png`` +
``<key>.cls``) are streamed by :class:`WebDatasetProducer` workers and a
vision transformer trains on the loader's ``(pixels, label)`` columns
through the GSPMD step — flash attention on TPU, dense elsewhere.

Run:

    python examples/train_vit.py             # THREAD mode
    python examples/train_vit.py process     # spawned producer processes

Exit 0 with finite, decreasing loss is the pass criterion.
"""

from __future__ import annotations

import io
import os
import sys
import tarfile
import tempfile

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from _common import configure  # noqa: E402

configure()

IMAGE_SIZE = 16
N_CLASSES = 4
SHARDS = 2
SAMPLES_PER_SHARD = 32


def make_shards(dirpath: str) -> str:
    """Synthetic labeled shards: class k images are brightness-banded, so
    the task is learnable."""
    try:
        from PIL import Image
    except ImportError as e:
        raise SystemExit(
            "this example needs Pillow (pip install 'ddl-tpu[image]')"
        ) from e

    rng = np.random.default_rng(0)
    os.makedirs(dirpath, exist_ok=True)
    for s in range(SHARDS):
        path = os.path.join(dirpath, f"train-{s:04d}.tar")
        tmp = f"{path}.tmp.{os.getpid()}"
        with tarfile.open(tmp, "w") as tf:
            for i in range(SAMPLES_PER_SHARD):
                label = (s * SAMPLES_PER_SHARD + i) % N_CLASSES
                base = 40 + label * 50
                arr = np.clip(
                    rng.normal(base, 12, (IMAGE_SIZE, IMAGE_SIZE, 3)),
                    0, 255,
                ).astype(np.uint8)
                buf = io.BytesIO()
                Image.fromarray(arr).save(buf, format="PNG")
                for name, data in (
                    (f"{s}-{i}.png", buf.getvalue()),
                    (f"{s}-{i}.cls", str(label).encode()),
                ):
                    info = tarfile.TarInfo(name)
                    info.size = len(data)
                    tf.addfile(info, io.BytesIO(data))
        os.replace(tmp, path)
    return os.path.join(dirpath, "train-*.tar")


def main(mode: str = "thread") -> int:
    import jax
    import optax
    from jax.sharding import PartitionSpec as P

    from ddl_tpu.config import LoaderConfig
    from ddl_tpu.models import vit
    from ddl_tpu.parallel.mesh import make_mesh
    from ddl_tpu.readers import WebDatasetProducer
    from ddl_tpu.trainer import Trainer

    pattern = make_shards(
        os.path.join(tempfile.gettempdir(), "ddl_tpu_wds")
    )
    cfg = LoaderConfig(
        batch_size=8,
        n_epochs=6,
        n_producers=2,
        mode=mode,
        nslots=2,
        output="jax",
    )
    model = vit.ViTConfig(
        image_size=IMAGE_SIZE, patch_size=4, d_model=64, n_layers=2,
        n_heads=4, d_ff=128, n_classes=N_CLASSES,
    )
    mesh = make_mesh({"dp": len(jax.local_devices())})
    trainer = Trainer(
        loss_fn=lambda p, b: vit.classification_loss(p, b, model),
        optimizer=optax.adamw(1e-3),
        mesh=mesh,
        param_specs=vit.param_specs(model),
        init_params=vit.init_params(model, jax.random.key(0)),
        batch_spec=P(("dp",)),
    )
    result = trainer.fit(
        WebDatasetProducer(pattern, image_size=IMAGE_SIZE, window_rows=16),
        config=cfg,
    )
    print("epoch losses:", [round(l, 4) for l in result.losses])
    ok = (
        all(np.isfinite(l) for l in result.losses)
        and result.losses[-1] < result.losses[0]
    )
    print("PASS" if ok else "FAIL", "- final loss", result.losses[-1])
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1] if len(sys.argv) > 1 else "thread"))
