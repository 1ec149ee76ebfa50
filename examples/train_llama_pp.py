"""Pipeline-parallel LM pretraining: token stream → Trainer → staged Llama.

The scale-out shape for models too big for one chip's HBM: the decoder
blocks regroup into ``pp`` pipeline stages (GPipe microbatch schedule
riding ``ppermute`` over ICI), each stage holding only its own layers —
and when the mesh also carries a ``tp`` axis, stages run TENSOR-PARALLEL
RESIDENT (local Megatron weight shards, two psums per layer), cutting
per-device weight working memory to params/(S·tp).  The data pipeline is
unchanged: the same token-stream producers, window rings, and
zero-copy window streaming feed the pipelined step.

Run:

    python examples/train_llama_pp.py            # pp=2 × dp over the rest
    python examples/train_llama_pp.py pp_tp      # pp=2 × tp=2 × dp (8 devices)
    python examples/train_llama_pp.py pp_1f1b    # interleaved 1F1B schedule
                                                 # (2 chunks/device: bubble
                                                 # 0.111 vs gpipe's 0.2 at
                                                 # pp=2, M=4)

Exit 0 with finite, decreasing loss is the pass criterion.
"""

from __future__ import annotations

import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from _common import configure  # noqa: E402

# Pipeline stages need multiple devices; default the CPU sim to 8.
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
configure()

from train_llama import (  # noqa: E402 - shared synthetic corpus
    SEQ_LEN,
    VOCAB,
    WINDOW_ROWS,
    _token_file_valid,
    make_token_file,
)


def main(layout: str = "pp") -> int:
    import tempfile

    import jax
    import optax
    from jax.sharding import PartitionSpec as P

    from ddl_tpu.config import LoaderConfig, TrainConfig
    from ddl_tpu.models import llama
    from ddl_tpu.parallel import bubble_fraction
    from ddl_tpu.parallel.mesh import make_mesh
    from ddl_tpu.readers import TokenStreamProducer
    from ddl_tpu.trainer import Trainer

    token_file = os.path.join(tempfile.gettempdir(), "ddl_tpu_tokens.bin")
    if not _token_file_valid(token_file):
        make_token_file(token_file)

    n_dev = len(jax.devices())
    n_micro = 4
    # The training hot-path knobs ride TrainConfig (env-overridable as
    # DDL_TPU_TRAIN_*): the pp_1f1b layout selects the interleaved
    # schedule, everything else stays gpipe.
    tc = TrainConfig(
        schedule="1f1b" if layout == "pp_1f1b" else "gpipe",
        pp_chunks=2 if layout == "pp_1f1b" else 0,
        n_microbatches=n_micro,
    )
    if layout == "pp_tp":
        if n_dev % 4:
            raise SystemExit(f"pp_tp needs a multiple of 4 devices, have {n_dev}")
        axes = {"pp": 2, "tp": 2, "dp": n_dev // 4}
    else:
        if n_dev % 2:
            raise SystemExit(f"pp needs an even device count, have {n_dev}")
        axes = {"pp": 2, "dp": n_dev // 2}
    mesh = make_mesh(axes)
    n_chunks = tc.pp_chunks or 1
    print(f"mesh {axes}, {n_micro} microbatches, schedule={tc.schedule}, "
          f"bubble={bubble_fraction(axes['pp'], n_micro, schedule=tc.schedule, n_chunks=tc.pp_chunks or None):.3f}")

    model = llama.LlamaConfig(
        vocab=VOCAB, d_model=128, n_layers=4, n_heads=4, n_kv_heads=2,
        d_ff=256, max_seq=SEQ_LEN,
    )
    cfg = LoaderConfig(
        batch_size=8,
        n_epochs=6,
        n_producers=2,
        mode="thread",
        nslots=2,
        output="jax",
        window_stream=True,
    )
    trainer = Trainer(
        loss_fn=lambda p, b: llama.next_token_loss_pp(
            p, b[0], model, mesh, n_microbatches=n_micro,
            **tc.pipeline_kwargs(),
        ),
        optimizer=optax.adamw(3e-3),
        mesh=mesh,
        param_specs=llama.pp_param_specs(model, n_chunks=n_chunks),
        init_params=llama.stage_params(
            llama.init_params(model, jax.random.key(0)), axes["pp"],
            n_chunks=n_chunks,
        ),
        batch_spec=P(("dp",)),
        train_config=tc,
    )
    result = trainer.fit(
        TokenStreamProducer(token_file, SEQ_LEN, WINDOW_ROWS),
        config=cfg,
    )
    print("epoch losses:", [round(l, 4) for l in result.losses])

    ok = (
        all(np.isfinite(l) for l in result.losses)
        and result.losses[-1] < result.losses[0]
    )
    print("OK" if ok else "FAILED")
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1] if len(sys.argv) > 1 else "pp"))
