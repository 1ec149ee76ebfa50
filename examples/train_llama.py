"""End-to-end LM pretraining example: token stream → Trainer → flash Llama.

The BASELINE configs[3-4] shape ("C4-style token feed", "Llama pretrain
loop fed solely by the ddl TPU backend") at laptop scale: a synthetic flat
token file is served by :class:`TokenStreamProducer` workers, batches
stream into HBM with prefetch, and the GSPMD train step runs the
Llama-style decoder with the Pallas flash-attention kernel on TPU (dense
XLA attention elsewhere).  Everything — topology, batch geometry, output
mode — comes from one :class:`LoaderConfig`.

Run:

    python examples/train_llama.py             # THREAD mode
    python examples/train_llama.py process     # spawned producer processes
    DDL_TPU_N_PRODUCERS=4 python examples/train_llama.py process

    # ZeRO-1 optimizer-state sharding over dp (and int8 grad comm) ride
    # the standard TrainConfig env — identical losses, ~dp× less
    # optimizer HBM per replica (ddl_tpu/parallel/optimizer.py):
    DDL_TPU_TRAIN_OPTIMIZER_SHARDING=zero1 python examples/train_llama.py
    DDL_TPU_TRAIN_OPTIMIZER_SHARDING=zero1 DDL_TPU_TRAIN_GRAD_COMM=int8 \
        python examples/train_llama.py

Exit 0 with finite, decreasing loss is the pass criterion.
"""

from __future__ import annotations

import os
import sys
import tempfile

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from _common import configure  # noqa: E402

configure()

SEQ_LEN = 128
WINDOW_ROWS = 32
VOCAB = 512


N_TOKENS = 200_000


def make_token_file(path: str) -> None:
    """A synthetic 'corpus': structured token stream (learnable bigrams).

    Written atomically (temp + rename) so an interrupted run never leaves
    a truncated file that a later run would silently train on.
    """
    rng = np.random.default_rng(0)
    # Each token mostly determines its successor — a model that learns
    # anything drives the loss well below log(VOCAB).
    succ = rng.integers(0, VOCAB, VOCAB)
    toks = np.empty(N_TOKENS, np.int32)
    toks[0] = 1
    noise = rng.random(N_TOKENS) < 0.1
    randoms = rng.integers(0, VOCAB, N_TOKENS)
    for i in range(1, N_TOKENS):
        toks[i] = randoms[i] if noise[i] else succ[toks[i - 1]]
    tmp = f"{path}.tmp.{os.getpid()}"
    toks.tofile(tmp)
    os.replace(tmp, path)


def _token_file_valid(path: str) -> bool:
    return (
        os.path.exists(path)
        and os.path.getsize(path) == N_TOKENS * 4
        and int(np.memmap(path, np.int32, mode="r").max()) < VOCAB
    )


def main(mode: str = "thread") -> int:
    import jax
    import optax
    from jax.sharding import PartitionSpec as P

    from ddl_tpu.config import LoaderConfig, TrainConfig
    from ddl_tpu.models import llama
    from ddl_tpu.parallel.mesh import make_mesh
    from ddl_tpu.readers import TokenStreamProducer
    from ddl_tpu.trainer import Trainer

    token_file = os.path.join(tempfile.gettempdir(), "ddl_tpu_tokens.bin")
    if not _token_file_valid(token_file):
        make_token_file(token_file)

    cfg = LoaderConfig(
        batch_size=8,
        n_epochs=6,
        n_producers=int(os.environ.get("DDL_TPU_N_PRODUCERS", "2")),
        mode=mode,
        nslots=2,
        output="jax",
        # The recommended TPU path: one zero-copy transfer per window, one
        # jitted scan of optimizer steps per window (numerically identical
        # to per-batch fit — tests/test_trainer.py proves equivalence).
        window_stream=True,
    )
    model = llama.LlamaConfig(
        vocab=VOCAB, d_model=128, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=256, max_seq=SEQ_LEN,
    )
    mesh = make_mesh({"dp": len(jax.local_devices())})
    # TrainConfig.load() picks up DDL_TPU_TRAIN_* from the env —
    # optimizer_sharding=zero1 shards adamw's moments over dp (inert at
    # dp=1; the loss trajectory is bit-identical either way).
    train_config = TrainConfig.load()
    trainer = Trainer(
        loss_fn=lambda p, b: llama.next_token_loss(p, b[0], model),
        optimizer=optax.adamw(3e-3),
        mesh=mesh,
        param_specs=llama.param_specs(model),
        init_params=llama.init_params(model, jax.random.key(0)),
        batch_spec=P(("dp",)),
        train_config=train_config,
    )
    result = trainer.fit(
        TokenStreamProducer(token_file, SEQ_LEN, WINDOW_ROWS),
        config=cfg,
    )
    print("epoch losses:", [round(l, 4) for l in result.losses])

    # Inference on the trained weights: greedy continuation via the exact
    # KV-cache decode path (one-forward prefill + scanned decode steps).
    prompt = jax.numpy.asarray(
        np.memmap(token_file, np.int32, mode="r")[:16][None]
    )
    continued = llama.generate(
        result.state.params, prompt, model, max_new_tokens=16
    )
    print("generated continuation:", np.asarray(continued[0, 16:]).tolist())

    ok = (
        all(np.isfinite(l) for l in result.losses)
        and result.losses[-1] < result.losses[0]
        and continued.shape == (1, 32)
        and int(continued.max()) < VOCAB
    )
    print("PASS" if ok else "FAIL", "- final loss", result.losses[-1])
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1] if len(sys.argv) > 1 else "thread"))
