"""Serving example: train briefly, then batched KV-cache generation.

The reference stops at data loading (no model code at all — SURVEY §0);
this example shows the inference side of the rebuilt stack: a tiny
llama is fitted on a repeating token pattern, then ``generate`` serves
batched completions three ways — greedy, temperature sampling, and
nucleus (top-p) sampling with a top-k cap — all through the in-place
stacked KV cache (prefill in one cached forward, scanned decode steps).

Run:

    python examples/generate.py

Exit 0 with a learned continuation (greedy decode reproduces the
training pattern) is the pass criterion.
"""

from __future__ import annotations

import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from _common import configure  # noqa: E402

configure()

VOCAB = 64
PERIOD = 7
SEQ = 32


def main() -> int:
    import jax
    import jax.numpy as jnp
    import optax

    from ddl_tpu.models import llama
    from ddl_tpu.parallel.mesh import make_mesh
    from ddl_tpu.parallel.train import make_train_step

    cfg = llama.LlamaConfig(
        vocab=VOCAB, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=128, max_seq=64, dtype=jnp.float32,
    )
    mesh = make_mesh({"dp": 1}, devices=jax.local_devices()[:1])
    init_fn, step_fn = make_train_step(
        lambda p, b: llama.next_token_loss(p, b, cfg),
        optax.adamw(1e-2), mesh, llama.param_specs(cfg),
    )
    state = init_fn(llama.init_params(cfg, jax.random.key(0)))

    # A deterministic repeating pattern the model can memorise fast.
    tokens = np.tile(np.arange(SEQ, dtype=np.int32) % PERIOD, (8, 1))
    loss = None
    for _ in range(60):
        state, loss = step_fn(state, tokens)
    print(f"train loss after 60 steps: {float(loss):.4f}")

    prompt = jnp.asarray(tokens[:4, :10])

    greedy = llama.generate(state.params, prompt, cfg, max_new_tokens=12)
    continuation = np.asarray(greedy)[:, 10:]
    expected = np.tile(np.arange(10, 22, dtype=np.int32) % PERIOD, (4, 1))
    ok = (continuation == expected).mean()
    print(f"greedy continuation matches pattern: {ok:.0%}")

    sampled = llama.generate(
        state.params, prompt, cfg, max_new_tokens=12,
        temperature=0.8, key=jax.random.key(42),
    )
    nucleus = llama.generate(
        state.params, prompt, cfg, max_new_tokens=12,
        temperature=0.8, key=jax.random.key(43), top_p=0.9, top_k=8,
    )
    print("sampled   :", np.asarray(sampled)[0, 10:].tolist())
    print("nucleus   :", np.asarray(nucleus)[0, 10:].tolist())
    for out in (sampled, nucleus):
        arr = np.asarray(out)
        assert arr.shape == (4, 22) and ((arr >= 0) & (arr < VOCAB)).all()

    if ok < 0.9:
        print("FAIL: model did not learn the pattern")
        return 1
    print("OK")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
