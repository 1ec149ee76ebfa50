"""On-chip MoE train-step measurement (single chip, ep=1 expert stack).

The bench's MFU record covers the llama family only; this probe extends
it to the MoE family with the same artifact-hostile method as
``bench._run_train``: all measured steps chained inside one jitted
``make_multistep`` scan (serialized by the params data dependence), the
clock stopped only after a host read-back of the final loss, and the
same plausibility gates (finite loss, 0 < MFU < 1).

MFU counts *model* FLOPs the standard MoE way — attention as dense,
MLP at top-k experts per token plus the router matmul; the capacity-
bounded dispatch/combine einsums are overhead, so they depress MFU
rather than inflate it (honest accounting).

Usage: python tools/probe_moe.py [einsum|ragged|both]

``ragged`` measures the sort-based dropless impl
(``MoeConfig.moe_impl="ragged"``, ``jax.lax.ragged_dot``); ``both``
(default) measures einsum then ragged for the A/B.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def _moe_flops_per_token(cfg, seq: int) -> float:
    """Analytic matmul model-FLOPs per token, fwd+bwd (bwd = 2x fwd):
    the shared attention+lm_head accounting (``bench.
    _attn_lm_head_flops_per_token`` — ONE definition across families)
    plus the MoE MLP term (router + top-k SwiGLU experts)."""
    import bench

    mlp = cfg.n_layers * (
        2 * cfg.d_model * cfg.n_experts  # router
        + cfg.topk * 3 * 2 * cfg.d_model * cfg.d_ff  # top-k experts
    )
    return 3.0 * (bench._attn_lm_head_flops_per_token(cfg, seq) + mlp)


def _probe_cfg(platform: str, impl: str, **overrides):
    """ONE config for the train and decode probes (the README's 'same
    model' claim must not be able to drift between them)."""
    from ddl_tpu.models import moe

    if platform == "tpu":
        base = dict(
            vocab=8192, d_model=2048, n_layers=4, n_heads=16,
            n_kv_heads=8, d_ff=4096, n_experts=8, topk=2, max_seq=2048,
            moe_impl=impl,
        )
    else:
        base = dict(max_seq=256, moe_impl=impl)
    base.update(overrides)
    return moe.MoeConfig(**base)


def run_one(platform: str, impl: str) -> None:
    import bench
    import jax
    import optax

    from ddl_tpu.models import moe
    from ddl_tpu.parallel.mesh import make_mesh
    from ddl_tpu.parallel.train import make_multistep

    cfg = _probe_cfg(platform, impl)
    if platform == "tpu":
        batch, seq, steps = 4, 2048, 12
    else:
        batch, seq, steps = 2, 128, 4

    mesh = make_mesh({"dp": 1}, devices=jax.local_devices()[:1])
    init_fn, multi_fn = make_multistep(
        lambda p, b: moe.next_token_loss(p, b[0], cfg, mesh=None),
        optax.adamw(3e-4), mesh, moe.param_specs(cfg), n_steps=steps,
    )
    rng = np.random.default_rng(0)
    tokens = (rng.integers(0, cfg.vocab, (batch, seq)).astype(np.int32),)

    state = init_fn(moe.init_params(cfg, jax.random.key(0)))
    state, losses = multi_fn(state, tokens)  # compile
    first_loss = float(losses[0])

    def timed():
        nonlocal state
        t0 = time.perf_counter()
        state, ls = multi_fn(state, tokens)
        fl = float(ls[-1])  # host sync inside the timed window
        return (time.perf_counter() - t0) / steps, fl

    dt, final_loss = bench.best_of(2, timed, key=lambda r: r[0])

    tokens_per_step = batch * seq
    flops_per_step = _moe_flops_per_token(cfg, seq) * tokens_per_step
    kind = jax.local_devices()[0].device_kind
    # A CPU run (asked for by name) has no device peak and no MFU.
    peak = bench._peak_flops(kind) if platform == "tpu" else None
    mfu = flops_per_step / dt / peak if peak else None
    if not np.isfinite(final_loss):
        raise RuntimeError(f"non-finite loss {final_loss}")
    if mfu is not None and not (0.0 < mfu < 1.0):
        raise RuntimeError(f"implausible MoE MFU {mfu:.3f} — rejected")
    n_params = sum(
        int(np.prod(np.shape(x))) for x in jax.tree.leaves(state.params)
    )
    print(json.dumps({
        "family": "moe",
        "moe_impl": impl,
        "platform": platform,
        "device_kind": kind,
        "params_billions": round(n_params / 1e9, 3),
        "n_experts": cfg.n_experts,
        "topk": cfg.topk,
        "tokens_per_sec": round(tokens_per_step / dt, 1),
        "step_time_ms": round(dt * 1e3, 2),
        "model_tflops_per_sec": round(flops_per_step / dt / 1e12, 2),
        "mfu": round(mfu, 4) if mfu is not None else None,
        "first_loss": round(first_loss, 4),
        "final_loss": round(final_loss, 4),
    }))


def run_decode(platform: str, impl: str) -> None:
    """Serving-phase MoE: batched greedy generate through the KV-cache
    path, by ``bench._run_decode``'s method — whole program jitted,
    clock stopped by host read-back of the tokens, prefill timed alone
    so decode-only throughput is separated, and per-trial gating inside
    ``best_valid`` (valid vocab ids, positive decode span) so an
    artifact trial can never win selection."""
    import bench
    import jax
    import jax.numpy as jnp

    from ddl_tpu.models import moe

    cfg = _probe_cfg(
        platform, impl,
        **({"param_dtype": jnp.bfloat16} if platform == "tpu" else {}),
    )
    if platform == "tpu":
        batch, prompt_len, new_tokens, trials = 8, 256, 128, 2
    else:
        batch, prompt_len, new_tokens, trials = 2, 16, 8, 1

    params = moe.init_params(cfg, jax.random.key(0))
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(
        rng.integers(0, cfg.vocab, (batch, prompt_len)).astype(np.int32)
    )

    short_tokens = max(1, new_tokens // 2)

    @jax.jit
    def gen(p, toks):
        return moe.generate(p, toks, cfg, max_new_tokens=new_tokens)

    @jax.jit
    def gen_short(p, toks):
        return moe.generate(p, toks, cfg, max_new_tokens=short_tokens)

    np.asarray(gen(params, prompt))  # compile + warm
    np.asarray(gen_short(params, prompt))
    steps = new_tokens - 1

    decode_s, prefill_s = bench.best_valid(
        trials,
        lambda: bench.decode_trial(
            lambda: gen(params, prompt),
            lambda: gen_short(params, prompt),
            batch, prompt_len, new_tokens, short_tokens, cfg.vocab,
        ),
        key=lambda r: r[0],
    )
    print(json.dumps({
        "family": "moe-decode",
        "moe_impl": impl,
        "platform": platform,
        "batch": batch,
        "prompt_len": prompt_len,
        "new_tokens": new_tokens,
        "prefill_ms": round(prefill_s * 1e3, 2),
        "decode_tokens_per_sec": round(batch * steps / decode_s, 1),
        "decode_step_ms": round(decode_s / steps * 1e3, 3),
    }))


def main() -> None:
    import bench

    platform = bench.bring_up()
    which = sys.argv[1] if len(sys.argv) > 1 else "both"
    impls = ("einsum", "ragged") if which == "both" else (which,)
    for impl in impls:
        run_one(platform, impl)
    for impl in impls:
        run_decode(platform, impl)


if __name__ == "__main__":
    main()
