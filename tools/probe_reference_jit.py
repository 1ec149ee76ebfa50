#!/usr/bin/env python3
"""Is the float32 reference itself on the chip?  The reproducer behind
PERF.md section 7, "found by PR 36 (a)", and behind the shape of
``benchmarks/lib/olmo_hybrid_reference.py`` (every stage a program, the
forward pass run eagerly).

One ``linear_attention`` layer's mixer of the reference at the published
widths (hidden 3840, 30 heads of 96 / 192, kernel 4), seeded float32
weights, one row of ``--tokens`` seeded hidden states, three ways:

- ``one_program``: ``jax.jit`` around the whole mixer - projections,
  convolutions, the ``lax.scan`` over positions, gated norm, ``Wo`` - one
  XLA program whose only output is the mixer's;
- ``stages``: the same functions called eagerly, a stage a program (how
  the benchmark's check runs them);
- ``host``: numpy, float64, on the host, position by position, for the
  first ``--witness-tokens`` positions (everything is causal: a prefix of
  the row is the row's own) - the witness that says which side is wrong.

A line a comparison: a position's error is the root mean square of the
differences over the root mean square of the witness, over the hidden
axis; the median, the worst positions, and the positions around the first
multiples of 1,024.  PR 36 read ``one_program`` 25-63% off for some twenty
positions behind every multiple of 1,024 and ``stages`` right to 3e-5.

    chiprun -- python3 tools/probe_reference_jit.py --seed 2654435769

Needs a TPU to say anything about the chip (``--rehearsal cpu`` runs the
control flow at a tiny size: all three agree there).
"""
import argparse
import dataclasses
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
OUT = os.path.join("chiprun_out", "probe_reference_jit.jsonl")


def say(**fields) -> None:
    line = json.dumps(fields)
    print(line, flush=True)
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    with open(OUT, "a") as f:
        f.write(line + "\n")


def host_mixer(h, layer, c, n: int):
    """The reference's linear mixer for the first ``n`` positions of one
    row, numpy float64, the recurrence position by position."""
    import numpy as np

    f64 = lambda x: np.asarray(x, np.float64)
    h = f64(h)[0, :n]
    H, dk, dv = c.n_linear_heads, c.key_dim, c.value_dim
    silu = lambda x: x / (1.0 + np.exp(-x))
    unit = lambda x: x / np.sqrt(np.sum(x * x, axis=-1, keepdims=True) + 1e-6)

    def mixed(w, taps, width):
        x, taps = h @ f64(layer[w]), f64(layer[taps])
        K = taps.shape[0]
        y = x * taps[K - 1]
        for j in range(1, K):
            y[j:] += x[: n - j] * taps[K - 1 - j]
        return silu(y).reshape(n, H, width)

    q = unit(mixed("wq", "conv_q", dk)) / np.sqrt(dk)
    k = unit(mixed("wk", "conv_k", dk))
    v = mixed("wv", "conv_v", dv)
    beta = 1.0 / (1.0 + np.exp(-(h @ f64(layer["wb"]))))
    if c.allow_neg_eigval:
        beta = 2.0 * beta
    pre = h @ f64(layer["wa"]) + f64(layer["dt_bias"])
    g = -np.exp(f64(layer["A_log"])) * np.logaddexp(0.0, pre)
    S, o = np.zeros((H, dv, dk)), np.empty((n, H, dv))
    for t in range(n):
        S *= np.exp(g[t])[:, None, None]
        err = v[t] - np.einsum("hvk,hk->hv", S, k[t])
        S += (beta[t][:, None] * err)[:, :, None] * k[t][:, None, :]
        o[t] = np.einsum("hvk,hk->hv", S, q[t])
    gate = silu(h @ f64(layer["wg"])).reshape(n, H, dv)
    y = o / np.sqrt(np.mean(o * o, axis=-1, keepdims=True) + c.norm_eps)
    return (y * f64(layer["o_norm"]) * gate).reshape(n, -1) @ f64(layer["wo"])


def by_position(got, want):
    """A position's relative error: (positions,) float64."""
    import numpy as np

    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.sqrt(np.sum((got - want) ** 2, -1) / np.sum(want**2, -1))


def report(what: str, err) -> None:
    import numpy as np

    around = {
        f"at_{m - 4}_{m + 12}": [round(float(e), 6) for e in err[m - 4 : m + 12]]
        for m in range(1024, len(err), 1024)[:3]
    }
    behind = [  # the worst of the twenty positions behind each multiple
        round(float(err[m : m + 20].max()), 6) for m in range(1024, len(err), 1024)
    ]
    say(line="err", what=what, positions=len(err),
        all=float(np.sqrt(np.mean(err**2))), median=float(np.median(err)),
        worst=[[int(i), round(float(err[i]), 6)] for i in np.argsort(-err)[:8]],
        worst_behind_each_1024=behind, **around)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=2654435769)
    ap.add_argument("--tokens", type=int, default=16384)
    ap.add_argument("--witness-tokens", type=int, default=2304)
    ap.add_argument("--rehearsal", choices=("cpu",), default=None)
    args = ap.parse_args()

    from ddl_tpu.bringup import bring_up

    bring_up(args.rehearsal)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.lib import olmo_hybrid_reference as reference
    from ddl_tpu.models import olmo_hybrid as model

    small = dict(d_model=64, n_heads=4, d_ff=128, n_linear_heads=4,
                 linear_key_dim=8, linear_value_dim=16) if args.rehearsal else {}
    T = 2304 if args.rehearsal else args.tokens
    cfg = dataclasses.replace(
        model.OlmoHybridConfig.olmo_hybrid_7b(), layer_types=(model.LINEAR,),
        vocab=256, max_seq=T, param_dtype=jnp.float32, **small,
    )
    c = reference.Config(
        n_heads=cfg.n_heads, n_linear_heads=cfg.n_linear_heads,
        key_dim=cfg.linear_key_dim, value_dim=cfg.linear_value_dim,
        linear_layers=(True,), norm_eps=cfg.norm_eps,
    )
    layer = model.init_params(cfg, jax.random.key(args.seed))["layers"][0]
    h = jnp.asarray(np.random.default_rng([args.seed, 36]).standard_normal(
        (1, T, cfg.d_model), dtype=np.float32))
    say(line="probe", seed=args.seed, tokens=T, hidden=cfg.d_model,
        heads=cfg.n_linear_heads, backend=jax.default_backend(),
        device=jax.devices()[0].device_kind, jax=jax.__version__)

    def mixer(h, layer):
        return reference._linear_mixer(h, layer, c, reference._same)

    with jax.default_matmul_precision("highest"):
        t0 = time.monotonic()
        one_program = np.asarray(jax.jit(mixer)(h, layer))[0]
        say(line="time", what="one_program", seconds=round(time.monotonic() - t0, 1))
        t0 = time.monotonic()
        stages = np.asarray(mixer(h, layer))[0]
        say(line="time", what="stages", seconds=round(time.monotonic() - t0, 1))
    n = min(args.witness_tokens, T)
    t0 = time.monotonic()
    host = host_mixer(np.asarray(h), jax.device_get(layer), c, n)
    say(line="time", what="host", positions=n, seconds=round(time.monotonic() - t0, 1))
    report("one_program_against_host", by_position(one_program[:n], host))
    report("stages_against_host", by_position(stages[:n], host))
    # the whole row: no witness, the two programs against each other
    report("one_program_against_stages", by_position(one_program, stages))
    return 0


if __name__ == "__main__":
    sys.exit(main())
