"""Family adapter: OLMoE-shaped sparse decoders through
``ddl_tpu/models/moe.py`` (pre-RMSNorm, full multi-head attention with
QK-norm, RoPE, 64 routed SwiGLU experts top-8 with raw gates, dropless
dispatch, no biases, untied head; load-balance + z router losses).

``loss_fn`` is the one hook the runner calls before the weights exist,
so it is where the system is held to the plain float32 reference
(``benchmarks/lib/olmoe_reference.py``), in every run's set-up: the
configured model itself - every layer, the configured remat - on
``CHECK_ROWS`` seeded rows: logits, loss, routing, and the norm of every
gradient leaf.  A run outside the limits exits non-zero and prints no
result.
"""

from __future__ import annotations

import json
import sys
import time

from benchmarks.lib import moe_flops

#: This family's rate metric (its unit is BENCHMARK.json's).
RATE_METRIC = "tokens_per_s"

#: Rows of ``max_seq`` tokens the reference check compares: the
#: ``tokens-4k`` mix's batch (a test holds the two together: the hook is
#: handed the model and the mesh, not the mix), ``PAIR_ROWS`` at a time.
CHECK_ROWS = 4
PAIR_ROWS = 2
#: Tokens of the first row whose loss gradient is compared leaf by leaf.
#: Not the whole row: the reference keeps every token's pass through all
#: 64 experts for its backward (float32, two layers).  AOT for a described
#: v5e (PERF.md section 6): 10.5 GiB at 512 tokens, 13.3 at 1,024, 20.0 at
#: 2,048 - and the check has to stay under the train step's own peak, or
#: the run's ``memory_peak_bytes`` would be the check's.  At 1,024 tokens
#: the worst leaf read 0.70% where 512 read 0.84% (seed 1779033703).
GRAD_TOKENS = 512
#: bf16's unit roundoff: 8 bits of mantissa.
U_BF16 = 2.0**-9
#: Each limit below comes from two readings of the configured model (2
#: layers, published widths) on the chip (my chip runs, PR 26, TPU v5
#: lite, 18 seeds; PERF.md section 6): the largest the system at its
#: configured bf16 gives, and what the reference computed in
#: float8_e4m3fn, the next precision down, gives - which has to be
#: refused.
#:
#: Logits on the tokens whose top-k sets agree in every layer: root mean
#: square of the differences over the reference logits' root mean square.
#: bf16: 5.34-6.23 u.  float8: 54.5 u.
LOGITS_RMS_LIMIT = 16 * U_BF16
#: |loss - reference loss| / reference loss of a pair of rows, over ALL
#: its tokens (a flipped last choice moves a token's output by one
#: expert's gate); the larger of the two pairs.  bf16: at most 5.8e-5
#: (36 pairs).  With random weights the loss is log(vocab) whatever the
#: precision (float8: 8.5e-5), so this limit guards the loss's terms, not
#: the precision: without the z-loss the loss moves by 1.7e-3, without
#: the load-balance term by 7e-3.
LOSS_REL_LIMIT = 1.5e-4
#: Share of tokens whose top-k sets must agree with the reference's in
#: every layer.  bf16: 0.876-0.901.  float8: 0.307.
MIN_AGREE_SHARE = 0.8
#: | |g| - |g_ref| | / |g_ref|, the worst leaf of d loss / d parameters
#: (``jax.grad`` of the train loss under the configured remat against
#: ``jax.grad`` of the reference): what holds the grouped matmuls'
#: transposes and the rematerialised forward to the reference.  bf16: at
#: most 1.13%, a ``w_router`` in 13 of 18 seeds (its gradient moves with
#: every flipped choice); the median leaf 0.10-0.31%.  float8: 100% (the
#: cotangents underflow to zero).  A norm does not see a direction: an
#: error that keeps every leaf's norm passes (PERF.md section 7).
GRAD_NORM_LIMIT = 0.035
#: On the CPU (a rehearsal: hidden 64, 8 experts top-3, vocabulary 256)
#: one flipped choice is a far larger share of the model: over 4 seeds the
#: loss reads up to 1.9e-3 and the worst gradient leaf 8.9%.  A rehearsal
#: rehearses the control flow; these two limits are three times those
#: readings there, the others are the chip's.
REHEARSAL_LOSS_REL_LIMIT = 6e-3
REHEARSAL_GRAD_NORM_LIMIT = 0.27


def sizes(c: dict, mix: dict) -> dict:
    """What the traffic generator needs to know of the model."""
    return {"seq": mix["seq"], "vocab": c["vocab_size"]}


def samples_per_row(c: dict, mix: dict) -> int:
    return mix["seq"]


def flops_per_sample(c: dict, mix: dict) -> float:
    return moe_flops.moe_decoder_flops_per_token(c, mix["seq"])


def model_config(c: dict, mix: dict):
    import jax.numpy as jnp

    from ddl_tpu.config import TrainConfig
    from ddl_tpu.models import moe

    t = c["training"]
    if c["hidden_size"] != c["num_attention_heads"] * c["head_dim"]:
        raise ValueError("models/moe.py derives head_dim as hidden/heads")
    return TrainConfig(remat=t["remat"]).model_config(moe.MoeConfig(
        vocab=c["vocab_size"], d_model=c["hidden_size"],
        n_layers=c["num_hidden_layers"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        n_experts=c["num_experts"], topk=c["num_experts_per_tok"],
        max_seq=mix["seq"], rope_theta=float(c["rope_theta"]),
        norm_eps=c["rms_norm_eps"], param_dtype=jnp.dtype(t["param_dtype"]),
        attn_impl=t["attn_impl"], qk_norm=True,
        norm_topk_prob=c["norm_topk_prob"],
        router_aux_weight=c["router_aux_loss_coef"],
        router_aux_all_slots=True, router_z_weight=c["router_z_loss_coef"],
    ))


def init_params(cfg, key):
    from ddl_tpu.models import moe

    return moe.init_params(cfg, key)


def param_specs(cfg):
    from ddl_tpu.models import moe

    return moe.param_specs(cfg)


def loss_fn(cfg, mesh):
    """The train loss over the loader's column tuple (one chip: no mesh
    for the attention and the routing; a mesh: batch-sharded local
    attention and per-shard dropless routing over it).  Where the mesh's
    devices are attached — not ``aot.py``'s described ones, on which
    nothing can run — the reference check runs first."""
    import jax

    from ddl_tpu.models import moe

    if mesh.devices.flat[0] in jax.devices():
        reference_check(cfg, _seed_of_this_run())
    on_mesh = mesh if mesh.devices.size > 1 else None
    return lambda p, b: moe.next_token_loss(p, b[0], cfg, mesh=on_mesh)


def _seed_of_this_run() -> int:
    """``--seed`` of the runner's command line (0 without one): the hook
    is handed the model and the mesh, not the run's arguments."""
    argv = sys.argv
    for i, arg in enumerate(argv):
        if arg == "--seed" and i + 1 < len(argv):
            return int(argv[i + 1])
        if arg.startswith("--seed="):
            return int(arg.split("=", 1)[1])
    return 0


def reference_config(cfg, reference):
    return reference.Config(
        n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
        n_experts=cfg.n_experts, topk=cfg.topk, rope_theta=cfg.rope_theta,
        norm_eps=cfg.norm_eps, norm_topk_prob=cfg.norm_topk_prob,
        router_aux_weight=cfg.router_aux_weight,
        router_z_weight=cfg.router_z_weight,
        query_block=min(512, cfg.max_seq),
    )


def compare_with_reference(cfg, seed: int, compute_dtype=None) -> dict:
    """The model the window trains - ``cfg`` as it stands: every layer,
    the configured remat and dispatch - against the float32 reference on
    the same seeded weights (``cfg``'s storage dtype) and ``CHECK_ROWS``
    seeded rows of ``cfg.max_seq`` tokens: logits, loss and the routers'
    picks of one forward pass a pair of rows at a time, then the norm of
    every leaf of the loss gradient on the first ``GRAD_TOKENS`` tokens of
    the first row.  With ``compute_dtype`` the reference computed in that
    precision stands in for the system (what a limit must refuse)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.lib import olmoe_reference as reference
    from ddl_tpu.models import moe

    c = reference_config(cfg, reference)
    stored = jax.jit(lambda key: moe.init_params(cfg, key))(
        jax.random.fold_in(jax.random.key(seed), 26)
    )
    exact = jax.tree.map(lambda x: x.astype(jnp.float32), stored)
    tokens = jnp.asarray(
        np.random.default_rng([seed, 26]).integers(
            0, cfg.vocab, (CHECK_ROWS, cfg.max_seq), dtype=np.int32
        )
    )

    def system(p, t):
        logits, picks = moe.forward_with_choices(p, t, cfg)
        return logits, moe.next_token_loss(p, t, cfg), picks

    def plain(p, t, dtype):
        """The reference computed in ``dtype`` (``None``: float32)."""
        logits, balance, z, picks = reference.forward(p, t, c, dtype)
        loss = (
            reference.cross_entropy(logits, t)
            + c.router_aux_weight * balance + c.router_z_weight * z
        )
        return logits, loss, picks

    @jax.jit
    def errors(stored, exact, t):
        """The system (or its stand-in) against the float32 reference on
        the rows ``t``, as sums.  One program, so that neither side's
        logits is ever a live array beside both sets of weights: the
        check must not set the run's ``memory_peak_bytes``."""
        if compute_dtype is None:
            got_logits, got_loss, got_picks = system(stored, t)
        else:
            got_logits, got_loss, got_picks = plain(exact, t, compute_dtype)
        want_logits, want_loss, want_picks = plain(exact, t, None)
        same = jnp.all(
            jnp.sort(got_picks, -1) == jnp.sort(want_picks, -1), axis=(0, -1)
        )  # (rows, seq): the sets agree in every layer
        diff2 = jnp.where(same[..., None], (got_logits - want_logits) ** 2, 0.0)
        want2 = jnp.where(same[..., None], want_logits**2, 0.0)
        return {
            "same": jnp.sum(same), "diff2": jnp.sum(diff2),
            "want2": jnp.sum(want2), "diff2_max": jnp.max(diff2),
            "loss": got_loss, "reference_loss": want_loss,
            # (layers, experts): how many of the choices each expert got
            "load": jax.vmap(
                lambda e: jnp.bincount(e.reshape(-1), length=cfg.n_experts)
            )(want_picks),
        }

    pairs = [
        jax.device_get(errors(stored, exact, tokens[lo : lo + PAIR_ROWS]))
        for lo in range(0, CHECK_ROWS, PAIR_ROWS)
    ]
    same = sum(float(p["same"]) for p in pairs)
    rms = np.sqrt(sum(float(p["want2"]) for p in pairs) / max(same, 1.0))
    load = sum(p["load"] for p in pairs).astype(np.float64)  # (layers, experts)
    loss_rel = [
        abs(float(p["loss"]) - float(p["reference_loss"]))
        / abs(float(p["reference_loss"])) for p in pairs
    ]
    out = {
        "agree_share": same / tokens.size,
        "logits_rel_rms": float(
            np.sqrt(sum(float(p["diff2"]) for p in pairs) / max(same, 1.0)) / rms
        ),
        "logits_rel_max": float(
            np.sqrt(max(float(p["diff2_max"]) for p in pairs)) / rms
        ),
        "loss": [float(p["loss"]) for p in pairs],
        "reference_loss": [float(p["reference_loss"]) for p in pairs],
        "loss_rel_diff": max(loss_rel),
        "expert_load_max_over_mean": float(np.max(load.max(1) / load.mean(1))),
        "largest_group_share": float(np.max(load.max(1) / load.sum(1))),
    }

    # -- the gradients ----------------------------------------------------------
    row = tokens[:1, : min(GRAD_TOKENS, cfg.max_seq)]

    def grad_norms(loss, p):
        """The norm of every leaf of d ``loss(p, row)`` / d ``p``."""
        return jax.device_get(jax.jit(lambda p, t: {
            jax.tree_util.keystr(path): jnp.sqrt(jnp.sum(g.astype(jnp.float32) ** 2))
            for path, g in jax.tree_util.tree_leaves_with_path(jax.grad(loss)(p, t))
        })(p, row))

    if compute_dtype is None:
        got_norms = grad_norms(lambda p, t: moe.next_token_loss(p, t, cfg), stored)
    del stored  # room for the reference's residuals
    if compute_dtype is not None:
        got_norms = grad_norms(
            lambda p, t: reference.loss(p, t, c, compute_dtype), exact
        )
    want_norms = grad_norms(lambda p, t: reference.loss(p, t, c), exact)
    rel = {
        k: abs(float(got_norms[k]) - float(w)) / float(w)
        for k, w in want_norms.items()
    }
    worst = max(rel, key=rel.get)
    out.update(
        grad_tokens=int(row.shape[1]), grad_leaves=len(rel),
        grad_norm_rel_diff=rel[worst], grad_norm_worst_leaf=worst,
        grad_norm_rel_diff_median=float(np.median(list(rel.values()))),
    )
    return out


def _peak_gib():
    """The device's peak so far, counted as the runner counts it."""
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    peak = (stats.get("peak_bytes_in_use") or 0) + (stats.get("peak_bytes_reserved") or 0)
    return round(peak / 2**30, 2) or None


def reference_check(cfg, seed: int) -> dict:
    """Run the comparison, say what it found, and refuse the run where it
    is outside the limits."""
    from benchmarks.lib import hostproc

    import jax

    t0 = time.monotonic()
    found = compare_with_reference(cfg, seed)
    rehearsal = jax.default_backend() == "cpu"
    loss_limit = REHEARSAL_LOSS_REL_LIMIT if rehearsal else LOSS_REL_LIMIT
    grad_limit = REHEARSAL_GRAD_NORM_LIMIT if rehearsal else GRAD_NORM_LIMIT
    problems = []
    if found["agree_share"] < MIN_AGREE_SHARE:
        problems.append(f"only {found['agree_share']:.3f} of the tokens route alike")
    if not found["logits_rel_rms"] <= LOGITS_RMS_LIMIT:
        problems.append(
            f"logits differ by {found['logits_rel_rms']:.4g} of their rms, "
            f"limit {LOGITS_RMS_LIMIT:.4g}"
        )
    if not found["loss_rel_diff"] <= loss_limit:
        problems.append(
            f"losses {found['loss']!r} against the reference's "
            f"{found['reference_loss']!r}, limit {loss_limit} relative"
        )
    if not found["grad_norm_rel_diff"] <= grad_limit:
        problems.append(
            f"the gradient of {found['grad_norm_worst_leaf']} differs in norm "
            f"by {found['grad_norm_rel_diff']:.4g}, limit {grad_limit}"
        )
    print(json.dumps({
        "line": "reference_check",
        "at_s": round(hostproc.seconds_since_process_start(), 2),
        "seed": seed, "rows": CHECK_ROWS, "seq": cfg.max_seq,
        "layers": cfg.n_layers, "remat": str(cfg.remat),
        "seconds": round(time.monotonic() - t0, 2),
        "peak_GiB": _peak_gib(), **found,
        "problems": problems,
    }), flush=True)
    if problems:
        raise SystemExit(
            "the system is not the float32 reference: " + "; ".join(problems)
        )
    return found
