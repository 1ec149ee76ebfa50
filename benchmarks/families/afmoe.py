"""Family adapter: AFMoE decoders (Trinity-Mini) through
``ddl_tpu/models/afmoe.py`` as ONE CHIP'S SHARE of a layer divided over
chips by experts: sliding-window and full gated attention in one stack,
sandwich norms, per-head QK-norm, a leading dense layer, then a sigmoid
top-k router over every published expert in front of the experts held
here plus a shared expert; the vocabulary's slice.

``loss_fn`` is the one hook the runner calls before the weights exist,
so it is where the system is held to the plain float32 reference
(``benchmarks/lib/afmoe_reference.py``, given the same share), in every
run's set-up: the configured model itself - every layer, the configured
remat, bf16 - on ``CHECK_ROWS`` seeded rows of the mix's length: logits,
loss, the held picks, and the norm of every gradient leaf on a prefix.  A
run outside the limits exits non-zero and prints no result.  The check's
findings stay in :data:`LAST_CHECK` for the ``held_choice_share`` reader.
"""

from __future__ import annotations

import dataclasses
import json
import sys
import time

# Imported here, not inside the hooks: a checkout whose program has no
# such model refuses the cell while the runner loads it - before a device,
# a weight or a producer exists.
from ddl_tpu.models import afmoe as model

from benchmarks.families.olmoe import _peak_gib, _seed_of_this_run
from benchmarks.lib import afmoe_flops

#: This family's rate metric (its unit is BENCHMARK.json's).
RATE_METRIC = "tokens_per_s"

#: What this run's reference check found (``None`` until it has run).
LAST_CHECK = None

#: Rows of ``max_seq`` tokens the reference check compares: the
#: ``tokens-8k`` mix's window, a step's rows at a time - the batch the
#: timed program takes (a test holds both to the mix: the hook is handed
#: the model and the mesh, not the mix).  AOT for a described v5e (PR 30):
#: both sides' forward passes on 2 x 8192 tokens are 3.94 GiB of arguments
#: (both sets of weights) + 3.08 of temporaries, under the train step's
#: own 3.94 + 4.98.
CHECK_ROWS = 4
PAIR_ROWS = 2
#: Tokens of the first row whose loss gradient is compared leaf by leaf.
#: Longer than the window, so that the band's lower edge is in the
#: backward kernels' work, and not the whole row: the reference's
#: attention probabilities are 32 heads x T x T float32 a layer (held one
#: layer at a time, ``checkpoint_layers``), and the check has to stay
#: under the train step's own peak, or the run's ``memory_peak_bytes``
#: would be the check's.
GRAD_TOKENS = 3072
#: bf16's unit roundoff: 8 bits of mantissa.
U_BF16 = 2.0**-9
#: Each limit below comes from two readings of the configured model (5
#: layers, published widths, the share) on the chip (my chip runs, PR 30,
#: TPU v5 lite, 15 seeds; PERF.md section 6): the largest the system at
#: its configured bf16 gives over the seeds, and what a stand-in gives that
#: has to be refused - the reference computed in float8_e4m3fn, the next
#: precision down, and the system with the window ignored (full causal
#: attention in the sliding layers).  The stand-ins' readings are given
#: twice: with the check's first form, and ("committed:") through this
#: file as it stands (tools/probe_afmoe_controls.py, seed 2654435769).
#:
#: Logits on the tokens whose HELD picks agree in every layer: root mean
#: square of the differences over the reference logits' root mean square.
#: bf16: 4.83-5.06 u.  float8: 74.6 u, committed: 63.9 u.  Window
#: ignored: 48.3 u, committed: 73.3 u.
LOGITS_RMS_LIMIT = 16 * U_BF16
#: |loss - reference loss| / reference loss of a row, over ALL its
#: tokens; the largest of the rows.  bf16: 2.5e-5-1.17e-4 (92 rows).
#: float8: 2.7e-4, committed: 3.4e-4.  Window ignored: 1.06e-3,
#: committed: 1.34e-3.
LOSS_REL_LIMIT = 2.0e-4
#: Share of tokens whose held picks must agree with the reference's in
#: every expert layer.  bf16: 0.933-0.945.  float8: 0.407, committed:
#: 0.507.  Window ignored: 0.249, committed: 0.259.
MIN_AGREE_SHARE = 0.8
#: | |g| - |g_ref| | / |g_ref|, the worst leaf of d loss / d parameters
#: on the prefix (``jax.grad`` of the train loss under the configured
#: remat against ``jax.grad`` of the reference).  ``expert_bias`` and, in
#: a share, ``w_router`` have no gradient on either side and are left out
#: (the readings below were taken with the router trained: its leaves
#: were the worst, so the limit is loose for what is compared now: 0.5-1.4%
#: over the third session's four seeds, a ``k_norm`` or ``q_norm``).  bf16: 1.5-5.0%, a
#: ``w_router`` in 14 of 15 seeds (about one of a token's eight choices is
#: held, so the router's gradient rides on few picks and moves with every
#: flipped one: OLMoE's limit of 3.5% refused a sound run here); the
#: median leaf 0.14-0.25%.  float8: 100% (the cotangents underflow),
#: committed too.  Window ignored: 6.5%, committed: 5.0% - inside this
#: limit, refused by the other three.
#: A norm does not see a direction (PERF.md section 7).
GRAD_NORM_LIMIT = 0.12
#: On the CPU (a rehearsal: hidden 64, 4 of 16 experts top-4, vocabulary
#: 256, 64-token rows) one flipped choice is a far larger share of the
#: model.  Over 8 seeds there: agreement 0.902-0.957, logits 8.9-14.6 u,
#: loss 6.7e-4-2.2e-3, worst gradient leaf 1.3-12.4%.  A rehearsal
#: rehearses the control flow; its limits are two to three times those
#: readings, and both stand-ins are outside them too (a test holds that).
REHEARSAL_LOGITS_RMS_LIMIT = 40 * U_BF16
REHEARSAL_LOSS_REL_LIMIT = 6e-3
REHEARSAL_GRAD_NORM_LIMIT = 0.27
REHEARSAL_MIN_AGREE_SHARE = 0.5


def sizes(c: dict, mix: dict) -> dict:
    """What the traffic generator needs to know of the model: ids are
    drawn from the vocabulary's slice."""
    return {"seq": mix["seq"], "vocab": c["vocab_size"]}


def samples_per_row(c: dict, mix: dict) -> int:
    return mix["seq"]


def flops_per_sample(c: dict, mix: dict) -> float:
    return afmoe_flops.afmoe_flops_per_token(c, mix["seq"])


def model_config(c: dict, mix: dict):
    import jax.numpy as jnp

    from ddl_tpu.config import TrainConfig

    t = c["training"]
    if c["score_func"] != "sigmoid" or c["n_group"] != 1 or c["topk_group"] != 1:
        raise ValueError("models/afmoe.py routes by sigmoid scores, one group")
    if len(c["layer_types"]) != c["num_hidden_layers"]:
        raise ValueError("layer_types does not state num_hidden_layers kinds")
    router = c["published"]["num_experts"]
    held = (c["deployment"]["first_expert"], c["num_experts"])
    return TrainConfig(remat=t["remat"]).model_config(model.AfmoeConfig(
        vocab=c["vocab_size"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"], n_kv_heads=c["num_key_value_heads"],
        head_dim=c["head_dim"], d_ff=c["intermediate_size"],
        d_expert=c["moe_intermediate_size"], n_experts=router,
        topk=c["num_experts_per_tok"], n_shared_experts=c["num_shared_experts"],
        layer_types=tuple(c["layer_types"]), n_dense_layers=c["num_dense_layers"],
        sliding_window=c["sliding_window"], route_norm=c["route_norm"],
        route_scale=c["route_scale"], mup_enabled=c["mup_enabled"],
        held_experts=None if held == (0, router) else held,
        max_seq=mix["seq"], rope_theta=float(c["rope_theta"]),
        norm_eps=c["rms_norm_eps"], param_dtype=jnp.dtype(t["param_dtype"]),
        attn_impl=t["attn_impl"],
    ))


def init_params(cfg, key):
    return model.init_params(cfg, key)


def param_specs(cfg):
    return model.param_specs(cfg)


def loss_fn(cfg, mesh):
    """The train loss over the loader's column tuple.  Where the mesh's
    devices are attached - not ``aot.py``'s described ones, on which
    nothing can run - the reference check runs first."""
    import jax

    if mesh.devices.flat[0] in jax.devices():
        reference_check(cfg, _seed_of_this_run())
    on_mesh = mesh if mesh.devices.size > 1 else None
    return lambda p, b: model.next_token_loss(p, b[0], cfg, mesh=on_mesh)


def reference_config(cfg, reference):
    return reference.Config(
        n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
        n_experts=cfg.n_experts, topk=cfg.topk, layer_types=cfg.layer_types,
        n_dense_layers=cfg.n_dense_layers, sliding_window=cfg.sliding_window,
        held=cfg.held, route_norm=cfg.route_norm, route_scale=cfg.route_scale,
        mup_enabled=cfg.mup_enabled, rope_theta=cfg.rope_theta,
        norm_eps=cfg.norm_eps, query_block=min(256, cfg.max_seq),
    )


def _sum_squares(g):
    import jax.numpy as jnp

    return jnp.sum(g.astype(jnp.float32) ** 2)


def _tap_norms(loss, layer_fn=None):
    """``norms(p, row)``: the norm of every leaf of d ``loss(p, row)`` / d
    ``p`` by leaf path, without the gradient tree ever being whole, from
    one program (``norms.program``): a leaf passes through an identity that
    hands its cotangent's sum of squares to a scalar probe, and the probes
    are what is differentiated.

    With ``layer_fn`` (the reference's ``_layer``: float32 cotangents,
    2.6 GiB of them) ``loss`` takes ``layer_fn=`` too, and a layer's leaves
    are tapped by the layer: its backward pass reduces every parameter
    cotangent of the layer to the probes BEFORE it lets the cotangent of
    its input go (an optimization barrier), so one layer's are alive at a
    time.  Leaf by leaf XLA schedules the reductions late and keeps all of
    them: 5.66 GiB of temporaries against 3.15 for half of the layers (AOT
    for a described v5e, PR 30) - more than the train step's 4.98, and the
    run's ``memory_peak_bytes`` would be the check's."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    @jax.custom_vjp
    def tap(w, probe):
        return w

    tap.defvjp(lambda w, probe: (w, None), lambda _, g: (g, _sum_squares(g)))

    @functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
    def tapped_layer(x, layer, c, r, sliding, dense):
        return layer_fn(x, layer["w"], c, r, sliding, dense)

    def tapped_layer_fwd(x, layer, c, r, sliding, dense):
        out, pull, top_e = jax.vjp(
            lambda x, w: layer_fn(x, w, c, r, sliding, dense), x, layer["w"],
            has_aux=True,
        )
        return (out, top_e), pull

    def tapped_layer_bwd(c, r, sliding, dense, pull, cotangents):
        g_x, g_w = pull(cotangents[0])
        g_x, squares = jax.lax.optimization_barrier(
            (g_x, jax.tree.map(_sum_squares, g_w))
        )
        return g_x, {"w": jax.tree.map(jnp.zeros_like, g_w), "probe": squares}

    tapped_layer.defvjp(tapped_layer_fwd, tapped_layer_bwd)

    def tapped(probes, p, t):
        if layer_fn is None:
            return loss(jax.tree.map(tap, p, probes), t)
        outside = {k: v for k, v in p.items() if k != "layers"}
        taps = jax.tree.map(tap, outside, {k: probes[k] for k in outside})
        taps["layers"] = [
            {"w": w, "probe": probe}
            for w, probe in zip(p["layers"], probes["layers"])
        ]
        return loss(taps, t, layer_fn=tapped_layer)

    program = jax.jit(jax.grad(tapped))

    def norms(p, row) -> dict:
        probes = jax.tree.map(lambda _: jnp.zeros((), jnp.float32), p)
        squares = jax.device_get(program(probes, p, row))
        return {
            jax.tree_util.keystr(path): float(np.sqrt(sq))
            for path, sq in jax.tree_util.tree_leaves_with_path(squares)
        }

    norms.program = program
    return norms


def check_programs(cfg, compute_dtype=None, window_ignored: bool = False) -> dict:
    """The comparison's programs for the model ``cfg`` (or a stand-in for
    it): ``errors(stored, exact, rows)``, the two sides' forward passes on
    the same rows as sums; ``got_norms`` / ``want_norms``, the two sides'
    gradient norms (:func:`_tap_norms`).  Built apart from the arrays so
    that a script can compile them for a described chip."""
    import jax
    import jax.numpy as jnp

    from benchmarks.lib import afmoe_reference as reference
    from ddl_tpu.models.losses import next_token_cross_entropy

    c = reference_config(cfg, reference)
    ran = cfg
    if window_ignored:
        ran = dataclasses.replace(cfg, sliding_window=cfg.max_seq)
    first, count = cfg.held

    def by_row(loss, logits, t):
        """(rows,): each row's own loss."""
        return jax.vmap(lambda lg, tk: loss(lg[None], tk[None]))(logits, t)

    def system(p, t):
        # One pass: the loss the model's ``next_token_loss`` takes of
        # these logits (a test holds the two together).
        logits, picks = model.forward_with_choices(p, t, ran)
        return logits, by_row(next_token_cross_entropy, logits, t), picks

    def plain(p, t, dtype):
        """The reference computed in ``dtype`` (``None``: float32)."""
        logits, picks = reference.forward(p, t, c, dtype)
        return logits, by_row(reference.cross_entropy, logits, t), picks

    def held_only(picks):
        """Each token's held picks, sorted; a pick held elsewhere is -1."""
        mine = (picks >= first) & (picks < first + count)
        return jnp.sort(jnp.where(mine, picks, -1), -1), mine

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
        got_held, mine = held_only(got_picks)
        want_held, _ = held_only(want_picks)
        same = jnp.all(got_held == want_held, axis=(0, -1))  # (rows, seq)
        diff2 = jnp.where(same[..., None], (got_logits - want_logits) ** 2, 0.0)
        want2 = jnp.where(same[..., None], want_logits**2, 0.0)
        return {
            "same": jnp.sum(same), "diff2": jnp.sum(diff2),
            "want2": jnp.sum(want2), "diff2_max": jnp.max(diff2),
            "loss": got_loss, "reference_loss": want_loss,
            # (expert layers,): the system's choices that fell on held experts
            "held": jnp.sum(mine, axis=(1, 2, 3)),
            # (expert layers, held experts): the rows each held expert got
            "load": jax.vmap(lambda e, m: jnp.bincount(
                jnp.where(m, e - first, count).reshape(-1), length=count + 1
            )[:count])(got_picks, mine),
        }

    c_grad = c._replace(checkpoint_layers=True)

    def plain_loss(dtype):
        return lambda p, t, layer_fn: reference.loss(p, t, c_grad, dtype, layer_fn)

    want_norms = _tap_norms(plain_loss(None), reference._layer)
    if compute_dtype is None:
        got_norms = _tap_norms(lambda p, t: model.next_token_loss(p, t, ran))
    else:
        got_norms = _tap_norms(plain_loss(compute_dtype), reference._layer)
    return {"errors": errors, "got_norms": got_norms, "want_norms": want_norms}


def compare_with_reference(cfg, seed: int, compute_dtype=None,
                           window_ignored: bool = False) -> dict:
    """The model the window trains - ``cfg`` as it stands: every layer,
    the configured remat, the share - against the float32 reference on the
    same seeded weights (``cfg``'s storage dtype) and ``CHECK_ROWS`` seeded
    rows of ``cfg.max_seq`` tokens: logits, loss and the routers' picks of
    one forward pass ``PAIR_ROWS`` at a time, then the norm of every leaf
    of the loss gradient on the first ``GRAD_TOKENS`` tokens of the first
    row.  Two stand-ins for the system, which a limit must refuse: with
    ``compute_dtype`` the reference computed in that precision; with
    ``window_ignored`` the system attending the whole causal triangle in
    its sliding layers."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    programs = check_programs(cfg, compute_dtype, window_ignored)
    first, count = cfg.held
    stored = jax.jit(lambda key: model.init_params(cfg, key))(
        jax.random.fold_in(jax.random.key(seed), 30)
    )
    exact = jax.tree.map(lambda x: x.astype(jnp.float32), stored)
    tokens = jnp.asarray(
        np.random.default_rng([seed, 30]).integers(
            0, cfg.vocab, (CHECK_ROWS, cfg.max_seq), dtype=np.int32
        )
    )
    pairs = [
        jax.device_get(programs["errors"](stored, exact, tokens[lo : lo + PAIR_ROWS]))
        for lo in range(0, CHECK_ROWS, PAIR_ROWS)
    ]
    same = sum(float(p["same"]) for p in pairs)
    rms = np.sqrt(sum(float(p["want2"]) for p in pairs) / max(same, 1.0))
    held = sum(p["held"] for p in pairs).astype(np.float64)  # (expert layers,)
    load = sum(p["load"] for p in pairs).astype(np.float64)
    choices = tokens.size * cfg.topk  # a layer
    loss = np.concatenate([p["loss"] for p in pairs]).astype(np.float64)
    reference_loss = np.concatenate([p["reference_loss"] for p in pairs]).astype(
        np.float64
    )
    out = {
        "agree_share": same / tokens.size,
        "logits_rel_rms": float(
            np.sqrt(sum(float(p["diff2"]) for p in pairs) / max(same, 1.0)) / rms
        ),
        "logits_rel_max": float(
            np.sqrt(max(float(p["diff2_max"]) for p in pairs)) / rms
        ),
        "loss": loss.tolist(), "reference_loss": reference_loss.tolist(),
        "loss_rel_diff": float(np.max(np.abs(loss - reference_loss) / reference_loss)),
        "held_choice_share": float(held.sum() / (choices * len(held))),
        "held_choice_share_by_layer": [float(h / choices) for h in held],
        "balanced_held_share": count / cfg.n_experts,
        "held_load_max_over_mean": float(
            np.max(load.max(1) / np.maximum(load.mean(1), 1.0))
        ),
    }

    # -- the gradients ----------------------------------------------------------
    row = tokens[:1, : min(GRAD_TOKENS, cfg.max_seq)]
    if compute_dtype is None:
        got_norms = programs["got_norms"](stored, row)
    del stored  # room for the reference's residuals
    if compute_dtype is not None:
        got_norms = programs["got_norms"](exact, row)
    want_norms = programs["want_norms"](exact, row)
    # The leaves the reference gives no gradient: expert_bias (selection
    # only) and, in a share, the router (not trained by a share).
    frozen = [k for k, w in want_norms.items() if w == 0.0]
    rel = {
        k: abs(float(got_norms[k]) - float(w)) / float(w)
        for k, w in want_norms.items() if k not in frozen
    }
    worst = max(rel, key=rel.get)
    out.update(
        grad_tokens=int(row.shape[1]), grad_leaves=len(rel),
        grad_norm_rel_diff=rel[worst], grad_norm_worst_leaf=worst,
        grad_norm_rel_diff_median=float(np.median(list(rel.values()))),
        frozen_leaves=len(frozen),
        frozen_grad_norm=max(float(got_norms[k]) for k in frozen),
    )
    return out


def problems_of(found: dict, rehearsal: bool) -> list:
    """What of a comparison is outside the limits."""
    loss_limit = REHEARSAL_LOSS_REL_LIMIT if rehearsal else LOSS_REL_LIMIT
    grad_limit = REHEARSAL_GRAD_NORM_LIMIT if rehearsal else GRAD_NORM_LIMIT
    agree_limit = REHEARSAL_MIN_AGREE_SHARE if rehearsal else MIN_AGREE_SHARE
    logits_limit = REHEARSAL_LOGITS_RMS_LIMIT if rehearsal else LOGITS_RMS_LIMIT
    problems = []
    if found["agree_share"] < agree_limit:
        problems.append(
            f"only {found['agree_share']:.3f} of the tokens pick the same held experts"
        )
    if not found["logits_rel_rms"] <= logits_limit:
        problems.append(
            f"logits differ by {found['logits_rel_rms']:.4g} of their rms, "
            f"limit {logits_limit:.4g}"
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
    if found["frozen_grad_norm"] != 0.0:
        problems.append(
            "a leaf the reference gives no gradient (expert_bias, a share's "
            "router) has one in the system"
        )
    return problems


def reference_check(cfg, seed: int) -> dict:
    """Run the comparison, say what it found, keep it for the
    ``held_choice_share`` reader, and refuse the run where it is outside
    the limits."""
    global LAST_CHECK
    from benchmarks.lib import hostproc

    import jax

    t0 = time.monotonic()
    found = compare_with_reference(cfg, seed)
    problems = problems_of(found, rehearsal=jax.default_backend() == "cpu")
    print(json.dumps({
        "line": "reference_check",
        "at_s": round(hostproc.seconds_since_process_start(), 2),
        "seed": seed, "rows": CHECK_ROWS, "seq": cfg.max_seq,
        "layers": cfg.n_layers, "remat": str(cfg.remat), "held": list(cfg.held),
        "seconds": round(time.monotonic() - t0, 2),
        "peak_GiB": _peak_gib(), **found,
        "problems": problems,
    }), flush=True)
    if problems:
        raise SystemExit(
            "the system is not the float32 reference: " + "; ".join(problems)
        )
    LAST_CHECK = found
    return found
