"""Family adapter: DeepSeek-V3-shaped decoders (Kanana-2-30B-A3B) through
``ddl_tpu/models/deepseek_v3.py`` as ONE CHIP'S SHARE of a layer divided
over chips by experts: latent attention (192-wide score heads as a
128-deep and a 64-deep rotary product with one shared rotary key, 128-wide
value heads) in front of a leading dense layer, then a sigmoid top-k
router over every published expert in front of the experts held here plus
the shared experts; the vocabulary's slice.

``loss_fn`` is the one hook the runner calls before the weights exist, so
it is where the system is held to the plain float32 reference
(``benchmarks/lib/deepseek_v3_reference.py``, given the same share), in
every run's set-up: the configured model itself - every layer, the
configured remat, bf16 - on ``CHECK_ROWS`` seeded rows of the mix's
length: logits, loss, the held picks, and the norm of every gradient leaf
on a prefix.  A run outside the limits exits non-zero and prints no
result.
"""

from __future__ import annotations

import contextlib
import json
import time
from unittest import mock

# Imported here, not inside the hooks: a checkout whose program has no
# such model refuses the cell while the runner loads it - before a device,
# a weight or a producer exists.
from ddl_tpu.models import deepseek_v3 as model

from benchmarks.families.afmoe import _tap_norms
from benchmarks.families.olmoe import _peak_gib, _seed_of_this_run
from benchmarks.lib import mla_flops

#: This family's rate metric (its unit is BENCHMARK.json's).
RATE_METRIC = "tokens_per_s"

#: Rows of ``max_seq`` tokens the reference check compares: the
#: ``tokens-8k`` mix's window, a step's rows at a time - the batch the
#: timed program takes (a test holds both to the mix: the hook is handed
#: the model and the mesh, not the mix).
CHECK_ROWS = 4
PAIR_ROWS = 2
#: Tokens of the first row whose loss gradient is compared leaf by leaf:
#: three of the kernels' 1024-blocks, so that the backward kernels run
#: their diagonal and their interior blocks, and not the whole row: the
#: reference's attention probabilities are 32 heads x T x T float32 a
#: layer (held a query block at a time, ``checkpoint_layers``), and the
#: check has to stay under the train step's own peak, or the run's
#: ``memory_peak_bytes`` would be the check's.
GRAD_TOKENS = 3072
#: bf16's unit roundoff: 8 bits of mantissa.
U_BF16 = 2.0**-9
#: The system's stand-ins with a planted fault of the new mechanism, which
#: the limits must refuse (:func:`_planted`).
FAULTS = ("no_rope_product", "scale_128")
#: Each limit below comes from two readings of the configured model
#: (1 + 6 layers, published widths, the share) on the chip (my chip runs,
#: PR 32, TPU v5 lite, 8 seeds; PERF.md section 6): the largest the system
#: at its configured bf16 gives over the seeds, and what a stand-in gives
#: that has to be refused - the reference computed in float8_e4m3fn, the
#: next precision down, and the system with the rotary product left out of
#: the score or with the scale 1/sqrt(128), all three through this file
#: (tools/probe_mla_controls.py, seed 2654435769).
#:
#: Logits on the tokens whose HELD picks agree in every layer: root mean
#: square of the differences over the reference logits' root mean square.
#: bf16: 7.84-8.19 u.  float8: 107 u.  Rotary product omitted: 303 u.
#: Scale 1/sqrt(128): 192 u.
LOGITS_RMS_LIMIT = 16 * U_BF16
#: |loss - reference loss| / reference loss of a row, over ALL its
#: tokens; the largest of the rows.  bf16: 7.5e-5-1.26e-4 (32 rows).
#: float8: 6.6e-4.  Rotary product omitted: 1.58e-3.  Scale: 7.3e-4.
LOSS_REL_LIMIT = 3.0e-4
#: Share of tokens whose held picks must agree with the reference's in
#: every expert layer (six of them).  bf16: 0.887-0.901.  float8: 0.279.
#: Rotary product omitted: 0.021.  Scale: 0.101.
MIN_AGREE_SHARE = 0.8
#: | |g| - |g_ref| | / |g_ref|, the worst leaf of d loss / d parameters
#: on the prefix (``jax.grad`` of the train loss under the configured
#: remat against ``jax.grad`` of the reference).  ``expert_bias`` and, in
#: a share, ``w_router`` have no gradient on either side and are left out.
#: bf16: 0.68-1.42%, a norm's weight (``attn_norm``, ``kv_a_norm``,
#: ``mlp_norm``) in every seed; the median leaf 0.14-0.27%.  float8: 100%
#: (the cotangents underflow).  Rotary product omitted: 19.6%.  Scale:
#: 49.8% (both a ``wq``).  A norm does not see a direction (PERF.md
#: section 7).
GRAD_NORM_LIMIT = 0.06
#: On the CPU (a rehearsal: hidden 64, 3 layers, 4 of 16 experts top-3,
#: vocabulary 256, 64-token rows) one flipped choice is a far larger share
#: of the model.  Over 8 seeds there: agreement 0.953-0.988, logits
#: 6.7-11.6 u, loss 5.2e-4-2.7e-3, worst gradient leaf 0.6-9.2%.  A
#: rehearsal rehearses the control flow; its limits are two to three times
#: those readings, and all three stand-ins are outside them too (a test
#: holds that): float8 agreement 0.66-0.71, logits 106-119 u, gradient
#: 64-73%; rotary product omitted 0.36, 285-312 u, 30-66%; scale
#: 1/sqrt(16) 0.66-0.73, 117-127 u, 27-43% (two seeds).
REHEARSAL_LOGITS_RMS_LIMIT = 40 * U_BF16
REHEARSAL_LOSS_REL_LIMIT = 6e-3
REHEARSAL_GRAD_NORM_LIMIT = 0.27
REHEARSAL_MIN_AGREE_SHARE = 0.85


def sizes(c: dict, mix: dict) -> dict:
    """What the traffic generator needs to know of the model: ids are
    drawn from the vocabulary's slice."""
    return {"seq": mix["seq"], "vocab": c["vocab_size"]}


def samples_per_row(c: dict, mix: dict) -> int:
    return mix["seq"]


def flops_per_sample(c: dict, mix: dict) -> float:
    return mla_flops.mla_flops_per_token(c, mix["seq"])


def model_config(c: dict, mix: dict):
    import jax.numpy as jnp

    from ddl_tpu.config import TrainConfig

    t = c["training"]
    if (c["scoring_func"], c["topk_method"], c["n_group"], c["topk_group"]) != (
        "sigmoid", "noaux_tc", 1, 1
    ):
        raise ValueError(
            "models/deepseek_v3.py routes by sigmoid scores under a selection "
            "bias, one group"
        )
    if c["q_lora_rank"] is not None or c["rope_scaling"] is not None:
        raise ValueError(
            "models/deepseek_v3.py has no query low-rank step and no rope scaling"
        )
    if not c["rope_interleave"] or c["moe_layer_freq"] != 1:
        raise ValueError("models/deepseek_v3.py: interleaved rope, every layer routed")
    if c["qk_head_dim"] != c["qk_nope_head_dim"] + c["qk_rope_head_dim"]:
        raise ValueError("qk_head_dim is not its two parts")
    router = c["published"]["n_routed_experts"]
    held = (c["deployment"]["first_expert"], c["n_routed_experts"])
    return TrainConfig(remat=t["remat"]).model_config(model.DeepseekV3Config(
        vocab=c["vocab_size"], d_model=c["hidden_size"],
        n_layers=c["num_hidden_layers"], n_heads=c["num_attention_heads"],
        qk_nope_dim=c["qk_nope_head_dim"], qk_rope_dim=c["qk_rope_head_dim"],
        v_head_dim=c["v_head_dim"], kv_lora_rank=c["kv_lora_rank"],
        d_ff=c["intermediate_size"], d_expert=c["moe_intermediate_size"],
        n_experts=router, topk=c["num_experts_per_tok"],
        n_shared_experts=c["n_shared_experts"],
        n_dense_layers=c["first_k_dense_replace"],
        route_norm=c["norm_topk_prob"], route_scale=c["routed_scaling_factor"],
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
        n_heads=cfg.n_heads, qk_nope_dim=cfg.qk_nope_dim,
        qk_rope_dim=cfg.qk_rope_dim, v_head_dim=cfg.v_head_dim,
        kv_lora_rank=cfg.kv_lora_rank, n_experts=cfg.n_experts, topk=cfg.topk,
        n_dense_layers=cfg.n_dense_layers, held=cfg.held,
        route_norm=cfg.route_norm, route_scale=cfg.route_scale,
        rope_theta=cfg.rope_theta, norm_eps=cfg.norm_eps,
        query_block=min(256, cfg.max_seq),
    )


@contextlib.contextmanager
def _planted(fault, cfg):
    """Trace the system with a fault of the latent mechanism planted in
    front of the attention dispatcher (the kernels run as they are):
    ``no_rope_product``: the rotary product left out of the score;
    ``scale_128``: the score scaled by ``1/sqrt(qk_nope_dim)``, not by
    ``1/sqrt`` of the whole width.  ``None``: the system as it stands."""
    if fault is None:
        yield
        return
    assert fault in FAULTS, fault
    from ddl_tpu.parallel import ring_attention

    real = ring_attention.attention
    wrong = ((cfg.qk_nope_dim + cfg.qk_rope_dim) / cfg.qk_nope_dim) ** 0.5

    def faulty(q, k, v, *, q_rope, k_rope, **kw):
        if fault == "no_rope_product":
            q_rope = q_rope * 0
        else:
            q, q_rope = (q * wrong).astype(q.dtype), (q_rope * wrong).astype(q.dtype)
        return real(q, k, v, q_rope=q_rope, k_rope=k_rope, **kw)

    with mock.patch.object(ring_attention, "attention", faulty):
        yield


def check_programs(cfg, compute_dtype=None, fault=None) -> dict:
    """The comparison's programs for the model ``cfg`` (or a stand-in for
    it): ``errors(stored, exact, rows)``, the two sides' forward passes on
    the same rows as sums; ``got_norms`` / ``want_norms``, the two sides'
    gradient norms (``families/afmoe.py:_tap_norms``).  Built apart from
    the arrays so that a script can compile them for a described chip."""
    import jax
    import jax.numpy as jnp

    from benchmarks.lib import deepseek_v3_reference as reference
    from ddl_tpu.models.losses import next_token_cross_entropy

    c = reference_config(cfg, reference)
    first, count = cfg.held

    def by_row(loss, logits, t):
        """(rows,): each row's own loss."""
        return jax.vmap(lambda lg, tk: loss(lg[None], tk[None]))(logits, t)

    def system(p, t):
        # One pass: the loss the model's ``next_token_loss`` takes of
        # these logits (a test holds the two together).
        with _planted(fault, cfg):
            logits, picks = model.forward_with_choices(p, t, cfg)
        return logits, by_row(next_token_cross_entropy, logits, t), picks

    def system_loss(p, t):
        with _planted(fault, cfg):
            return model.next_token_loss(p, t, cfg)

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
        }

    c_grad = c._replace(checkpoint_layers=True)

    # ``_tap_norms`` taps a layer through AFMoE's six-argument ``_layer``
    # (its fifth says whether the layer slides); this family's has five.
    def reference_layer(x, w, c, r, _sliding, dense):
        return reference._layer(x, w, c, r, dense)

    def plain_loss(dtype):
        def loss(p, t, layer_fn):
            return reference.loss(
                p, t, c_grad, dtype,
                lambda x, w, c, r, dense: layer_fn(x, w, c, r, False, dense),
            )

        return loss

    want_norms = _tap_norms(plain_loss(None), reference_layer)
    if compute_dtype is None:
        got_norms = _tap_norms(system_loss)
    else:
        got_norms = _tap_norms(plain_loss(compute_dtype), reference_layer)
    return {"errors": errors, "got_norms": got_norms, "want_norms": want_norms}


def compare_with_reference(cfg, seed: int, compute_dtype=None, fault=None) -> dict:
    """The model the window trains - ``cfg`` as it stands: every layer,
    the configured remat, the share - against the float32 reference on the
    same seeded weights (``cfg``'s storage dtype) and ``CHECK_ROWS`` seeded
    rows of ``cfg.max_seq`` tokens: logits, loss and the routers' picks of
    one forward pass ``PAIR_ROWS`` at a time, then the norm of every leaf
    of the loss gradient on the first ``GRAD_TOKENS`` tokens of the first
    row.  Stand-ins for the system, which a limit must refuse: with
    ``compute_dtype`` the reference computed in that precision; with
    ``fault`` the system with that fault planted (:func:`_planted`)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    programs = check_programs(cfg, compute_dtype, fault)
    first, count = cfg.held
    stored = jax.jit(lambda key: model.init_params(cfg, key))(
        jax.random.fold_in(jax.random.key(seed), 32)
    )
    exact = jax.tree.map(lambda x: x.astype(jnp.float32), stored)
    tokens = jnp.asarray(
        np.random.default_rng([seed, 32]).integers(
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
    """Run the comparison, say what it found, and refuse the run where it
    is outside the limits."""
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
    return found
