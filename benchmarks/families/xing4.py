"""Family adapter: Xing4.0 decoders (Xing4.0-29B-A4B) through
``ddl_tpu/models/xing4.py`` as ONE CHIP'S SHARE of a layer divided over chips
by experts: a four-stream hyper-connected residual path (two wraps a layer,
Sinkhorn-projected mixing) around latent attention with a query low-rank
step under YaRN, a leading dense layer, then a sigmoid top-k router over
every published expert in front of the experts held here plus a shared
expert; a multi-token-prediction module (one more routed layer, a second
head) in the train loss; the vocabulary's slice.

``loss_fn`` is the one hook the runner calls before the weights exist, so it
is where the system is held to the plain float32 reference
(``benchmarks/lib/xing4_reference.py``, given the same share, run a stage a
program), in every run's set-up: the configured model itself - every layer,
the module, the configured remat, bf16 - on ``CHECK_ROWS`` seeded rows of the
mix's length: the main and the module's logits where the held picks agree in
every routed layer (a position at a time), both losses, the agreeing share;
on a prefix the norm of every gradient leaf and ONE REAL OPTIMIZER STEP of
``parallel/train.py``'s window program against a plain adamw step of the
reference's gradients (a state left unchanged reads 1).  A run outside the
limits exits non-zero and prints no result.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import resource
import time
from unittest import mock

# Imported here, not inside the hooks: a checkout whose program has no such
# model refuses the cell while the runner loads it - before a device, a
# weight or a producer exists.
from ddl_tpu.models import xing4 as model

from benchmarks.families.afmoe import _tap_norms
from benchmarks.families.olmoe import _peak_gib, _seed_of_this_run
from benchmarks.lib import xing4_flops

#: This family's rate metric (its unit is BENCHMARK.json's).
RATE_METRIC = "tokens_per_s"

#: Rows of ``max_seq`` tokens the reference check compares, a step's rows at
#: a time - the batch the timed program takes (a test holds both to the mix:
#: the hook is handed the model and the mesh, not the mix).
CHECK_ROWS = 4
PAIR_ROWS = 1
#: Tokens of the first row whose loss gradient is compared leaf by leaf and
#: on which the optimizer step is taken: three of the flash kernels'
#: 1024-blocks (diagonal and interior blocks of the backward kernel), and not
#: the whole row: the check has to stay under the train step's own peak.
GRAD_TOKENS = 3072
#: Queries a block of the reference's masked softmax.
QUERY_BLOCK = 256
#: The system's stand-ins with a planted fault, which the limits must refuse
#: (:func:`_planted`); ``skipped_update`` is the optimizer step's.
FAULTS = ("no_column_step", "sinkhorn_bf16", "mtp_shift_one", "no_yarn_scale",
          "no_mtp_term", "skipped_update")
#: The gradient leaves that are zero in exact arithmetic: the FIRST wrap of a
#: stream reads four identical rows, so ``h = (sum Hpre) x`` only scales what
#: the attention's norm divides away and ``Hres X = (row sums) x = x``: the
#: ``pre`` and ``res`` parameters of ``hc_attn`` in the stack's first layer and
#: in the module's layer get rounding noise on both sides (1e-6 of their
#: neighbours' norms in float32), and a ratio of two noises is no reading.
#: They are left out of the leaf-by-leaf comparison (in bfloat16 the
#: cancellation is not exact: they read 12-17% of the median leaf's norm, my
#: chip runs, PR 46; printed as ``noise_grad_norm`` and held to nothing); the
#: optimizer step below compares every element of every leaf, these too.
NOISE_LEAVES = tuple(
    f"{where}['hc_attn']['{name}_{part}']"
    for where in ("['layers'][0]", "['mtp']['layer']")
    for name in ("alpha", "b", "phi") for part in ("pre", "res")
)
#: A wrap's ``alpha`` (one number) and ``b`` (4, 4 and 16) are each a sum of
#: signed terms over every token and channel, and a sum that nearly cancels
#: has no relative error to speak of (``b_post`` of the last wrap in front of
#: a head: its gradient is ``<dx_L, y>`` where the final norm makes ``dx_L``
#: orthogonal to ``x_L``, of which ``y`` is a part).  The 27 of a wrap are
#: pooled over all wraps into ONE leaf; ``phi`` and ``norm`` stand alone.
POOLED = "the wraps' alpha and b, pooled"


def _pooled(norms: dict) -> dict:
    """``norms`` with every wrap's ``alpha_*`` / ``b_*`` (but the
    :data:`NOISE_LEAVES`) folded into :data:`POOLED`."""
    small = [k for k in norms if "['hc_" in k and k not in NOISE_LEAVES
             and k.rsplit("['", 1)[1].startswith(("alpha_", "b_"))]
    out = {k: v for k, v in norms.items() if k not in small}
    out[POOLED] = sum(float(norms[k]) ** 2 for k in small) ** 0.5
    return out
#: Each limit below comes from two readings: the largest the configured model
#: (1 + 5 layers and the module, published widths, the share, bf16) gives on
#: the chip over its seeds (my chip runs, PR 46, TPU v5 lite: 4600000019,
#: 4600000043, 4600000079; PERF.md section 6), and what a stand-in gives that
#: has to be refused - the reference computed in float8_e4m3fn, the next
#: precision down, and the system with a fault of the new mechanisms planted
#: (``tools/probe_xing4_controls.py``; PERF.md section 6 has the stand-ins'
#: readings and says which are the chip's and which the rehearsal's).
#:
#: Logits (main and the module's together) on the tokens whose HELD picks
#: agree in every routed layer: root mean square of the differences over the
#: reference logits' root mean square.  bf16: 0.0401-0.0450 (main 0.041-0.047,
#: the module's 0.038-0.043; the median position 0.038-0.044, the worst
#: 0.12-0.17; five seeds).  float8 (chip, seed 4600000101): 0.382.  YaRN's scale
#: left out: 1.04.
LOGITS_RMS_LIMIT = 0.1
#: |loss - reference loss| / reference loss of a row, over ALL its tokens,
#: the larger of the two losses' (main, module); the largest of the rows.
#: bf16: 1.4e-4-3.7e-4 (twenty rows, five seeds).  float8 5.1e-4 (INSIDE: the
#: next precision down is refused by the logits and the agreement, not by
#: this).  YaRN's scale left out 2.2e-3; the module's targets one ahead 3.5e-3.
#: The limit stands 2.7 times over the largest sound reading and 2.2 under the
#: nearest fault's.
LOSS_REL_LIMIT = 1.0e-3
#: Share of tokens whose held picks must agree with the reference's in every
#: routed layer of the stack (five).  bf16: 0.828 (at 1 + 6 layers), 0.874-0.883
#: (four seeds).  float8 0.273.  YaRN's scale left out 0.019.
MIN_AGREE_SHARE = 0.6
#: | |g| - |g_ref| | / |g_ref|, the worst leaf of d loss / d parameters on the
#: prefix (``jax.grad`` of the train loss under the configured remat against
#: the reference's gradients), ``NOISE_LEAVES`` and the leaves without a
#: gradient on either side (``expert_bias``, a share's ``w_router``) left out.
#: bf16: 4.2-8.3% over five seeds, the wraps' pooled ``alpha`` and ``b`` in every
#: one; the median leaf 0.25-0.30%.  The stand-ins' gradient halves were not read
#: on the chip (the budget ended); at the rehearsal's size float8 reads over
#: 100%, YaRN's scale left out 46%, the module's targets one ahead 25%.
GRAD_NORM_LIMIT = 0.2
#: How far the system's ``Hres`` is from doubly stochastic at the check's
#: weights, the largest over the wraps and tokens of |a row's sum - 1| and of
#: |a column's sum - 1|.  The rows are the rounds' last step: float32 rounding
#: (1.2e-6), where rounds in bfloat16 leave ~4e-3.  The columns are what 20
#: rounds leave of a stirred wrap's gap (near the identity a round closes 13%
#: of it): 0.0138-0.0157 on the chip; without the column step they stay where
#: ``exp(Z)`` put them, 0.356; rounds in bfloat16 leave the rows 6.0e-3 off.
#: Logits and losses cannot tell either fault at bfloat16's noise: the
#: streams of a seeded model differ by a few percent, so ``Hres X`` is ``(row
#: sums) x`` to that share whatever the columns do.
HRES_ROW_LIMIT = 1e-4
HRES_COL_LIMIT = 0.05
#: ONE REAL OPTIMIZER STEP (``families/lfm2_moe.py:UPDATE_REL_LIMIT`` has the
#: reasoning: adamw's first step is the gradient's sign times 3e-5 on weights
#: stored in bfloat16; a state left unchanged reads exactly 1).  bf16:
#: 0.536-0.569 (five seeds) at sign agreement 0.918-0.926 (2 sqrt(1 - agreement): 0.55) and
#: ``update_norm_ratio`` 1.0001.  Rehearsal (its own 0.36-0.43): float8 1.39, the
#: module's targets one ahead 0.77, YaRN's scale left out 1.03.
UPDATE_REL_LIMIT = 0.75
#: On the CPU (a rehearsal at the cell's tiny sizes) one flipped choice is a
#: far larger share of the model; a rehearsal rehearses the control flow.
REHEARSAL = {
    "LOGITS_RMS_LIMIT": 0.12, "LOSS_REL_LIMIT": 6e-3, "MIN_AGREE_SHARE": 0.5,
    "GRAD_NORM_LIMIT": 0.3, "UPDATE_REL_LIMIT": 0.75,
    "HRES_ROW_LIMIT": 1e-4, "HRES_COL_LIMIT": 0.05,
}


def limit(name: str, rehearsal: bool) -> float:
    return REHEARSAL[name] if rehearsal else globals()[name]


def sizes(c: dict, mix: dict) -> dict:
    """What the traffic generator needs to know of the model: ids are drawn
    from the vocabulary's slice."""
    return {"seq": mix["seq"], "vocab": c["vocab_size"]}


def samples_per_row(c: dict, mix: dict) -> int:
    return mix["seq"]


def flops_per_sample(c: dict, mix: dict) -> float:
    return xing4_flops.xing4_flops_per_token(c, mix["seq"])


def model_config(c: dict, mix: dict):
    import jax.numpy as jnp

    from ddl_tpu.config import TrainConfig

    t, y = c["training"], c["rope_scaling"]
    if (c["scoring_func"], c["topk_method"], c["n_group"], c["topk_group"]) != (
        "sigmoid", "noaux_tc", 1, 1
    ) or c["moe_layer_freq"] != 1:
        raise ValueError(
            "models/xing4.py routes by sigmoid scores under a selection bias, "
            "one group, every layer behind the dense ones routed"
        )
    if y["type"] != "yarn" or c["tie_word_embeddings"] or c["attention_bias"]:
        raise ValueError("models/xing4.py: YaRN, an untied head, no attention bias")
    router = c["published"]["n_routed_experts"]
    held = (c["deployment"]["first_expert"], c["n_routed_experts"])
    return TrainConfig(remat=t["remat"]).model_config(model.Xing4Config(
        vocab=c["vocab_size"], d_model=c["hidden_size"],
        n_layers=c["num_hidden_layers"], n_heads=c["num_attention_heads"],
        qk_nope_dim=c["qk_nope_head_dim"], qk_rope_dim=c["qk_rope_head_dim"],
        v_head_dim=c["v_head_dim"], kv_lora_rank=c["kv_lora_rank"],
        q_lora_rank=c["q_lora_rank"], d_ff=c["intermediate_size"],
        d_expert=c["moe_intermediate_size"], n_experts=router,
        topk=c["num_experts_per_tok"], n_shared_experts=c["n_shared_experts"],
        n_dense_layers=c["first_k_dense_replace"], route_norm=c["norm_topk_prob"],
        route_scale=float(c["routed_scaling_factor"]),
        held_experts=None if held == (0, router) else held,
        max_seq=mix["seq"], rope_theta=float(c["rope_theta"]),
        rope_scaling=model.Yarn(
            float(y["factor"]), y["original_max_position_embeddings"],
            float(y["beta_fast"]), float(y["beta_slow"]), float(y["mscale"]),
            float(y["mscale_all_dim"])),
        norm_eps=c["rms_norm_eps"], hc_mult=c["hc_mult"],
        hc_sinkhorn_iters=c["hc_sinkhorn_iters"], hc_eps=c["hc_eps"],
        hc_clamp=(float(c["mhc_h_res_clamp_min"]), float(c["mhc_h_res_clamp_max"])),
        n_mtp=c["num_nextn_predict_layers"],
        param_dtype=jnp.dtype(t["param_dtype"]), attn_impl=t["attn_impl"],
    ))


def init_params(cfg, key):
    return model.init_params(cfg, key)


def param_specs(cfg):
    return model.param_specs(cfg)


def loss_fn(cfg, mesh):
    """The train loss over the loader's column tuple.  Where the mesh's
    devices are attached - not ``aot.py``'s described ones, on which nothing
    can run - the reference check runs first."""
    import jax

    if mesh.devices.flat[0] in jax.devices():
        reference_check(cfg, _seed_of_this_run())
    on_mesh = mesh if mesh.devices.size > 1 else None
    return lambda p, b: model.next_token_loss(p, b[0], cfg, mesh=on_mesh)


def stirred(params, key):
    """``params`` with every wrap moved off its start: ``alpha`` 0.3 where a
    fresh wrap has 0.01, the biases and the norm's weight perturbed.  A fresh
    wrap is the plain residual to a hundredth - its streams stay equal, ``Hres``
    is the identity whatever the rounds do, and a fault in the mixing cannot
    be seen - so the check compares the configured model at weights where
    the mechanism works: the same program, other numbers in 27 + 14,336 of a
    wrap's parameters."""
    import jax

    def wrap_off(path, x):
        name = jax.tree_util.keystr(path)
        if "['hc_" not in name:
            return x
        leaf = name.rsplit("['", 1)[1]
        if leaf.startswith("alpha_"):
            return 30.0 * x
        if leaf.startswith("phi_"):
            return x
        k = jax.random.fold_in(key, sum(map(ord, name)) + 131 * len(name))
        by = 0.1 if leaf.startswith("norm") else 0.3
        return x + by * jax.random.normal(k, x.shape, x.dtype)

    return jax.tree_util.tree_map_with_path(wrap_off, params)


def reference_config(cfg, reference):
    yarn = cfg.rope_scaling
    return reference.Config(
        n_heads=cfg.n_heads, qk_nope_dim=cfg.qk_nope_dim,
        qk_rope_dim=cfg.qk_rope_dim, v_head_dim=cfg.v_head_dim,
        kv_lora_rank=cfg.kv_lora_rank, n_experts=cfg.n_experts, topk=cfg.topk,
        n_dense_layers=cfg.n_dense_layers, held=cfg.held,
        yarn=None if yarn is None else tuple(yarn), route_norm=cfg.route_norm,
        route_scale=cfg.route_scale, rope_theta=cfg.rope_theta,
        norm_eps=cfg.norm_eps, hc_mult=cfg.hc_mult,
        hc_iters=cfg.hc_sinkhorn_iters, hc_eps=cfg.hc_eps, hc_clamp=cfg.hc_clamp,
        n_mtp=cfg.n_mtp, mtp_weight=model.MTP_LOSS_WEIGHT,
        query_block=min(QUERY_BLOCK, cfg.max_seq),
    )


@contextlib.contextmanager
def _planted(fault):
    """Trace the system with a fault of the new mechanisms planted in front
    of the routines the timed step runs (they run as they are):
    ``no_column_step``: Sinkhorn's rounds normalise the rows only;
    ``sinkhorn_bf16``: the rounds computed in bfloat16; ``mtp_shift_one``:
    the module's targets one ahead, not two; ``no_yarn_scale``: YaRN's
    ``mscale^2`` left out of the score's scale; ``no_mtp_term``: the module's
    cross-entropy dropped from the loss.  ``None`` and ``skipped_update``
    (planted in the step of :func:`check_programs`' ``update``): the system as
    it stands."""
    if fault in (None, "skipped_update"):
        yield
        return
    assert fault in FAULTS, fault
    import jax.numpy as jnp

    from ddl_tpu.models import hyper_connections as hc
    from ddl_tpu.parallel import ring_attention

    real_sinkhorn, real_attention = hc.sinkhorn, ring_attention.attention

    def rows_only(M, iters, eps):
        for _ in range(iters):
            M = M / (jnp.sum(M, axis=2, keepdims=True) + eps)
        return M

    def in_bf16(M, iters, eps):
        return real_sinkhorn(M.astype(jnp.bfloat16), iters, eps).astype(jnp.float32)

    real_targets = model._mtp_targets

    def one_ahead(tokens):
        return jnp.roll(tokens, -1, axis=1), real_targets(tokens)[1]

    def sinkhorn_as(wrong):
        # The passes that run the rounds are jitted by name and JAX keeps a
        # jitted function's traces: the fault is traced through the bare ones.
        stack = contextlib.ExitStack()
        stack.enter_context(mock.patch.object(hc, "sinkhorn", wrong))
        for name in ("_hc_matrices", "_hc_pre_bwd"):
            stack.enter_context(
                mock.patch.object(hc, name, getattr(hc, name).__wrapped__))
        return stack

    patch = {
        "no_column_step": lambda: sinkhorn_as(rows_only),
        "sinkhorn_bf16": lambda: sinkhorn_as(in_bf16),
        "mtp_shift_one": lambda: mock.patch.object(model, "_mtp_targets", one_ahead),
        "no_yarn_scale": lambda: mock.patch.object(
            ring_attention, "attention",
            lambda *a, score_scale=1.0, **kw: real_attention(*a, **kw)),
        "no_mtp_term": lambda: mock.patch.object(model, "MTP_LOSS_WEIGHT", 0.0),
    }[fault]
    with patch():
        yield


def _learning_rate() -> float:
    """The cell's: ``benchmarks/run.py`` builds ``optax.adamw`` from the
    configuration's ``training`` and leaves every other default."""
    from benchmarks.lib import cells

    with open(os.path.join(cells.HERE, "configs", "xing4.0-29b-a4b.json")) as f:
        return json.load(f)["training"]["learning_rate"]


def check_programs(cfg, compute_dtype=None, fault=None) -> dict:
    """The comparison's programs for the model ``cfg`` (or a stand-in for
    it): ``errors(stored, rows)``: the two sides' forward passes on the same
    rows, as sums a position; ``got_norms``: the system's gradient norms
    (``families/afmoe.py:_tap_norms``); ``update(again, row)``: one optimizer
    step of the train loop's own program from the weights ``again()`` makes
    (consumed, and made anew) against a plain adamw step of the reference's
    gradients, and the reference's gradient norms.  Built apart from the
    arrays so that a script can compile them for a described chip."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from benchmarks.lib import xing4_reference as reference
    from ddl_tpu.models import hyper_connections as hc
    from ddl_tpu.models.losses import cross_entropy, next_token_cross_entropy
    from ddl_tpu.parallel.mesh import make_mesh
    from ddl_tpu.parallel.train import make_multistep

    c = reference_config(cfg, reference)
    first, count = cfg.held
    exact = lambda *xs: tuple(x.astype(jnp.float32) for x in xs)
    # A checkpoint changes no forward value, and the wraps' matrices cannot
    # be read out of one: the forward comparison walks the layers bare.
    bare = dataclasses.replace(cfg, remat="none")

    def by_row(loss, logits, t):
        """(rows,): each row's own loss."""
        return jax.vmap(lambda lg, tk: loss(lg[None], tk[None]))(logits, t)

    def mtp_loss(logits, t):
        return cross_entropy(logits, *model._mtp_targets(t))

    def system_loss(p, t):
        with _planted(fault):
            return model.next_token_loss(p, t, cfg)

    @jax.jit
    def system_forward(stored, t):
        # One pass: the losses the model's ``next_token_loss`` takes of these
        # logits (a test holds the two together), and how far the wraps'
        # Hres is from doubly stochastic.
        off, real_post = [], hc.hc_post

        def spy(X, y, post, res):
            off.append(jnp.stack([
                jnp.max(jnp.abs(jnp.sum(res, axis=2) - 1.0)),  # a row's sum
                jnp.max(jnp.abs(jnp.sum(res, axis=1) - 1.0)),  # a column's
            ]))
            return real_post(X, y, post, res)

        with _planted(fault), mock.patch.object(hc, "hc_post", spy):
            logits, mtp_logits, picks = model.forward_all(stored, t, bare)
            loss = by_row(next_token_cross_entropy, logits, t)
            module_loss = by_row(mtp_loss, mtp_logits, t)
        return (logits, mtp_logits, loss, module_loss, picks,
                jnp.max(jnp.stack(off), axis=0))

    def held_only(picks):
        """Each token's held picks, sorted; a pick held elsewhere is -1."""
        mine = (picks >= first) & (picks < first + count)
        return jnp.sort(jnp.where(mine, picks, -1), -1), mine

    @jax.jit
    def sums(got, got_mtp, got_picks, want, want_mtp, want_picks, t):
        """``got_mtp`` keeps the row's length (its last two positions have no
        target), ``want_mtp`` is one position short; the module's picks are
        the last of the layers'."""
        T = t.shape[1]
        got_held, mine = held_only(got_picks)
        want_held, _ = held_only(want_picks)
        agree = jnp.all(got_held == want_held, axis=-1)  # (layers, rows, seq)
        same = jnp.all(agree[:-1], axis=0)  # the stack's layers: (rows, seq)
        counts = (jnp.arange(T) < T - 2)[None, :]
        same_mtp = (same & agree[-1] & counts)[:, : T - 2]

        def parts(got, want, same):
            diff2 = jnp.where(same[..., None], (got - want) ** 2, 0.0)
            want2 = jnp.where(same[..., None], want**2, 0.0)
            return jnp.sum(diff2, axis=-1), jnp.sum(want2, axis=-1), jnp.max(diff2)

        diff2, want2, worst = parts(got, want, same)
        mdiff2, mwant2, mworst = parts(
            got_mtp[:, : T - 2], want_mtp[:, : T - 2], same_mtp)
        return {
            # (rows, seq): a position's sums over the vocabulary, zero where
            # the held picks differ
            "same": same, "diff2": diff2, "want2": want2,
            "same_mtp": same_mtp, "mtp_diff2": mdiff2, "mtp_want2": mwant2,
            "diff2_max": jnp.maximum(worst, mworst),
            "reference_loss": by_row(reference.cross_entropy, want, t),
            "reference_mtp_loss": by_row(reference.mtp_cross_entropy, want_mtp, t),
            # (routed layers, the module's last): the system's choices that
            # fell on held experts
            "held": jnp.sum(mine, axis=(1, 2, 3)),
        }

    def a_layer_at_a_time(X, layer, c, r, dense):
        # the host does not run ahead of the device by more than a layer:
        # arrays queued behind it would all be alive at once
        return jax.block_until_ready(reference._layer(X, layer, c, r, dense))

    def errors(stored, t):
        """The system (or its stand-in) against the float32 reference on the
        rows ``t``, as sums.  The system's forward pass is one program; the
        reference runs EAGERLY, a stage a program (its docstring).  Both read
        the STORED weights, so that the arrays alive here stay under the train
        state's own bytes: the check must not set the run's
        ``memory_peak_bytes``."""
        want, want_mtp, want_picks = reference.forward_all(
            stored, t, c, None, a_layer_at_a_time)
        # The reference's two sets of logits (0.5 GiB each) wait on the host
        # while the system's are made: the check's live arrays stay under the
        # train state's, or the run's ``memory_peak_bytes`` would be the check's.
        want, want_mtp = jax.device_get((want, want_mtp))
        off = None
        if compute_dtype is None:
            got, got_mtp, got_loss, got_mtp_loss, got_picks, off = system_forward(stored, t)
        else:
            got, got_mtp, got_picks = reference.forward_all(
                stored, t, c, compute_dtype, a_layer_at_a_time)
            got_loss = by_row(reference.cross_entropy, got, t)
            got_mtp_loss = by_row(reference.mtp_cross_entropy, got_mtp, t)
            # the reference's module is one position short, its pick there -1
            got_mtp = jnp.pad(got_mtp, ((0, 0), (0, 1), (0, 0)))
        out = {"loss": got_loss, "mtp_loss": got_mtp_loss,
               **sums(got, got_mtp, got_picks, want, want_mtp, want_picks, t)}
        if off is not None:
            out["hres_off"] = off
        return out

    # -- the gradients and one optimizer step ----------------------------------------
    c_grad = c._replace(checkpoint_layers=True)

    # ``_tap_norms`` taps a layer through AFMoE's six-argument ``_layer`` (its
    # fifth says whether the layer slides); this family's has five.
    def reference_layer(X, w, c, r, _sliding, dense):
        return reference._layer(X, w, c, r, dense)

    def tapped_plain_loss(p, t, layer_fn):
        return reference.loss(
            p, t, c_grad, compute_dtype,
            lambda X, w, c, r, dense: layer_fn(X, w, c, r, False, dense))

    if compute_dtype is None:
        got_loss, got_norms = system_loss, _tap_norms(system_loss)
    else:
        got_loss = lambda p, t: reference.loss(p, t, c_grad, compute_dtype)
        got_norms = _tap_norms(tapped_plain_loss, reference_layer)

    lr = _learning_rate()
    optimizer = optax.adamw(lr)
    if fault == "skipped_update":
        optimizer = optax.chain(optimizer, optax.scale(0.0))
    # The program ``Trainer.fit(window_stream=True)`` runs a window with
    # (``trainer.py:_fit_windows``), one step long; undonated on the CPU, as
    # there.
    init_state, step = make_multistep(
        lambda p, b: got_loss(p, b[0]), optimizer,
        make_mesh({"dp": 1}, devices=jax.devices()[:1]), model.param_specs(cfg),
        n_steps=1, donate=jax.default_backend() != "cpu",
    )

    def stored_as(x, dtype):
        """``x`` (float32) rounded to the storage ``dtype`` and back, by an op
        XLA keeps (``families/lfm2_moe.py`` has why)."""
        info = jnp.finfo(dtype)
        return jax.lax.reduce_precision(x, info.nexp, info.nmant)

    def plain_adamw(p, g):
        """adamw's FIRST step written out in float32, optax's defaults: the
        moments start from zero, so their bias corrections cancel and the
        step is ``g / (|g| + eps)`` plus the decay."""
        p32, g = exact(p, g)
        change = g / (jnp.abs(g) + 1e-8) + 1e-4 * p32
        return stored_as(p32 - lr * change, p.dtype)

    @jax.jit
    def part_sums(before, moved, grads):
        """Of one part of the model (a layer, or what stands beside the
        stack), from the reference's gradients at ``before``, a row a leaf:
        the squares of the two changes' difference, of the plain step's change
        and of the system's; the elements the plain step moves and those of
        them the system moves the same way; then each leaf's gradient norm."""

        def leaf(p, new, g):
            want = plain_adamw(p, g)
            p, new = exact(p, new)
            got, want = new - p, want - p
            moves = want != 0
            return jnp.stack([
                jnp.sum((got - want) ** 2), jnp.sum(want**2), jnp.sum(got**2),
                jnp.sum(moves), jnp.sum(moves & (got * want > 0)),
            ])

        sums = jnp.stack(jax.tree.leaves(jax.tree.map(leaf, before, moved, grads)))
        return sums, jax.tree.map(lambda g: jnp.sqrt(jnp.sum(exact(g)[0] ** 2)), grads)

    def update(again, row):
        """The reference's gradients come a layer at a time
        (``reference.loss_and_grads_by_layer``: the whole backward pass as one
        float32 program took the chip's host past its 40 GiB while it compiled,
        my chip run, PR 46) and each part is reduced as it comes: no gradient
        tree is ever whole."""
        state = init_state(again())
        state, _ = step(state, (row[None],), per_step=True)
        # on the host, a part at a time back: beside ``before``, the layers'
        # float32 inputs and a part's gradients a second copy of the model
        # would pass the train state's own bytes
        moved = jax.device_get(state.params)
        del state
        before = again()
        total, norms = np.zeros(5), {}

        def consume(where, grads):
            nonlocal total
            if where == ("top",):
                prefix = ""
                mine = lambda tree: {k: v for k, v in tree.items() if k != "layers"}
            else:
                prefix = f"['layers'][{where[1]}]"
                mine = lambda tree: tree["layers"][where[1]]
            sums, part = jax.device_get(part_sums(mine(before), mine(moved), grads))
            total += sums.astype(np.float64).sum(axis=0)
            for path, norm in jax.tree_util.tree_leaves_with_path(part):
                norms[prefix + jax.tree_util.keystr(path)] = float(norm)

        reference.loss_and_grads_by_layer(before, row, c_grad, consume)
        return total, norms

    return {"errors": errors, "got_norms": got_norms, "update": update}


#: What a comparison is made of (:func:`compare_with_reference`'s ``parts``).
PARTS = ("forward", "gradients")


def compare_with_reference(cfg, seed: int, compute_dtype=None, fault=None,
                           parts=PARTS) -> dict:
    """The model the window trains - ``cfg`` as it stands: every layer, the
    module, the configured remat, the share - against the float32 reference
    on the same seeded weights (``cfg``'s storage dtype, the wraps moved off
    their start: :func:`stirred`) and ``CHECK_ROWS``
    seeded rows of ``cfg.max_seq`` tokens: logits, losses and the routers'
    picks of one forward pass ``PAIR_ROWS`` at a time, then on the first
    ``GRAD_TOKENS`` tokens of the first row the norm of every leaf of the
    loss gradient and one optimizer step.  Stand-ins for the system, which a
    limit must refuse: with ``compute_dtype`` the reference computed in that
    precision; with ``fault`` the system with that fault planted
    (:func:`_planted`)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    programs = check_programs(cfg, compute_dtype, fault)
    init = jax.jit(lambda key: stirred(model.init_params(cfg, key), key))
    again = lambda: init(jax.random.fold_in(jax.random.key(seed), 46))
    tokens = jnp.asarray(
        np.random.default_rng([seed, 46]).integers(
            0, cfg.vocab, (CHECK_ROWS, cfg.max_seq), dtype=np.int32
        )
    )
    out = {}
    if "forward" in parts:
        out.update(compare_forward(cfg, programs, again(), tokens))
    if "gradients" in parts:
        row = tokens[:1, : min(GRAD_TOKENS, cfg.max_seq)]
        out.update(compare_gradients(programs, again, row))
    return out


def compare_forward(cfg, programs, stored, tokens) -> dict:
    """Logits, losses and picks of ``tokens``, a step's rows at a time."""
    import jax
    import numpy as np

    pairs = [
        jax.device_get(programs["errors"](stored, tokens[lo : lo + PAIR_ROWS]))
        for lo in range(0, CHECK_ROWS, PAIR_ROWS)
    ]
    join = lambda key: np.concatenate([p[key] for p in pairs]).astype(np.float64)
    same, same_mtp = join("same") > 0, join("same_mtp") > 0
    diff2, want2 = join("diff2"), join("want2")
    mdiff2, mwant2 = join("mtp_diff2"), join("mtp_want2")
    # Where no token agrees there is nothing to compare: the ratios read 1
    # (the agreeing share, 0, is what refuses such a run).
    ratio = lambda num, den: float(np.sqrt(num / den)) if den > 0 else 1.0
    by_position = np.sqrt(diff2[same] / want2[same]) if same.any() else np.ones(1)
    held = sum(p["held"] for p in pairs).astype(np.float64)  # (routed layers,)
    choices = tokens.size * cfg.topk  # a layer
    rel = lambda got, want: float(np.max(np.abs(join(got) - join(want)) / join(want)))
    out = {
        "agree_share": float(same.mean()),
        "mtp_agree_share": float(same_mtp.mean()),
        # the two heads' logits together, then each alone
        "logits_rel_rms": ratio(diff2.sum() + mdiff2.sum(), want2.sum() + mwant2.sum()),
        "main_logits_rel_rms": ratio(diff2.sum(), want2.sum()),
        "mtp_logits_rel_rms": ratio(mdiff2.sum(), mwant2.sum()),
        # an agreeing position's own (the main head's): the median and the worst
        "logits_rel_rms_median_position": float(np.median(by_position)),
        "logits_rel_rms_worst_position": float(np.max(by_position)),
        # the worst element against an element's rms
        "logits_rel_max": ratio(
            max(float(p["diff2_max"]) for p in pairs) * cfg.vocab * same.sum(),
            want2.sum()),
        "loss": join("loss").tolist(), "reference_loss": join("reference_loss").tolist(),
        "mtp_loss": join("mtp_loss").tolist(),
        "reference_mtp_loss": join("reference_mtp_loss").tolist(),
        "loss_rel_diff": max(rel("loss", "reference_loss"),
                             rel("mtp_loss", "reference_mtp_loss")),
        "held_choice_share": float(held.sum() / (choices * max(len(held), 1))),
        "held_choice_share_by_layer": [float(h / choices) for h in held],
        "balanced_held_share": cfg.held[1] / cfg.n_experts,
    }
    if "hres_off" in pairs[0]:
        off = np.max([p["hres_off"] for p in pairs], axis=0)
        out["hres_row_sum_off"], out["hres_col_sum_off"] = float(off[0]), float(off[1])
    return out


def compare_gradients(programs, again, row) -> dict:
    """On the prefix ``row``: every gradient leaf's norm against the
    reference's, and one optimizer step of the train loop's program against
    a plain adamw step of the reference's gradients.  ``again()`` makes the
    stored weights, a program's own each time: the step consumes its."""
    import numpy as np

    got_norms = _pooled(programs["got_norms"](again(), row))
    (diff2, want2, got2, moved, same), want_norms = programs["update"](again, row)
    want_norms = _pooled(want_norms)
    # The leaves the reference gives no gradient: expert_bias (selection
    # only) and, in a share, the router (not trained by a share).
    frozen = [k for k, w in want_norms.items() if w == 0.0]
    rel = {
        k: abs(float(got_norms[k]) - float(w)) / float(w)
        for k, w in want_norms.items() if k not in frozen and k not in NOISE_LEAVES
    }
    worst = max(rel, key=rel.get)
    typical = float(np.median([want_norms[k] for k in rel]))
    return {
        "grad_tokens": int(row.shape[1]), "grad_leaves": len(rel),
        "grad_norm_rel_diff": rel[worst], "grad_norm_worst_leaf": worst,
        "grad_norm_rel_diff_median": float(np.median(list(rel.values()))),
        "frozen_leaves": len(frozen),
        "frozen_grad_norm": max([float(got_norms[k]) for k in frozen] or [0.0]),
        # the first wraps' pre / res parameters: noise over a typical leaf
        "noise_grad_norm": max(
            [max(float(got_norms[k]), float(want_norms[k])) for k in NOISE_LEAVES
             if k in want_norms] or [0.0]) / typical,
        # | change - plain change | / | plain change |: 1 where nothing moved
        "update_rel_diff": float(np.sqrt(diff2 / want2)),
        "update_norm_ratio": float(np.sqrt(got2 / want2)),
        "update_sign_agreement": float(same / max(moved, 1.0)),
    }


def problems_of(found: dict, rehearsal: bool) -> list:
    """What of a comparison is outside the limits."""
    at_most = [
        ("logits_rel_rms", "LOGITS_RMS_LIMIT", "the two heads' logits differ "
         "from the reference's by this share of their rms"),
        ("loss_rel_diff", "LOSS_REL_LIMIT", "a row's loss (main or the "
         "module's) differs from the reference's"),
        ("grad_norm_rel_diff", "GRAD_NORM_LIMIT", "the worst gradient leaf "
         "differs in norm"),
        ("hres_row_sum_off", "HRES_ROW_LIMIT", "a row of Hres sums to 1 but for"),
        ("hres_col_sum_off", "HRES_COL_LIMIT", "a column of Hres sums to 1 but for"),
        ("update_rel_diff", "UPDATE_REL_LIMIT", "one optimizer step's change of "
         "the parameters differs from a plain adamw step of the reference's "
         "gradients by this share of its norm (1: nothing moved)"),
    ]
    problems = [
        f"{what}: {found[key]:.4g}"
        + (f" ({found['grad_norm_worst_leaf']})" if key == "grad_norm_rel_diff" else "")
        + f", limit {limit(name, rehearsal):.4g}"
        for key, name, what in at_most
        if key in found and not found[key] <= limit(name, rehearsal)
    ]
    floor = limit("MIN_AGREE_SHARE", rehearsal)
    if "agree_share" in found and not found["agree_share"] >= floor:
        problems.append(
            f"only {found['agree_share']:.3f} of the tokens pick the same held "
            f"experts, floor {floor}"
        )
    if found.get("frozen_grad_norm", 0.0) != 0.0:
        problems.append(
            "a leaf the reference gives no gradient (expert_bias, a share's "
            "router) has one in the system"
        )
    return problems


def reference_check(cfg, seed: int) -> dict:
    """Run the comparison, say what it found, and refuse the run where it is
    outside the limits."""
    from benchmarks.lib import hostproc

    import jax

    t0 = time.monotonic()
    found = compare_with_reference(cfg, seed)
    problems = problems_of(found, rehearsal=jax.default_backend() == "cpu")
    print(json.dumps({
        "line": "reference_check",
        "at_s": round(hostproc.seconds_since_process_start(), 2),
        "seed": seed, "rows": CHECK_ROWS, "seq": cfg.max_seq,
        "layers": cfg.n_layers, "mtp": cfg.n_mtp, "remat": str(cfg.remat),
        "held": list(cfg.held), "seconds": round(time.monotonic() - t0, 2),
        "peak_GiB": _peak_gib(), "host_peak_GiB": round(
            resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2**20, 2),
        **found,
        "problems": problems,
    }), flush=True)
    if problems:
        raise SystemExit(
            "the system is not the float32 reference: " + "; ".join(problems)
        )
    return found
