"""Family adapter: LFM2-MoE decoders (LFM2-24B-A2B) through
``ddl_tpu/models/lfm2_moe.py`` as ONE CHIP'S SHARE of a layer divided over
chips by experts: gated short convolutions three layers to one of 64-wide
grouped-query attention (32 query heads over 8 key heads, per-head QK-norm
in front of RoPE), a leading dense layer, then a sigmoid top-k router over
every published expert in front of the experts held here, no shared expert;
the vocabulary's slice under a tied head.

``loss_fn`` is the one hook the runner calls before the weights exist, so
it is where the system is held to the plain float32 reference
(``benchmarks/lib/lfm2_moe_reference.py``, given the same share, run a
stage a program), in every run's set-up: the configured model itself -
every layer, the configured remat, bf16 - on ``CHECK_ROWS`` seeded rows of
the mix's length: logits where the held picks agree in every expert layer
(a position at a time: the worst position is printed), the loss, the
agreeing share; on a prefix the norm of every gradient leaf and ONE REAL
OPTIMIZER STEP of ``parallel/train.py``'s window program against a plain
adamw step of the reference's gradients (a state left unchanged reads 1).
A run outside the limits exits non-zero and prints no result.
"""

from __future__ import annotations

import contextlib
import json
import os
import time
from unittest import mock

# Imported here, not inside the hooks: a checkout whose program has no
# such model refuses the cell while the runner loads it - before a device,
# a weight or a producer exists.
from ddl_tpu.models import lfm2_moe as model

from benchmarks.families.afmoe import _tap_norms
from benchmarks.families.olmoe import _peak_gib, _seed_of_this_run
from benchmarks.lib import lfm2_flops

#: This family's rate metric (its unit is BENCHMARK.json's).
RATE_METRIC = "tokens_per_s"

#: Rows of ``max_seq`` tokens the reference check compares: the
#: ``tokens-8k`` mix's window, a step's rows at a time - the batch the
#: timed program takes (a test holds both to the mix: the hook is handed
#: the model and the mesh, not the mix).
CHECK_ROWS = 4
PAIR_ROWS = 2
#: Tokens of the first row whose loss gradient is compared leaf by leaf and
#: on which the optimizer step is taken: three of the flash kernels'
#: 1024-blocks, so that the backward kernels run their diagonal and their
#: interior blocks, and not the whole row: the reference's attention
#: probabilities are 32 heads x T x T float32 a layer (held a query block
#: at a time, ``checkpoint_layers``), and the check has to stay under the
#: train step's own peak, or the run's ``memory_peak_bytes`` would be the
#: check's.
GRAD_TOKENS = 3072
#: Queries a block of the reference's masked softmax.
QUERY_BLOCK = 256
#: bf16's unit roundoff: 8 bits of mantissa.
U_BF16 = 2.0**-9
#: The system's stand-ins with a planted fault, which the limits must
#: refuse (:func:`_planted`); ``skipped_update`` is the optimizer step's.
FAULTS = ("taps_shifted", "no_c_gate", "untied_head", "skipped_update")
#: Each limit below comes from two readings of the configured model (1 + 8
#: layers, published widths, the share, the tied head) on the chip (my chip
#: runs, PR 43, TPU v5 lite, thirteen seeds: 3000000019, 3900000811,
#: 2654435769, 4300000043, 4300000101 ... 4300000923; PERF.md section 6): the largest the
#: system at its configured bf16 gives over the seeds, and what a stand-in
#: gives that has to be refused - the reference computed in float8_e4m3fn,
#: the next precision down, and the system with the taps shifted by one
#: position (a gated input from the future), with the C gate dropped, or
#: with the head untied from the embedding (``tools/probe_lfm2_controls.py``,
#: seed 2654435769).  (The reference "computed in bfloat16" is no stand-in on
#: the chip: XLA removes a float32 -> bfloat16 -> float32 pair of converts
#: inside a program, so ``_rounder(bfloat16)`` rounds nothing there; it read
#: 10.3 u and 0.950 below, inside every limit, as bfloat16 should.)
#:
#: Logits on the tokens whose HELD picks agree in every layer: root mean
#: square of the differences over the reference logits' root mean square.
#: bf16: 20.3-21.7 u (0.0397-0.0423; the median POSITION 12.6-12.7 u, the
#: worst 0.30-0.39: a few positions carry the mean).  float8: 133 u (0.260).
#: Taps shifted, C gate dropped, head untied: 1.41 each.  Three times the
#: other share cells' 5-8 u: eight routed layers whose every flipped choice
#: among the 56 experts held elsewhere moves nothing here but is a different
#: token downstream, no norm behind a mixer, and a cubic gate (PERF.md
#: section 7).
LOGITS_RMS_LIMIT = 0.1
#: |loss - reference loss| / reference loss of a row, over ALL its
#: tokens; the largest of the rows.  bf16: 4.0e-5-1.40e-4 (52 rows).
#: float8: 6.3e-4.  Taps shifted: 3.6e-3.  C gate dropped: 1.9e-3.  Head
#: untied: 4.3e-3.
LOSS_REL_LIMIT = 3.0e-4
#: Share of tokens whose held picks must agree with the reference's in
#: every expert layer (eight of them).  bf16: 0.853-0.866.  float8: 0.299.
#: Taps shifted, C gate dropped: 0.0002.  (Head untied: 0.856, the picks do
#: not read the head; refused by the four other limits.)
MIN_AGREE_SHARE = 0.6
#: | |g| - |g_ref| | / |g_ref|, the worst leaf of d loss / d parameters
#: on the prefix (``jax.grad`` of the train loss under the configured
#: remat against ``jax.grad`` of the reference).  ``expert_bias`` and, in
#: a share, ``w_router`` have no gradient on either side and are left out.
#: bf16: 1.0-2.8%, a ``k_norm`` or ``q_norm`` in every seed (64 numbers that
#: see every head of a layer); the median leaf 0.17-0.18%.  float8: 100% (the
#: cotangents underflow).  Taps shifted: 20.9%.  C gate dropped: 52.8%.
#: Head untied: 41.7%.  A norm does not see a direction (PERF.md section
#: 7): the optimizer step below does.
GRAD_NORM_LIMIT = 0.08
#: ONE REAL OPTIMIZER STEP of the program the Trainer runs a window with
#: (``parallel/train.py:make_multistep``, adamw as the cell builds it, one
#: step long, on the gradient's prefix) from the seeded weights, against a
#: plain float32 adamw step of the REFERENCE's gradients rounded to the
#: storage dtype: | change - plain change | / | plain change | over all
#: parameters.  What the first-window loss cannot see at adamw 3e-5 (the
#: configuration's ``loss_tolerance``): a state left unchanged reads
#: exactly 1 (``skipped_update``).  The system reads 0.434-0.462 at thirteen
#: seeds, with ``update_norm_ratio`` 1.00002 and ``update_sign_agreement``
#: 0.9459-0.9522; float8 1.024; the three planted faults 1.13 (first build).
#: Far over a tenth, and the whole of it is accounted for: adamw's first
#: step is the gradient's SIGN times 3e-5 on weights stored in bfloat16,
#: which moves only the weights under 2^-7 (26% of them), each by one or
#: two ulps; where the system's bf16 gradient and the reference's float32
#: one differ in SIGN (4.9-5.4% of the moved elements: the ones whose gradient
#: is smallest, three quarters of them in expert stacks that ~190 of the
#: prefix's 3,072 tokens reach) the two changes differ by twice a change,
#: and 2 sqrt(1 - agreement) is the reading to three digits (0.9483: 0.455).  At
#: MiniCPM-SALA's 3e-4 2.5% differ in sign and it reads 0.13-0.17.  The
#: limit leaves the largest reading 0.29 of room and a state left
#: unchanged 0.25.
UPDATE_REL_LIMIT = 0.75
#: On the CPU (a rehearsal: hidden 64, 4 heads of 16 over 2, 4 of 16
#: experts top-4, vocabulary 256, 64-token rows) one flipped choice is a
#: far larger share of the model.  Over three seeds there (my CPU runs, PR
#: 43): agreement 0.934-0.953, logits 0.024-0.026, loss 1.0e-3-2.2e-3, worst
#: gradient leaf 1.1-6.7%, the step 0.27-0.34; float8 0.33-0.36, 0.31-0.33,
#: 7.5e-3-1.4e-2, 87-130%, 1.05-1.08; the planted faults further out.  A
#: rehearsal rehearses the control flow; its limits are two to four times
#: its readings, and every stand-in is outside at least three of them
#: (``benchmarks/tests/test_lfm2_moe.py``).
REHEARSAL = {
    "LOGITS_RMS_LIMIT": 0.12, "LOSS_REL_LIMIT": 6e-3,
    "MIN_AGREE_SHARE": 0.5, "GRAD_NORM_LIMIT": 0.27, "UPDATE_REL_LIMIT": 0.75,
}


def limit(name: str, rehearsal: bool) -> float:
    return REHEARSAL[name] if rehearsal else globals()[name]


def sizes(c: dict, mix: dict) -> dict:
    """What the traffic generator needs to know of the model: ids are
    drawn from the vocabulary's slice."""
    return {"seq": mix["seq"], "vocab": c["vocab_size"]}


def samples_per_row(c: dict, mix: dict) -> int:
    return mix["seq"]


def flops_per_sample(c: dict, mix: dict) -> float:
    return lfm2_flops.lfm2_flops_per_token(c, mix["seq"])


def model_config(c: dict, mix: dict):
    import jax.numpy as jnp

    from ddl_tpu.config import TrainConfig

    t = c["training"]
    if c["conv_bias"] or not c["use_expert_bias"]:
        raise ValueError(
            "models/lfm2_moe.py: a convolution without bias, a selection bias"
        )
    if len(c["layer_types"]) != c["num_hidden_layers"]:
        raise ValueError("layer_types does not state num_hidden_layers kinds")
    if c["rope_parameters"]["rope_type"] != "default":
        raise ValueError("models/lfm2_moe.py has no rope scaling")
    router = c["published"]["num_experts"]
    held = (c["deployment"]["first_expert"], c["num_experts"])
    return TrainConfig(remat=t["remat"]).model_config(model.Lfm2MoeConfig(
        vocab=c["vocab_size"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"], n_kv_heads=c["num_key_value_heads"],
        d_ff=c["intermediate_size"], d_expert=c["moe_intermediate_size"],
        n_experts=router, topk=c["num_experts_per_tok"],
        layer_types=tuple(c["layer_types"]), n_dense_layers=c["num_dense_layers"],
        conv_kernel=c["conv_L_cache"], route_norm=c["norm_topk_prob"],
        route_scale=float(c["routed_scaling_factor"]),
        held_experts=None if held == (0, router) else held,
        max_seq=mix["seq"], rope_theta=float(c["rope_parameters"]["rope_theta"]),
        norm_eps=c["norm_eps"], param_dtype=jnp.dtype(t["param_dtype"]),
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
        n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads, n_experts=cfg.n_experts,
        topk=cfg.topk,
        conv_layers=tuple(kind == model.CONV for kind in cfg.layer_types),
        n_dense_layers=cfg.n_dense_layers, held=cfg.held,
        route_norm=cfg.route_norm, route_scale=cfg.route_scale,
        route_eps=cfg.route_eps, rope_theta=cfg.rope_theta,
        norm_eps=cfg.norm_eps, query_block=min(QUERY_BLOCK, cfg.max_seq),
    )


@contextlib.contextmanager
def _planted(fault):
    """Trace the system with a fault of the new mechanisms planted in front
    of the routines the timed step runs (they run as they are):
    ``taps_shifted``: the gated input ``B * u`` handed to the taps one
    position early - position t sees ``a[t-1 .. t+1]``, an input from the
    future; ``no_c_gate``: the output gate ``C`` replaced by ones;
    ``untied_head``: the head's rows no longer the embedding's (each
    vocabulary row reads its neighbour's).  ``None`` and ``skipped_update``
    (planted in the step of :func:`check_programs`' ``update``): the system
    as it stands."""
    if fault in (None, "skipped_update"):
        yield
        return
    assert fault in FAULTS, fault
    import jax.numpy as jnp

    from ddl_tpu.models import decoder

    if fault == "untied_head":
        real_head = decoder.lm_head

        def untied(params, x, cfg, scale=None):
            head = jnp.roll(params["embed"], 1, axis=0).T
            return real_head({**params, "lm_head": head}, x, cfg, scale)

        with mock.patch.object(decoder, "lm_head", untied):
            yield
        return
    real_conv = model.gated_short_conv

    def faulty(bcx, taps):
        d = bcx.shape[-1] // 3
        b, c, u = bcx[..., :d], bcx[..., d : 2 * d], bcx[..., 2 * d :]
        if fault == "no_c_gate":
            c = jnp.ones_like(c)
        else:
            early = lambda x: jnp.pad(x[:, 1:], ((0, 0), (0, 1), (0, 0)))
            b, u = early(b), early(u)
        return real_conv(jnp.concatenate([b, c, u], axis=-1), taps)

    with mock.patch.object(model, "gated_short_conv", faulty):
        yield


def _learning_rate() -> float:
    """The cell's: ``benchmarks/run.py`` builds ``optax.adamw`` from the
    configuration's ``training`` and leaves every other default."""
    from benchmarks.lib import cells

    with open(os.path.join(cells.HERE, "configs", "lfm2-24b-a2b.json")) as f:
        return json.load(f)["training"]["learning_rate"]


def check_programs(cfg, compute_dtype=None, fault=None) -> dict:
    """The comparison's programs for the model ``cfg`` (or a stand-in for
    it): ``errors(stored, rows)``: the two sides' forward passes on the same
    rows, as sums a position; ``got_norms``: the system's gradient norms
    (``families/afmoe.py:_tap_norms``); ``update(again, row)``: one
    optimizer step of the train loop's own program from the weights
    ``again()`` makes (consumed, and made anew) against a plain adamw step
    of the reference's gradients, and the reference's gradient norms.
    Built apart from the arrays so that a script can compile them for a
    described chip."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from benchmarks.lib import lfm2_moe_reference as reference
    from ddl_tpu.models.losses import next_token_cross_entropy
    from ddl_tpu.parallel.mesh import make_mesh
    from ddl_tpu.parallel.train import make_multistep

    c = reference_config(cfg, reference)
    first, count = cfg.held
    exact = lambda *xs: tuple(x.astype(jnp.float32) for x in xs)

    def by_row(loss, logits, t):
        """(rows,): each row's own loss."""
        return jax.vmap(lambda lg, tk: loss(lg[None], tk[None]))(logits, t)

    def system_loss(p, t):
        with _planted(fault):
            return model.next_token_loss(p, t, cfg)

    @jax.jit
    def system_forward(stored, t):
        # One pass: the loss the model's ``next_token_loss`` takes of
        # these logits (a test holds the two together).
        with _planted(fault):
            logits, picks = model.forward_with_choices(stored, t, cfg)
        return logits, by_row(next_token_cross_entropy, logits, t), picks

    def held_only(picks):
        """Each token's held picks, sorted; a pick held elsewhere is -1."""
        mine = (picks >= first) & (picks < first + count)
        return jnp.sort(jnp.where(mine, picks, -1), -1), mine

    @jax.jit
    def sums(got, got_picks, want, want_picks, t):
        got_held, mine = held_only(got_picks)
        want_held, _ = held_only(want_picks)
        same = jnp.all(got_held == want_held, axis=(0, -1))  # (rows, seq)
        diff2 = jnp.where(same[..., None], (got - want) ** 2, 0.0)
        want2 = jnp.where(same[..., None], want**2, 0.0)
        return {
            # (rows, seq): a position's sums over the vocabulary, zero where
            # the held picks differ
            "same": same, "diff2": jnp.sum(diff2, axis=-1),
            "want2": jnp.sum(want2, axis=-1), "diff2_max": jnp.max(diff2),
            "reference_loss": by_row(reference.cross_entropy, want, t),
            # (expert layers,): the system's choices that fell on held experts
            "held": jnp.sum(mine, axis=(1, 2, 3)),
        }

    def a_layer_at_a_time(x, layer, c, r, conv, dense):
        # the host does not run ahead of the device by more than a layer:
        # arrays queued behind it would all be alive at once
        return jax.block_until_ready(reference._layer(x, layer, c, r, conv, dense))

    def errors(stored, t):
        """The system (or its stand-in) against the float32 reference on
        the rows ``t``, as sums.  The system's forward pass is one program;
        the reference runs EAGERLY, a stage a program (its docstring).
        Both read the STORED weights - the reference computes in float32
        from them, a stage's copy at a time - so that the arrays alive here
        stay under the train state's own bytes: the check must not set the
        run's ``memory_peak_bytes``."""
        want, want_picks = reference.forward(stored, t, c, None, a_layer_at_a_time)
        if compute_dtype is None:
            got, got_loss, got_picks = system_forward(stored, t)
        else:
            got, got_picks = reference.forward(
                stored, t, c, compute_dtype, a_layer_at_a_time
            )
            got_loss = by_row(reference.cross_entropy, got, t)
        return {"loss": got_loss, **sums(got, got_picks, want, want_picks, t)}

    # -- the gradients and one optimizer step ----------------------------------------
    c_grad = c._replace(checkpoint_layers=True)

    def tapped_plain_loss(p, t, layer_fn):
        return reference.loss(p, t, c_grad, compute_dtype, layer_fn)

    if compute_dtype is None:
        got_loss, got_norms = system_loss, _tap_norms(system_loss)
    else:
        got_loss = lambda p, t: reference.loss(p, t, c_grad, compute_dtype)
        got_norms = _tap_norms(tapped_plain_loss, reference._layer)

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
        XLA keeps: a ``float32 -> bfloat16 -> float32`` pair of converts it
        removes on a TPU (excess precision is allowed there), and the plain
        step then moves EVERY element by the learning rate where the stored
        one moves by whole ulps or not at all (my chip runs, PR 43: the
        first build read 0.883 with 26% of the elements "moved" alike - the
        share of the weights under 2^-7, the only ones 3e-5 can move)."""
        info = jnp.finfo(dtype)
        return jax.lax.reduce_precision(x, info.nexp, info.nmant)

    def plain_adamw(p, g):
        """adamw's FIRST step written out in float32, optax's defaults: the
        moments start from zero, so their bias corrections cancel and the
        step is ``g / (|g| + eps)`` - the gradient's SIGN wherever it is
        well above 1e-8 - plus the decay."""
        p32, g = exact(p, g)
        change = g / (jnp.abs(g) + 1e-8) + 1e-4 * p32
        return stored_as(p32 - lr * change, p.dtype)

    @jax.jit
    def update_sums(before, moved, row):
        """From the reference's gradients at ``before`` (which reads the
        STORED weights, as the system does), a row a leaf: the squares of
        the two changes' difference, of the plain step's change and of the
        system's; the elements the plain step moves and those of them the
        system moves the same way; then each leaf's gradient norm.  One
        program, so that the gradients are its temporaries and not a third
        copy of the model beside ``before`` and ``moved``."""
        grads = jax.grad(lambda p: reference.loss(p, row, c_grad))(before)

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
        state = init_state(again())
        state, _ = step(state, (row[None],), per_step=True)
        moved = jax.block_until_ready(state.params)
        del state
        before = again()
        sums, norms = jax.device_get(update_sums(before, moved, row))
        return sums.astype(np.float64).sum(axis=0), {
            jax.tree_util.keystr(path): float(norm)
            for path, norm in jax.tree_util.tree_leaves_with_path(norms)
        }

    return {"errors": errors, "got_norms": got_norms, "update": update}


#: What a comparison is made of (:func:`compare_with_reference`'s ``parts``).
PARTS = ("forward", "gradients")


def compare_with_reference(cfg, seed: int, compute_dtype=None, fault=None,
                           parts=PARTS) -> dict:
    """The model the window trains - ``cfg`` as it stands: every layer,
    the configured remat, the share - against the float32 reference on the
    same seeded weights (``cfg``'s storage dtype) and ``CHECK_ROWS`` seeded
    rows of ``cfg.max_seq`` tokens: logits, loss and the routers' picks of
    one forward pass ``PAIR_ROWS`` at a time, then on the first
    ``GRAD_TOKENS`` tokens of the first row the norm of every leaf of the
    loss gradient and one optimizer step.  Stand-ins for the system, which
    a limit must refuse: with ``compute_dtype`` the reference computed in
    that precision; with ``fault`` the system with that fault planted
    (:func:`_planted`)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    programs = check_programs(cfg, compute_dtype, fault)
    init = jax.jit(lambda key: model.init_params(cfg, key))
    again = lambda: init(jax.random.fold_in(jax.random.key(seed), 43))
    tokens = jnp.asarray(
        np.random.default_rng([seed, 43]).integers(
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
    same, diff2, want2 = join("same") > 0, join("diff2"), join("want2")
    # Where no token agrees there is nothing to compare: the ratios read 1
    # (the agreeing share, 0, is what refuses such a run).
    agreed = bool(same.any())
    ratio = lambda num: float(np.sqrt(num / want2.sum())) if agreed else 1.0
    by_position = np.sqrt(diff2[same] / want2[same]) if agreed else np.ones(1)
    held = sum(p["held"] for p in pairs).astype(np.float64)  # (expert layers,)
    choices = tokens.size * cfg.topk  # a layer
    loss, reference_loss = join("loss"), join("reference_loss")
    return {
        "agree_share": float(same.mean()),
        "logits_rel_rms": ratio(diff2.sum()),
        # an agreeing position's own: the median and the worst
        "logits_rel_rms_median_position": float(np.median(by_position)),
        "logits_rel_rms_worst_position": float(np.max(by_position)),
        # the worst element against an element's rms
        "logits_rel_max": ratio(
            max(float(p["diff2_max"]) for p in pairs) * cfg.vocab * same.sum()
        ),
        "loss": loss.tolist(), "reference_loss": reference_loss.tolist(),
        "loss_rel_diff": float(np.max(np.abs(loss - reference_loss) / reference_loss)),
        "held_choice_share": float(held.sum() / (choices * max(len(held), 1))),
        "held_choice_share_by_layer": [float(h / choices) for h in held],
        "balanced_held_share": cfg.held[1] / cfg.n_experts,
    }


def compare_gradients(programs, again, row) -> dict:
    """On the prefix ``row``: every gradient leaf's norm against the
    reference's, and one optimizer step of the train loop's program against
    a plain adamw step of the reference's gradients.  ``again()`` makes the
    stored weights, a program's own each time: the step consumes its.  The
    programs run one after the other and hold at most the train state and
    a step's temporaries on a prefix, or two copies of the stored model and
    the reference's residuals: under the timed step's own peak."""
    import numpy as np

    got_norms = programs["got_norms"](again(), row)
    (diff2, want2, got2, moved, same), want_norms = programs["update"](again, row)
    # The leaves the reference gives no gradient: expert_bias (selection
    # only) and, in a share, the router (not trained by a share).
    frozen = [k for k, w in want_norms.items() if w == 0.0]
    rel = {
        k: abs(float(got_norms[k]) - float(w)) / float(w)
        for k, w in want_norms.items() if k not in frozen
    }
    worst = max(rel, key=rel.get)
    return {
        "grad_tokens": int(row.shape[1]), "grad_leaves": len(rel),
        "grad_norm_rel_diff": rel[worst], "grad_norm_worst_leaf": worst,
        "grad_norm_rel_diff_median": float(np.median(list(rel.values()))),
        "frozen_leaves": len(frozen),
        "frozen_grad_norm": max([float(got_norms[k]) for k in frozen] or [0.0]),
        # | change - plain change | / | plain change |: 1 where nothing moved
        "update_rel_diff": float(np.sqrt(diff2 / want2)),
        "update_norm_ratio": float(np.sqrt(got2 / want2)),
        "update_sign_agreement": float(same / max(moved, 1.0)),
    }


def problems_of(found: dict, rehearsal: bool) -> list:
    """What of a comparison is outside the limits."""
    at_most = [
        ("logits_rel_rms", "LOGITS_RMS_LIMIT", "logits differ from the "
         "reference's by this share of their rms"),
        ("loss_rel_diff", "LOSS_REL_LIMIT", "a row's loss differs from the "
         "reference's"),
        ("grad_norm_rel_diff", "GRAD_NORM_LIMIT", "the worst gradient leaf "
         "differs in norm"),
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
