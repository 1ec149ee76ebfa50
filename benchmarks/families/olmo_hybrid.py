"""Family adapter: Olmo-Hybrid decoders (Olmo-Hybrid-7B) through
``ddl_tpu/models/olmo_hybrid.py``: gated-delta-rule linear attention
(``ops/gated_delta.py``: the chunked scan and its backward pass) three
layers to one of full softmax attention without positions; the
vocabulary's slice.

``loss_fn`` is the one hook the runner calls before the weights exist, so
it is where the system is held to the plain float32 reference
(``benchmarks/lib/olmo_hybrid_reference.py``: the recurrence a ``lax.scan``
over positions), in every run's set-up: the configured model itself - every
layer, the configured remat, bf16 - on ``CHECK_ROWS`` seeded rows of the
mix's length: logits, loss, and the norm of every gradient leaf on a
prefix; and the mixer's core alone (convolutions, L2 norms, the scan), in
float32 at the configured head shape and the mix's WHOLE length, against
the same recurrence: its output and every input's gradient.  A run outside
the limits exits non-zero and prints no result.
"""

from __future__ import annotations

import contextlib
import functools
import json
import time
from unittest import mock

# Imported here, not inside the hooks: a checkout whose program has no
# such model refuses the cell while the runner loads it - before a device,
# a weight or a producer exists.
from ddl_tpu.models import olmo_hybrid as model

from benchmarks.families.afmoe import _tap_norms
from benchmarks.families.olmoe import _peak_gib, _seed_of_this_run
from benchmarks.lib import gdn_flops

#: This family's rate metric (its unit is BENCHMARK.json's).
RATE_METRIC = "tokens_per_s"

#: Rows of ``max_seq`` tokens the reference check compares: the
#: ``tokens-16k`` mix's window, a step's row at a time - the batch the
#: timed program takes (a test holds both to the mix: the hook is handed
#: the model and the mesh, not the mix).
CHECK_ROWS = 2
PAIR_ROWS = 1
#: Tokens of the first row whose loss gradient is compared leaf by leaf:
#: 48 chunks of 64, so that the backward chain runs many chunks, and not
#: the whole row: the reference keeps a float32 state a block of positions
#: and the full layer's probabilities a query block, and the check has to
#: stay under the train step's own peak, or the run's ``memory_peak_bytes``
#: would be the check's.  What a prefix cannot show - the chain, forward
#: and reverse, over ALL the row's chunks - is the core's check
#: (:func:`compare_core`), which holds no weights and runs the whole row.
GRAD_TOKENS = 3072
#: Heads the core's check runs at a time (or the largest divisor of the
#: heads under it): the pass ``gated_delta_rule`` itself makes at one row
#: of 16,384, and a fifth of the arrays all 30 heads would hold.
CORE_HEADS = 6
#: Positions a block of the reference's recurrence in the core's check: its
#: backward pass keeps a state a block and a block's states at a time.
CORE_BLOCK = 128
#: bf16's unit roundoff: 8 bits of mantissa.
U_BF16 = 2.0**-9
#: The system's stand-ins with a planted fault of the new mechanism, which
#: the limits must refuse (:func:`_planted`).
FAULTS = ("no_decay", "beta_not_doubled", "bf16_state")

#: LIMITS.  Each comes from two readings of the configured model (4 layers,
#: published widths, the slice) on the chip (my chip runs, PR 36, TPU v5
#: lite, eight seeds; PERF.md section 6): the largest the system at its
#: configured bf16 gives over the seeds, and what a stand-in gives that has
#: to be refused - the reference computed in float8_e4m3fn, the next
#: precision down, and the system with a planted fault: the decay left out
#: (alpha = 1), beta not doubled, the chunk-to-chunk state carried in
#: bfloat16 - all four through this file (tools/probe_gdn_controls.py,
#: seed 3600000011).
#:
#: Logits, every position: root mean square of the differences over the
#: reference logits' root mean square.  bf16: 6.12-6.25 u (a position's
#: own: median 1.18%, the worst of 32,768 3.6-7.1%).  float8: 108 u.  No
#: decay: 528 u.  Beta not doubled: 181 u.  State in bfloat16: 6.12 u - one
#: more bfloat16 rounding among the model's own; the scan's limit is for it.
LOGITS_RMS_LIMIT = 16 * U_BF16
#: |loss - reference loss| / reference loss of a row, over ALL its tokens;
#: the larger of the rows.  bf16: 1.0e-5-2.5e-5 (12 rows).  float8: 1.37e-4.
#: No decay: 7.6e-4.  Beta not doubled: 1.97e-4.  State in bfloat16: 7e-6.
LOSS_REL_LIMIT = 6.0e-5
#: | |g| - |g_ref| | / |g_ref|, the worst leaf of d loss / d parameters on
#: the prefix (``jax.grad`` of the train loss under the configured remat
#: against ``jax.grad`` of the reference).  bf16: 0.37-0.70% over six
#: seeds, 1.8% and 2.4% at two more, always a third-layer or second-layer
#: ``A_log``, ``dt_bias`` or convolution's taps (a head's sum over every
#: position, through ``exp(-exp(.))``); the median leaf 0.15-0.21%.
#: float8: 100% (the cotangents underflow).  No decay: 377%.  Beta not
#: doubled: 32%.  State in bfloat16: 0.59%.  A norm does not see a
#: direction (PERF.md section 7).
GRAD_NORM_LIMIT = 0.10
#: A linear mixer's core alone (:func:`compare_core`: the convolutions and
#: L2 norms of ``models/olmo_hybrid.py``, ``ops/gated_delta.gated_delta_rule``)
#: on float32 operands (the MXU at full precision) against the reference's
#: stages and recurrence over the mix's WHOLE row, 16,384 positions, 256
#: chunks, all 30 heads six at a time: root mean square of the differences
#: over the reference's root mean square, of the output ...
#: float32 on the chip, eleven seeds: 1.80e-4-2.62e-4 - not the scan's error
#: but what 16,384 successive ``exp`` of the chip accumulate in the
#: recurrence, where the chunked form takes one ``exp`` of a sum (the CPU,
#: whose ``exp`` is exact to an ulp, reads 4e-7 on such operands); no block
#: of 1,024 positions past 1.42e-3 in any of them.  The chain's
#: state in bfloat16, six seeds: 1.75e-3-2.06e-3.
CORE_RMS_LIMIT = 7.0e-4
#: ... and the worst of the eight operands' gradients of ``sum(o w)``: the
#: reverse chain over all 256 chunks, ``dM = G H^T``, the convolutions' own
#: backward pass.  The same eleven seeds: 4.9e-4-8.9e-4, always the decay's
#: (``g``: the other seven 1.9e-4-5.8e-4).  State in bfloat16: 3.41e-3-4.40e-3
#: - and refused by the output's limit besides.
CORE_GRAD_RMS_LIMIT = 2.0e-3
#: On the CPU (a rehearsal: hidden 64, 4 heads of 8 / 16, vocabulary 256,
#: 128-token rows) the same architecture is far less well conditioned in
#: bfloat16: logits 17-40 u, loss 5e-4-2e-3, a gradient leaf's norm by up
#: to 70% (the reference's own rounding to bfloat16 moves them as much).  A
#: rehearsal rehearses the control flow; the model-level stand-ins are
#: refused by the logits and the loss there, the state's by the core's own
#: limits, where the CPU's exact ``exp`` reads 3e-7-6e-7 and 7e-7-1.1e-6 against
#: 2.0e-4-3.6e-4 and 4.5e-4-7.7e-4 (five seeds; a test holds all four).
REHEARSAL_LOGITS_RMS_LIMIT = 60 * U_BF16
REHEARSAL_LOSS_REL_LIMIT = 8e-3
REHEARSAL_GRAD_NORM_LIMIT = 4.0
REHEARSAL_CORE_RMS_LIMIT = 1.0e-5
REHEARSAL_CORE_GRAD_RMS_LIMIT = 2.0e-5


def sizes(c: dict, mix: dict) -> dict:
    """What the traffic generator needs to know of the model: ids are
    drawn from the vocabulary's slice."""
    return {"seq": mix["seq"], "vocab": c["vocab_size"]}


def samples_per_row(c: dict, mix: dict) -> int:
    return mix["seq"]


def flops_per_sample(c: dict, mix: dict) -> float:
    return gdn_flops.olmo_hybrid_flops_per_token(c, mix["seq"])


def model_config(c: dict, mix: dict):
    import jax.numpy as jnp

    from ddl_tpu.config import TrainConfig

    t = c["training"]
    if c["rope_parameters"]["rope_theta"] is not None:
        raise ValueError("models/olmo_hybrid.py: full attention without positions")
    if c["num_key_value_heads"] != c["num_attention_heads"]:
        raise ValueError("models/olmo_hybrid.py: the full layers are multi-head")
    if c["linear_num_key_heads"] != c["linear_num_value_heads"]:
        raise ValueError("models/olmo_hybrid.py: one key head a value head")
    if (c["hidden_act"], c["attention_bias"], c["tie_word_embeddings"]) != (
        "silu", False, False
    ):
        raise ValueError("models/olmo_hybrid.py: SiLU, no biases, an untied head")
    if len(c["layer_types"]) != c["num_hidden_layers"]:
        raise ValueError("layer_types is not num_hidden_layers long")
    if not c["assumed"]["norm_placement"].startswith("post:"):
        raise ValueError("models/olmo_hybrid.py places its norms after the blocks")
    return TrainConfig(remat=t["remat"]).model_config(model.OlmoHybridConfig(
        vocab=c["vocab_size"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"], d_ff=c["intermediate_size"],
        layer_types=tuple(c["layer_types"]),
        n_linear_heads=c["linear_num_value_heads"],
        linear_key_dim=c["linear_key_head_dim"],
        linear_value_dim=c["linear_value_head_dim"],
        conv_kernel=c["linear_conv_kernel_dim"],
        allow_neg_eigval=c["linear_allow_neg_eigval"],
        max_seq=mix["seq"], norm_eps=c["rms_norm_eps"],
        param_dtype=jnp.dtype(t["param_dtype"]), attn_impl=t["attn_impl"],
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
        n_heads=cfg.n_heads, n_linear_heads=cfg.n_linear_heads,
        key_dim=cfg.linear_key_dim, value_dim=cfg.linear_value_dim,
        linear_layers=tuple(kind == model.LINEAR for kind in cfg.layer_types),
        allow_neg_eigval=cfg.allow_neg_eigval, norm_eps=cfg.norm_eps,
        query_block=min(256, cfg.max_seq),
    )


@contextlib.contextmanager
def _planted(fault):
    """Trace the system with a fault of the new mechanism planted:
    ``no_decay``: the recurrence handed ``g = 0`` (alpha = 1);
    ``beta_not_doubled``: handed half its beta (``sigmoid``, not ``2
    sigmoid``); both in front of ``gated_delta_rule``, the kernels as they
    are.  ``bf16_state``: the chain's kernel carries its state from chunk
    to chunk in bfloat16.  ``None``: the system as it stands."""
    if fault is None:
        yield
        return
    assert fault in FAULTS, fault
    import jax.numpy as jnp

    from ddl_tpu.ops import gated_delta

    if fault == "bf16_state":
        with mock.patch.object(gated_delta, "_STATE_DTYPE", jnp.bfloat16):
            yield
        return
    real = gated_delta.gated_delta_rule

    def faulty(q, k, v, g, beta):
        if fault == "no_decay":
            return real(q, k, v, g * 0, beta)
        return real(q, k, v, g, beta / 2)

    with mock.patch.object(model, "gated_delta_rule", faulty):
        yield


def core_heads(cfg) -> int:
    """Heads a call of the core's check holds: the largest divisor of the
    configured heads up to :data:`CORE_HEADS`."""
    H = cfg.n_linear_heads
    return max(h for h in range(1, min(CORE_HEADS, H) + 1) if H % h == 0)


def core_inputs(cfg, seed: int, group: int):
    """Seeded operands of a linear mixer's core at ``cfg``'s head shape,
    :func:`core_heads` heads (the ``group``-th of them), one row of
    ``cfg.max_seq`` positions, float32: what the q, k and v projections
    hand on (normal) and the three convolutions' taps (normal over the root
    of their number, as they are drawn), ``beta = 2 sigmoid(normal)``, ``g =
    -rate_h * exp(normal)`` with a head's rate log-uniform between 1 /
    positions (the state carries through the whole row) and 1 (it forgets
    in a few positions), and the output's cotangent (normal)."""
    import jax.numpy as jnp
    import numpy as np

    T, K = cfg.max_seq, cfg.conv_kernel
    H, dk, dv = core_heads(cfg), cfg.linear_key_dim, cfg.linear_value_dim
    rng = np.random.default_rng([seed, 36, group])
    normal = lambda *shape: rng.standard_normal(shape, dtype=np.float32)
    qp, kp, vp = normal(1, T, H * dk), normal(1, T, H * dk), normal(1, T, H * dv)
    taps = [normal(K, C) / np.sqrt(K) for C in (H * dk, H * dk, H * dv)]
    beta = 2.0 / (1.0 + np.exp(-normal(1, T, H)))
    rate = np.exp(rng.uniform(-np.log(T), 0.0, H))
    g = -rate * np.exp(normal(1, T, H))
    w = normal(1, T, H, dv)
    return tuple(
        jnp.asarray(x, jnp.float32) for x in (qp, kp, vp, *taps, g, beta, w)
    )


#: What :func:`core_inputs` hands over, less the cotangent: the names the
#: core's gradients are reported under.
CORE_OPERANDS = ("q_proj", "k_proj", "v_proj", "conv_q", "conv_k", "conv_v", "g", "beta")


def check_programs(cfg, compute_dtype=None, fault=None) -> dict:
    """The comparison's programs for the model ``cfg`` (or a stand-in for
    it): ``errors(stored, rows)``, the two sides' forward passes on
    the same rows as sums; ``got_norms`` / ``want_norms``, the two sides'
    gradient norms (``families/afmoe.py:_tap_norms``); ``core(*operands,
    w)``, a linear mixer's core - convolutions, L2 norms, the scan - through
    the system's routines and through the reference's on the same float32
    operands (:func:`core_inputs`): the output and the gradient of ``sum(o
    w)`` with respect to every operand, as sums a position."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.lib import olmo_hybrid_reference as reference
    from ddl_tpu.models.losses import next_token_cross_entropy
    from ddl_tpu.ops import gated_delta

    c = reference_config(cfg, reference)

    def by_row(loss, logits, t):
        """(rows,): each row's own loss."""
        return jax.vmap(lambda lg, tk: loss(lg[None], tk[None]))(logits, t)

    def system_loss(p, t):
        with _planted(fault):
            return model.next_token_loss(p, t, cfg)

    @jax.jit
    def system_logits(stored, t):
        with _planted(fault):
            got = model.forward(stored, t, cfg)
        # the loss the model's ``next_token_loss`` takes of these logits
        return got, by_row(next_token_cross_entropy, got, t)

    @jax.jit
    def sums(got, want, t):
        diff2 = (got - want) ** 2
        return {
            # (rows, seq): a position's sums over the vocabulary
            "diff2": jnp.sum(diff2, axis=-1), "want2": jnp.sum(want**2, axis=-1),
            "diff2_max": jnp.max(diff2),
            "reference_loss": by_row(reference.cross_entropy, want, t),
        }

    def a_layer_at_a_time(x, layer, c, r, linear):
        # the host does not run ahead of the device by more than a layer:
        # arrays queued behind it would all be alive at once
        return jax.block_until_ready(reference._layer(x, layer, c, r, linear))

    def errors(stored, t):
        """The system (or its stand-in) against the float32 reference on
        the rows ``t``, as sums.  The system's forward pass is one program;
        the reference runs EAGERLY, a stage a program: as one XLA program
        of 16,384 positions it is not itself on the chip (its docstring;
        PERF.md section 6).  Both read the STORED weights - the reference
        computes in float32 from them, a stage's copy at a time - so that
        the arrays alive here stay under the train state's own bytes: the
        check must not set the run's ``memory_peak_bytes``."""
        want = reference.forward(stored, t, c, None, a_layer_at_a_time)
        if compute_dtype is None:
            got, got_loss = system_logits(stored, t)
        else:
            got = reference.forward(stored, t, c, compute_dtype, a_layer_at_a_time)
            got_loss = by_row(reference.cross_entropy, got, t)
        return {"loss": got_loss, **sums(got, want, t)}

    H, dk, dv = core_heads(cfg), cfg.linear_key_dim, cfg.linear_value_dim

    def system_core(qp, kp, vp, tq, tk, tv, g, beta):
        """``models/olmo_hybrid._linear_block`` between its projections and
        its gated norm, on the operands as they come (float32: the MXU at
        full precision)."""
        heads = lambda x, d: x.reshape(x.shape[:2] + (H, d))
        q = model._unit(heads(model._silu_conv(qp, tq), dk)) * dk**-0.5
        k = model._unit(heads(model._silu_conv(kp, tk), dk))
        v = heads(model._silu_conv(vp, tv), dv)
        return gated_delta.gated_delta_rule(q, k, v, g, beta)

    # a function of this call's own: jit's cache goes by the function, and a
    # planted fault is a different trace of the same one
    @jax.jit
    def run_system(*operands_and_w):
        o, pull = jax.vjp(system_core, *operands_and_w[:-1])
        return (o,) + pull(operands_and_w[-1])

    @jax.jit
    def reference_qkv(qp, kp, vp, tq, tk, tv):
        heads = lambda x, d: x.reshape(x.shape[:2] + (H, d))
        silu_conv = lambda x, t: jax.nn.silu(reference._conv(x, t))
        q = reference._unit(heads(silu_conv(qp, tq), dk)) / np.sqrt(dk)
        return q, reference._unit(heads(silu_conv(kp, tk), dk)), heads(silu_conv(vp, tv), dv)

    recurrence = functools.partial(
        reference.recurrence, block=CORE_BLOCK, checkpoint_blocks=True
    )

    def run_reference(qp, kp, vp, tq, tk, tv, g, beta, w):
        """EAGERLY, a stage and a pass a program, as the reference's forward
        pass is run (its docstring): what feeds the scan, the scan, the
        scan's backward pass, the backward pass of what feeds it."""
        with jax.default_matmul_precision("highest"):
            qkv, pull_qkv = jax.vjp(reference_qkv, qp, kp, vp, tq, tk, tv)
            o, pull_scan = jax.vjp(recurrence, *qkv, g, beta)
            d_q, d_k, d_v, d_g, d_beta = pull_scan(w)
            return (o,) + pull_qkv((d_q, d_k, d_v)) + (d_g, d_beta)

    @jax.jit
    def core_sums(got, want):
        """(1 + operands, positions) each: the squared differences and the
        reference's squares, summed over all but the positions (a
        convolution's taps have none: theirs stand at position 0)."""
        def by_position(x):
            if x.ndim == 2:  # taps (K, C)
                return jnp.zeros(got[0].shape[1]).at[0].set(jnp.sum(x))
            return jnp.sum(x, axis=(0,) + tuple(range(2, x.ndim)))

        return {
            "diff2": jnp.stack([by_position((a - b) ** 2) for a, b in zip(got, want)]),
            "want2": jnp.stack([by_position(b**2) for b in want]),
        }

    def core(*operands_and_w):
        with _planted(fault):
            got = run_system(*operands_and_w)
        return core_sums(got, run_reference(*operands_and_w))

    c_grad = c._replace(checkpoint_layers=True)

    # ``_tap_norms`` taps a layer through AFMoE's six-argument ``_layer``
    # that returns (x, picks); this family's has five and returns x.
    def reference_layer(x, w, c, r, _sliding, linear):
        return reference._layer(x, w, c, r, linear), None

    def plain_loss(dtype):
        def loss(p, t, layer_fn):
            return reference.loss(
                p, t, c_grad, dtype,
                lambda x, w, c, r, linear: layer_fn(x, w, c, r, False, linear)[0],
            )

        return loss

    want_norms = _tap_norms(plain_loss(None), reference_layer)
    if compute_dtype is None:
        got_norms = _tap_norms(system_loss)
    else:
        got_norms = _tap_norms(plain_loss(compute_dtype), reference_layer)
    return {
        "errors": errors, "core": core, "got_norms": got_norms,
        "want_norms": want_norms,
    }


def compare_with_reference(cfg, seed: int, compute_dtype=None, fault=None) -> dict:
    """The model the window trains - ``cfg`` as it stands: every layer,
    the configured remat - against the float32 reference on the same
    seeded weights (``cfg``'s storage dtype) and ``CHECK_ROWS`` seeded rows
    of ``cfg.max_seq`` tokens: logits and loss of one forward pass
    ``PAIR_ROWS`` at a time, then the norm of every leaf of the loss
    gradient on the first ``GRAD_TOKENS`` tokens of the first row, then a
    linear mixer's core alone over the whole row (:func:`compare_core`).
    Stand-ins for the system, which a
    limit must refuse: with ``compute_dtype`` the reference computed in
    that precision; with ``fault`` the system with that fault planted
    (:func:`_planted`)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    programs = check_programs(cfg, compute_dtype, fault)
    stored = jax.jit(lambda key: model.init_params(cfg, key))(
        jax.random.fold_in(jax.random.key(seed), 36)
    )
    tokens = jnp.asarray(
        np.random.default_rng([seed, 36]).integers(
            0, cfg.vocab, (CHECK_ROWS, cfg.max_seq), dtype=np.int32
        )
    )
    pairs = [
        jax.device_get(programs["errors"](stored, tokens[lo : lo + PAIR_ROWS]))
        for lo in range(0, CHECK_ROWS, PAIR_ROWS)
    ]
    diff2 = np.concatenate([p["diff2"] for p in pairs]).astype(np.float64)
    want2 = np.concatenate([p["want2"] for p in pairs]).astype(np.float64)
    rms = np.sqrt(want2.sum() / (tokens.size * cfg.vocab))
    loss = np.concatenate([p["loss"] for p in pairs]).astype(np.float64)
    reference_loss = np.concatenate([p["reference_loss"] for p in pairs]).astype(
        np.float64
    )
    out = {
        "logits_rel_rms": float(np.sqrt(diff2.sum() / want2.sum())),
        # a position's own: the median and the worst
        "logits_rel_rms_median_position": float(np.median(np.sqrt(diff2 / want2))),
        "logits_rel_rms_worst_position": float(np.max(np.sqrt(diff2 / want2))),
        "logits_rel_max": float(
            np.sqrt(max(float(p["diff2_max"]) for p in pairs)) / rms
        ),
        "loss": loss.tolist(), "reference_loss": reference_loss.tolist(),
        "loss_rel_diff": float(np.max(np.abs(loss - reference_loss) / reference_loss)),
    }

    # -- the gradients ----------------------------------------------------------
    row = tokens[:1, : min(GRAD_TOKENS, cfg.max_seq)]
    if compute_dtype is None:
        got_norms = programs["got_norms"](stored, row)
    exact = jax.tree.map(lambda x: x.astype(jnp.float32), stored)
    del stored  # room for the reference's residuals
    if compute_dtype is not None:
        got_norms = programs["got_norms"](exact, row)
    want_norms = programs["want_norms"](exact, row)
    del exact
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

    out.update(compare_core(cfg, seed, programs["core"]))
    return out


def compare_core(cfg, seed: int, core) -> dict:
    """A linear mixer's core alone (``check_programs``' ``core``) on
    :func:`core_inputs`, every group of :func:`core_heads` heads in turn,
    over the mix's WHOLE row - every chunk the timed step's chains run,
    forward and reverse: root mean square of the differences over the
    reference's root mean square, of the output (``core_rel_rms``) and of
    each operand's gradient (``core_grad_rel_rms``; the worst of them is
    what the limit reads), and the worst 1,024 positions' own reading of
    any of them (a fault behind a boundary shows there first)."""
    import jax
    import numpy as np

    groups = cfg.n_linear_heads // core_heads(cfg)
    diff2 = want2 = 0.0
    for group in range(groups):
        found = jax.device_get(core(*core_inputs(cfg, seed, group)))
        diff2 = diff2 + found["diff2"].astype(np.float64)
        want2 = want2 + found["want2"].astype(np.float64)
    rel = np.sqrt(diff2.sum(axis=1) / want2.sum(axis=1))
    by_operand = dict(zip(CORE_OPERANDS, rel[1:].tolist()))
    worst = max(by_operand, key=by_operand.get)
    # blocks of 1,024 positions (a shorter row: one block), the taps' left out
    blocks = lambda x: np.add.reduceat(x, np.arange(0, x.shape[1], 1024), axis=1)
    positional = [i for i, name in enumerate(("o",) + CORE_OPERANDS) if "conv" not in name]
    by_block = np.sqrt(blocks(diff2[positional]) / blocks(want2[positional]))
    return {
        "core_tokens": cfg.max_seq, "core_heads": groups * core_heads(cfg),
        "core_rel_rms": float(rel[0]),
        "core_grad_rel_rms": by_operand,
        "core_grad_rel_rms_worst": by_operand[worst], "core_grad_worst_operand": worst,
        "core_rel_rms_worst_block": float(by_block.max()),
    }


def problems_of(found: dict, rehearsal: bool) -> list:
    """What of a comparison is outside the limits."""
    loss_limit = REHEARSAL_LOSS_REL_LIMIT if rehearsal else LOSS_REL_LIMIT
    grad_limit = REHEARSAL_GRAD_NORM_LIMIT if rehearsal else GRAD_NORM_LIMIT
    logits_limit = REHEARSAL_LOGITS_RMS_LIMIT if rehearsal else LOGITS_RMS_LIMIT
    problems = []
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
    core_limit = REHEARSAL_CORE_RMS_LIMIT if rehearsal else CORE_RMS_LIMIT
    if not found["core_rel_rms"] <= core_limit:
        problems.append(
            f"the scan differs from the recurrence by {found['core_rel_rms']:.4g} "
            f"of its rms in float32, limit {core_limit}"
        )
    core_grad_limit = (
        REHEARSAL_CORE_GRAD_RMS_LIMIT if rehearsal else CORE_GRAD_RMS_LIMIT
    )
    if not found["core_grad_rel_rms_worst"] <= core_grad_limit:
        problems.append(
            f"the scan's gradient of {found['core_grad_worst_operand']} differs "
            f"from the recurrence's by {found['core_grad_rel_rms_worst']:.4g} of "
            f"its rms in float32, limit {core_grad_limit}"
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
