"""Family adapter: MiniCPM-SALA decoders through
``ddl_tpu/models/minicpm_sala.py``: Lightning linear attention
(``ops/lightning_attention.py``: the fixed-decay chunked scan and its
backward pass) three layers to one of InfLLM-V2 block-sparse attention
(``ops/sparse_attention.py``: the selection stage and the flash kernels
over scalar-prefetched block lists); MiniCPM's muP; the vocabulary's slice.

``loss_fn`` is the one hook the runner calls before the weights exist, so
it is where the system is held to the plain float32 reference
(``benchmarks/lib/minicpm_sala_reference.py``), in every run's set-up: the
configured model itself - every layer, bf16 - on ``CHECK_ROWS`` seeded
rows of the mix's length: the selection's block scores and picks, logits
and loss with the reference GIVEN the system's block lists, the loss again
with the reference's own; the norm of every gradient leaf on a prefix under
the configured remat (the median leaf and the share of outliers: the limits
say why); ONE REAL OPTIMIZER STEP of ``parallel/train.py``'s window program
on that prefix, the parameters' change against a plain adamw step of the
reference's gradients (a state left unchanged reads 1); and the two cores
alone over the mix's WHOLE length, float32 and again in the timed
bfloat16: the scan against a ``lax.scan`` over positions, the sparse
attention against a masked softmax in query blocks.  A run outside the
limits exits non-zero and prints no result.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import time
from unittest import mock

# Imported here, not inside the hooks: a checkout whose program has no
# such model refuses the cell while the runner loads it - before a device,
# a weight or a producer exists.
from ddl_tpu.models import minicpm_sala as model

from benchmarks.families.afmoe import _tap_norms
from benchmarks.families.olmoe import _peak_gib, _seed_of_this_run
from benchmarks.lib import sala_flops

#: This family's rate metric (its unit is BENCHMARK.json's).
RATE_METRIC = "tokens_per_s"

#: Rows of ``max_seq`` tokens the reference check compares: the
#: ``tokens-16k`` mix's window, a step's row at a time.
CHECK_ROWS = 2
PAIR_ROWS = 1
#: Tokens of the first row whose loss gradient is compared leaf by leaf: 24
#: chunks of 128 through each scan's backward chain.  SHORTER than
#: ``dense_len``: the prefix checks the dense attention path and the scans'
#: backward; the sparse kernels' backward over the whole row is the sparse
#: core's check (:func:`compare_cores`), which holds no weights.
GRAD_TOKENS = 3072
#: Queries a block of the reference's masked softmax and selection.
QUERY_BLOCK = 256
#: bf16's unit roundoff: 8 bits of mantissa.
U_BF16 = 2.0**-9
#: The system's stand-ins with a planted fault, which the limits must
#: refuse (:func:`_planted`, :func:`_faulty_config`).
FAULTS = (
    "bf16_state", "no_decay", "dense_attention", "per_head_selection",
    "cut_depth_residual", "skipped_update",
)
#: A stand-in that is no fault: the system with the reference's float32
#: recurrence in the scan kernels' place, fed the same bfloat16 q, k, v -
#: the second witness to which side an ill-conditioned gradient leaf is on
#: (GRAD_MEDIAN_LIMIT's comment).
WITNESSES = ("f32_recurrence",)

#: LIMITS.  Each comes from two readings of the configured model (4 layers,
#: published widths, the slice) on the chip (my chip runs, PR 39, TPU v5
#: lite; PERF.md section 6 has the table): the largest the system at its
#: configured bf16 gives over the seeds, and what a stand-in gives that has
#: to be refused - the reference computed in float8_e4m3fn, the next
#: precision down, and the system with a planted fault (FAULTS) - all
#: through this file (tools/probe_sala_controls.py).
#:
#: Logits, every position, the reference given the system's block lists:
#: root mean square of the differences over the reference logits' rms.
#: bf16: 5.263e-3-5.267e-3 (2.7 u; a position's own: median 5.263e-3, the
#: worst of 32,768 5.7e-3-7.2e-3), ten seeds.  float8: 3.45e-2.  No decay:
#: 2.62e-2.  The residual scale from the cut's depth: 5.73e-2.
LOGITS_RMS_LIMIT = 6 * U_BF16
#: |loss - reference loss| / reference loss of a row over ALL its tokens,
#: the reference given the system's lists; the larger of the rows.  The
#: logits are near zero (muP divides them by 16) and the loss near log
#: 9,216 = 9.13 whatever the model does, so a reading is a whole number of
#: float32 ulps of the loss (1.044e-7 each): bf16 reads 1, 1, 2, 2, 4, 4, 4,
#: 5, 5, 5 over ten seeds (six later ones 0, 2, 2, 4, 4, 6), float8 17
#: (1.78e-6), the cut's depth in the residual 35.  The limit passes 13 and
#: refuses 14: at 1e-6 (9 ulps, four above the largest of ten readings
#: that scatter like a half-normal of ~3 ulps) a sound run in some hundreds
#: would have been refused.  A weak witness; the logits carry it.
LOSS_REL_LIMIT = 1.4e-6
#: The same with the reference's OWN lists (bf16 the same readings, one seed
#: a ulp more: a disputed near-tie swaps one block of 64 keys among 6,144
#: for a query, and a sparse layer's output is small beside the stream at
#: random weights; float8 16 ulps, 1.67e-6): looser, as stated - passes 14,
#: refuses 15.
OWN_LOSS_REL_LIMIT = 1.5e-6
#: | |g| - |g_ref| | / |g_ref|, every leaf of d loss / d parameters on the
#: prefix under the configured remat: the MEDIAN leaf, and the share of
#: leaves past ``GRAD_LEAF_TOLERANCE``; the worst leaf is recorded and not
#: limited.  Why not the worst: at random weights this architecture's
#: gradient is ill-conditioned in its INPUTS' precision.  A lightning head's
#: output goes through an RMSNorm of its own (eps 1e-6) and, without a
#: softmax, is what is left of decayed scores of both signs; at the few
#: (position, head) where it all but cancels the norm amplifies by up to a
#: thousand, those points carry a layer's q / k gradients (and its input
#: norm's), and ANY rounding upstream re-rolls them: the worst leaf reads
#: 0.21-0.44% at seven seeds of ten, 1.6%, 10% and 64% at the others (10 of
#: the 54 leaves past 5% there: the q / k leaves and input norms of the
#: lightning layers; six later seeds 0.20-1.5% at four, 6.0% and 102%) -
#: while logits read 2.7 u and the median leaf 5.7e-4-7.1e-4 at all.  The
#: second witness (``WITNESSES``: the reference's float32 ``lax.scan`` in
#: the scan kernels' place, fed the same bfloat16 q, k, v), on the chip at
#: the two worst seeds, each read twice (the second reading the first's to
#: every digit): the SAME leaves stay past the tolerance - seed 3000000019
#: layer 3's five at 19-26% (the kernels 20-30%), layer 1's at 6-12% (the
#: kernels 40-64%); seed 3900000503 layer 2's at 8-22% (the kernels
#: 62-102%) - so a 5% limit on the worst leaf would refuse an exact
#: recurrence, and the kernels' own bfloat16 makes it up to five times
#: worse where it hits (not found which rounding: PERF.md section 7).  On
#: the CPU (hidden 512, 32 heads of 16, eight seeds) the kernels, the
#: kernels with 16-bit p and dp in the backward, and the recurrence read
#: the same to three digits, 61% at the one bad seed.  The float32 system
#: IS the reference to 1e-6, and the scan's own gradients are held to 2e-6
#: in float32 and 2.9e-3 in bfloat16 over the whole row by the core check
#: below (my CPU and chip runs, PR 39; PERF.md section 6).
#: Median: bf16 5.7e-4-7.1e-4 at 24 seeds, 1.03e-3 and 2.05e-3 at the two
#: whose last layer is the fragile one (seeds 3900001123, 3900001153: its
#: error rides back through every layer; the limit stood at 3e-3 until
#: that reading left it one and a half times of room); float8 1.0 (the
#: cotangents underflow); the residual scale from the cut's depth 1.83; no
#: decay 1.1e-3 (refused by the logits and the core).  Share past 5%:
#: bf16 0 at seventeen seeds of twenty-six, 0.074-0.093 at five, 0.148,
#: 0.167 twice and 0.185; float8 0.963, the cut's depth 0.944, no decay
#: 0.24.
GRAD_MEDIAN_LIMIT = 1.0e-2
GRAD_LEAF_TOLERANCE = 0.05
GRAD_OUTLIER_SHARE_LIMIT = 0.5
#: The leaves on the q / k side of a lightning head are what the paragraph
#: above is about: FRAGILE.  Every OTHER leaf's norm (39 of the 54: the
#: MLPs, the v, gate and output projections, the output norms, the sparse
#: layer, embedding, final norm and head) is limited one by one: to the
#: tolerance, or to what the worst fragile leaf reads - a fragile leaf's
#: error rides the stream back into the layers before it (the sparse
#: layer's ``wv`` ``wo`` ``wg`` first) and is never larger there than at
#: its source.  The worst of the 39 beside the worst fragile leaf, my chip
#: runs: 0.18% beside 0.42% and the like at the seeds without an outlier;
#: 6.1% beside 102%, under 5% beside 64%, 23.7% beside 232% and 17.9%
#: beside 71% at the four worst of 26 seeds (a fixed 0.2, then a quarter
#: of the fragile reading, each refused one of those sound runs: the ride
#: back is not proportional).  The cut's depth in the residual reads 1.8-1.9
#: at every leaf, float8 ~1 at every leaf: refused by the median first.
FRAGILE = ("input_norm", "wq", "wk", "q_norm", "k_norm")
#: The selection's block scores against the reference's: rms of the
#: differences over the reference's rms, over the blocks open to a pick.
#: bf16 q and compressed keys: 1.78e-4-1.79e-4 (ten seeds).  float8:
#: 2.95e-3.  A selection a head: 0.93.
SELECT_SCORES_RMS_LIMIT = 7.0e-4
#: The least share of the reference's picks the system's selection holds
#: too.  With random weights the scores are near-uniform and near-ties are
#: many: agreement is a recorded number with a floor, not an equality.
#: bf16: 0.99802-0.99808.  float8: 0.9739.  A selection a head: 0.627.
MIN_SELECTION_AGREEMENT = 0.99
#: Every disputed pick is between blocks whose REFERENCE scores differ by
#: less than this share of the query's smallest picked score: the rounding
#: of a sum of 16 bf16-scored softmaxes.  bf16: 7.9e-4-1.14e-3 (sixteen
#: seeds).  float8: 1.79e-2.  A selection a head: 0.31.
DISPUTED_GAP_LIMIT = 4.0e-3
#: The cores alone, float32 operands, the whole row (:func:`compare_cores`):
#: rms of the differences over the reference's rms, output and the worst
#: input gradient.  The scan: 1.57e-6 and 1.57e-6 (ten seeds; the CPU
#: reads 5e-7); its state carried in bfloat16: 1.21e-3 and 1.21e-3.
LIGHTNING_CORE_LIMIT = 4.0e-5
LIGHTNING_CORE_GRAD_LIMIT = 4.0e-5
#: The sparse attention: 1.17e-6-1.20e-6, its gradients 2.80e-5-2.89e-5
#: (ten seeds)
#: (the blockwise backward's float32, as the dense flash kernels': PERF.md
#: section 7); one bfloat16 rounding of p or ds is 2e-3, a hundred and ten
#: times the limits.
SPARSE_CORE_LIMIT = 2.0e-5
SPARSE_CORE_GRAD_LIMIT = 2.0e-4
#: The same cores on the same operands ROUNDED TO BFLOAT16 - the kernels as
#: the timed step compiles them, which the float32 runs above are not -
#: against the float32 forms fed the rounded values: what the kernels'
#: own bfloat16 (MXU operands, the saved states, the stored outputs) adds.
#: The operands are seeded normals, so the readings hardly move with the
#: seed (thirteen of them, my chip runs, PR 39) and the limits sit one and
#: a half times above them; a second rounding of the output alone would
#: read 1.4 times.  The scan: output 1.9630e-3-1.9642e-3, worst gradient
#: (dq) 2.8723e-3-2.8736e-3; no decay 17.9 both.  The sparse attention:
#: 2.0990e-3-2.1183e-3 and (dq) 2.4171e-3-2.4456e-3; dense attention in
#: its place 0.270 and 0.295.
LIGHTNING_BF16_LIMIT = 3.0e-3
LIGHTNING_BF16_GRAD_LIMIT = 4.3e-3
SPARSE_BF16_LIMIT = 3.2e-3
SPARSE_BF16_GRAD_LIMIT = 3.7e-3
#: ONE REAL OPTIMIZER STEP of the program the Trainer runs a window with
#: (``parallel/train.py:make_multistep``, adamw as the cell builds it, one
#: step long, on the gradient's prefix) from the seeded weights, against a
#: plain float32 adamw step of the REFERENCE's gradients: | change - plain
#: change | / | plain change | over all parameters.  What the first-window
#: loss cannot see in this cell (the configuration's ``loss_tolerance``):
#: a state left unchanged reads exactly 1 (seeds 3900000811, 3000000019).
#: The system reads 0.132-0.151 at ten seeds, 0.165 and 0.171 at the two
#: whose last layer is the fragile one (seeds 3900001153, 3900001123: worst
#: gradient leaf 71% and 232%; the float32 recurrence in the kernels' place
#: 0.136-0.141).  A tenth and more is no rounding of
#: the STEP's: adamw's first step is ``g / (|g| + 1e-8)``, the gradient's
#: sign times the learning rate, on parameters stored in bfloat16 - an
#: element moves by whole ulps or not at all, so a gradient that differs
#: from the reference's by a few percent an element (bfloat16's, upstream)
#: shows where the gradient is smallest: 97.0-97.8% of the elements the
#: plain step moves go the same way, and of the difference's squares
#: 53-60% are elements the system leaves where the plain step moves them
#: (an ulp, mostly), 1-10% elements moved the other way, the rest moved
#: further or less far (37-40% / 28-32% at the two worst seeds); on the CPU
#: at the rehearsal's size 94% is flips and the reading 0.106.
#: ``update_norm_ratio`` reads 0.9807-0.9822 on the chip, 1.0000 on the
#: CPU: the system moves 1.9% less far, not explained (PERF.md section 7).
#: The limit leaves the largest reading three times of room and a state
#: left unchanged two.
UPDATE_REL_LIMIT = 0.5
#: On the CPU (a rehearsal: hidden 64, 4 heads of 16, vocabulary 256,
#: 128-token rows, blocks of 16) the same architecture in bfloat16 is less
#: well conditioned; a rehearsal rehearses the control flow.  The cores are
#: float32 there too, and the CPU's ``exp`` is exact to an ulp.
REHEARSAL = {
    "LOGITS_RMS_LIMIT": 60 * U_BF16, "LOSS_REL_LIMIT": 8e-3,
    "OWN_LOSS_REL_LIMIT": 2e-2, "GRAD_MEDIAN_LIMIT": 0.5, "GRAD_OUTLIER_SHARE_LIMIT": 0.9,
    "SELECT_SCORES_RMS_LIMIT": 40 * U_BF16, "MIN_SELECTION_AGREEMENT": 0.5,
    "DISPUTED_GAP_LIMIT": 0.5, "LIGHTNING_CORE_LIMIT": 1.0e-5,
    "LIGHTNING_CORE_GRAD_LIMIT": 2.0e-5, "SPARSE_CORE_LIMIT": 1.0e-5,
    "SPARSE_CORE_GRAD_LIMIT": 2.0e-5, "LIGHTNING_BF16_LIMIT": 3e-2,
    "LIGHTNING_BF16_GRAD_LIMIT": 3e-2, "SPARSE_BF16_LIMIT": 3e-2,
    "SPARSE_BF16_GRAD_LIMIT": 3e-2, "UPDATE_REL_LIMIT": 0.9,
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
    return sala_flops.minicpm_sala_flops_per_token(c, mix["seq"])


def model_config(c: dict, mix: dict):
    import jax.numpy as jnp

    from ddl_tpu.config import TrainConfig
    from ddl_tpu.ops.sparse_attention import SparseConfig

    t, s = c["training"], sala_flops.sparse_sizes(c)
    if c["attn_use_rope"] or not c["lightning_use_rope"]:
        raise ValueError(
            "models/minicpm_sala.py: sparse layers without positions, RoPE in "
            "the lightning layers"
        )
    if c["lightning_nh"] != c["lightning_nkv"]:
        raise ValueError("models/minicpm_sala.py: one key-value head a lightning head")
    if not (c["qk_norm"] and c["use_output_norm"] and c["use_output_gate"]
            and c["attn_use_output_gate"]):
        raise ValueError("models/minicpm_sala.py: QK-norm, output norm and both gates")
    if (c["hidden_act"], c["attention_bias"], c["tie_word_embeddings"]) != (
        "silu", False, False
    ):
        raise ValueError("models/minicpm_sala.py: SiLU, no biases, an untied head")
    if c["lightning_scale"] != "1/sqrt(d)":
        raise ValueError("models/minicpm_sala.py: the scan's output over sqrt(d)")
    if len(c["mixer_types"]) != c["num_hidden_layers"]:
        raise ValueError("mixer_types is not num_hidden_layers long")
    if c["sparse_config"]["window_size"] % s["block"]:
        raise ValueError("window_size is counted in whole blocks")
    return TrainConfig(remat=t["remat"]).model_config(model.MiniCPMSalaConfig(
        vocab=c["vocab_size"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"], n_kv_heads=c["num_key_value_heads"],
        head_dim=c["head_dim"], n_lightning_heads=c["lightning_nh"],
        lightning_head_dim=c["lightning_head_dim"], d_ff=c["intermediate_size"],
        mixer_types=tuple(c["mixer_types"]), rope_theta=float(c["rope_theta"]),
        scale_emb=float(c["scale_emb"]), scale_depth=c["scale_depth"],
        mup_denominator=c["mup_denominator"], dim_model_base=c["dim_model_base"],
        sparse=SparseConfig(
            block=s["block"], kernel=s["kernel"], stride=s["stride"],
            topk=s["topk"], init_blocks=s["init_blocks"],
            local_blocks=s["local_blocks"],
        ),
        dense_len=s["dense_len"], max_seq=mix["seq"], norm_eps=c["rms_norm_eps"],
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
    s = cfg.sparse
    return reference.Config(
        n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
        lightning_heads=cfg.n_lightning_heads,
        lightning_head_dim=cfg.lightning_head_dim,
        sparse_layers=tuple(kind == model.SPARSE for kind in cfg.mixer_types),
        rope_theta=cfg.rope_theta, scale_emb=cfg.scale_emb,
        residual_scale=cfg.scale_depth / cfg.mup_denominator**0.5,
        logit_div=cfg.d_model / cfg.dim_model_base, norm_eps=cfg.norm_eps,
        block=s.block, kernel=s.kernel, stride=s.stride, topk=s.topk,
        init_blocks=s.init_blocks, local_blocks=s.local_blocks,
        dense_len=cfg.dense_len, query_block=min(QUERY_BLOCK, cfg.max_seq),
        scan_block=min(128, cfg.max_seq),
    )


def _faulty_config(cfg, fault):
    """``cfg`` with a fault that is a configuration's planted:
    ``dense_attention``: plain causal attention in place of the sparse path
    (``dense_len`` past every row); ``cut_depth_residual``: the residual
    scale taken from the cut's depth."""
    if fault == "dense_attention":
        return dataclasses.replace(cfg, dense_len=2**30)
    if fault == "cut_depth_residual":
        return dataclasses.replace(cfg, mup_denominator=cfg.n_layers)
    return cfg


@contextlib.contextmanager
def _planted(fault):
    """Trace the system with a fault of the new mechanisms planted:
    ``bf16_state``: the scan's kernels carry their state from chunk to chunk
    in bfloat16; ``no_decay``: the scan handed ``lam = 1``;
    ``per_head_selection``: every query head selects for itself (the sparse
    layer run with a key-value head a query head, each a copy of its
    group's).  The witness ``f32_recurrence``: the reference's float32
    ``lax.scan`` over positions in the scan kernels' place, fed the q, k, v
    the model hands them.  ``None``, and the faults planted elsewhere
    (:func:`_faulty_config`; the step of :func:`check_programs`' ``update``):
    the system as it stands."""
    if fault in (None, "dense_attention", "cut_depth_residual", "skipped_update"):
        yield
        return
    assert fault in FAULTS + WITNESSES, fault
    import jax
    import jax.numpy as jnp

    from ddl_tpu.ops import lightning_attention
    from ddl_tpu.parallel import ring_attention

    if fault == "bf16_state":
        with mock.patch.object(lightning_attention, "_STATE_DTYPE", jnp.bfloat16):
            yield
        return
    if fault == "no_decay":
        real = lightning_attention.lightning_attention

        def undecayed(q, k, v):
            return real(q, k, v, log_decay=(0.0,) * q.shape[2])

        with mock.patch.object(model, "lightning_attention", undecayed):
            yield
        return
    if fault == "f32_recurrence":
        from benchmarks.lib import minicpm_sala_reference as reference

        def recurrence(q, k, v):
            with jax.default_matmul_precision("highest"):
                o = reference.lightning(
                    *(x.astype(jnp.float32) for x in (q, k, v)),
                    reference.slopes(q.shape[2]), 128, True,
                )
            return o.astype(q.dtype)

        with mock.patch.object(model, "lightning_attention", recurrence):
            yield
        return
    select, attend = model.select_blocks, ring_attention.attention

    def every_head(x, q):
        return jnp.repeat(x, q.shape[2] // x.shape[2], axis=2)

    def select_by_head(q, k, sc):
        return select(q, every_head(k, q), sc)

    def attend_by_head(q, k, v, **kw):
        if kw.get("selection") is not None:
            k, v = every_head(k, q), every_head(v, q)
            kw = dict(kw, kv_repeat=1)
        return attend(q, k, v, **kw)

    with mock.patch.object(model, "select_blocks", select_by_head), \
            mock.patch.object(ring_attention, "attention", attend_by_head):
        yield


def core_inputs(cfg, seed: int):
    """Seeded float32 operands of the two cores at ``cfg``'s head shapes, one
    row of ``cfg.max_seq`` positions: what the norms hand on (normal: unit
    root mean square an element), and an output's cotangent."""
    import jax.numpy as jnp
    import numpy as np

    T = cfg.max_seq
    rng = np.random.default_rng([seed, 39])
    normal = lambda *shape: jnp.asarray(rng.standard_normal(shape, dtype=np.float32))
    H, d = cfg.n_lightning_heads, cfg.lightning_head_dim
    lightning = tuple(normal(1, T, H, d) for _ in range(4))
    sparse = (
        normal(1, T, cfg.n_heads, cfg.head_dim),
        normal(1, T, cfg.n_kv_heads, cfg.head_dim),
        normal(1, T, cfg.n_kv_heads, cfg.head_dim),
        normal(1, T, cfg.n_heads, cfg.head_dim),
    )
    return lightning, sparse


def _learning_rate() -> float:
    """The cell's: ``benchmarks/run.py`` builds ``optax.adamw`` from the
    configuration's ``training`` and leaves every other default."""
    from benchmarks.lib import cells

    with open(os.path.join(cells.HERE, "configs", "minicpm-sala.json")) as f:
        return json.load(f)["training"]["learning_rate"]


def check_programs(cfg, compute_dtype=None, fault=None) -> dict:
    """The comparison's programs for the model ``cfg`` (or a stand-in for
    it): ``errors(stored, rows)``: the two sides' forward passes and
    selections on the same rows, as sums; ``got_norms``: the system's
    gradient norms (``families/afmoe.py:_tap_norms``); ``update(again,
    row)``: one optimizer step of the train loop's own program from the
    weights ``again()`` makes (consumed, and made anew) against a plain
    adamw step of the reference's gradients, and the reference's gradient
    norms; ``lightning_core`` / ``sparse_core(q, k, v, w)``: a
    mixer's core through the system's routine and through the reference's
    on the same operands: the output and the gradient of ``sum(o w)`` with
    respect to every operand, as sums a position."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from benchmarks.lib import minicpm_sala_reference as reference
    from ddl_tpu.models.losses import next_token_cross_entropy
    from ddl_tpu.ops import lightning_attention, sparse_attention
    from ddl_tpu.parallel.mesh import make_mesh
    from ddl_tpu.parallel.train import make_multistep

    c = reference_config(cfg, reference)
    cfg = _faulty_config(cfg, fault)
    sc = cfg.sparse
    # remat changes no forward value: the forward-only program is traced
    # without the policy's jax.checkpoint, so that what a sparse layer
    # selected can leave it beside the logits
    cfg_forward = dataclasses.replace(cfg, remat="none")

    def by_row(loss, logits, t):
        """(rows,): each row's own loss."""
        return jax.vmap(lambda lg, tk: loss(lg[None], tk[None]))(logits, t)

    def system_loss(p, t):
        with _planted(fault):
            return model.next_token_loss(p, t, cfg)

    @jax.jit
    def system_forward(stored, t):
        """Logits, each row's loss, and every sparse layer's selection: its
        block scores and the blocks each query sees, (B, T, G, blocks)."""
        taken = []
        pick = sparse_attention.visible_blocks

        def tapped(scores, sc):
            seen = pick(scores, sc)
            taken.append(tuple(jnp.moveaxis(x, 1, 2) for x in (scores, seen)))
            return seen

        # a tap INSIDE ``select_blocks``: the routine the timed step runs
        # (or a fault's stand-in for it) selects, and what passes between
        # its scores and its lists is recorded on the way
        with mock.patch.object(sparse_attention, "visible_blocks", tapped), \
                _planted(fault):
            got = model.forward(stored, t, cfg_forward)
        return got, by_row(next_token_cross_entropy, got, t), taken

    @jax.jit
    def sums(got, want, t):
        diff2 = (got - want) ** 2
        return {
            # (rows, seq): a position's sums over the vocabulary
            "diff2": jnp.sum(diff2, axis=-1), "want2": jnp.sum(want**2, axis=-1),
            "diff2_max": jnp.max(diff2),
            "reference_loss": by_row(reference.cross_entropy, want, t),
        }

    @jax.jit
    def selection_sums(got_scores, got_seen, want_scores, want_seen):
        """One sparse layer's selection against the reference's, (B, T, G,
        blocks) each: sums of the scores' squares; the picks both hold and
        the reference's; the worst disputed gap, as a share of the query's
        smallest picked reference score."""
        T, nb = want_scores.shape[1], want_scores.shape[-1]
        own = (jnp.arange(T) // sc.block)[None, :, None, None]
        b = jnp.arange(nb)[None, None, None, :]
        open_ = (b >= sc.init_blocks) & (own - b >= sc.local_blocks)
        got_pick, want_pick = got_seen & open_, want_seen & open_
        only_want = jnp.where(want_pick & ~got_pick, want_scores, -jnp.inf)
        only_got = jnp.where(got_pick & ~want_pick, want_scores, jnp.inf)
        floor = jnp.min(jnp.where(want_pick, want_scores, jnp.inf), axis=-1)
        gap = jnp.max(only_want, axis=-1) - jnp.min(only_got, axis=-1)
        gap = jnp.where(jnp.isfinite(gap), gap / floor, 0.0)
        return {
            "scores_diff2": jnp.sum(jnp.where(open_, got_scores - want_scores, 0.0) ** 2),
            "scores_want2": jnp.sum(jnp.where(open_, want_scores, 0.0) ** 2),
            "picks_both": jnp.sum(got_pick & want_pick),
            "picks_want": jnp.sum(want_pick),
            "disputed_gap": jnp.max(gap),
        }

    def a_layer_at_a_time(x, layer, c, r, sparse, seen):
        # the host does not run ahead of the device by more than a layer:
        # arrays queued behind it would all be alive at once
        return jax.block_until_ready(reference._layer(x, layer, c, r, sparse, seen))

    def errors(stored, t):
        """The system (or its stand-in) against the float32 reference on
        the rows ``t``, as sums.  The system's forward pass is one program;
        the reference runs EAGERLY, a stage a program (its docstring), twice:
        selecting for itself - its block scores and picks are what the
        system's are held to - and GIVEN the system's lists, which is what
        the logits are held to.  Both read the STORED weights."""
        sparse_rows = t.shape[1] > c.dense_len and any(c.sparse_layers)

        def selecting_into(kept):
            """The reference's layer, a sparse one selecting for itself and
            leaving (block scores, blocks seen) in ``kept``."""
            def layer_fn(x, layer, c, r, sparse, seen):
                if sparse and sparse_rows:
                    h = reference._normed(x, layer["input_norm"], c.norm_eps, r)
                    q, k, _ = reference.sparse_inputs(h, layer, c, r)
                    scores = reference.block_scores(q, k, c)
                    seen = reference.visible_blocks(scores, c)
                    kept.append((scores, seen))
                return a_layer_at_a_time(x, layer, c, r, sparse, seen)

            return layer_fn

        own, taken = [], []
        want_own = reference.forward(stored, t, c, None, selecting_into(own))
        # each pass is waited for before the next is queued: an array the
        # host has dropped stays allocated until the programs queued on it
        # have run, and with the host three passes ahead the check held
        # 8.20 GiB of arrays on its second row where a pass at a time holds
        # 5.70 - over the train state's 6.62, so the run's
        # ``memory_peak_bytes`` read the check's (my chip runs, PR 39)
        own_loss = jax.block_until_ready(by_row(reference.cross_entropy, want_own, t))
        del want_own
        if compute_dtype is None:
            got, got_loss, taken = jax.block_until_ready(system_forward(stored, t))
        else:
            got = reference.forward(
                stored, t, c, compute_dtype, selecting_into(taken)
            )
            got_loss = by_row(reference.cross_entropy, got, t)
        # the lists the system attended, handed to the reference layer by
        # layer - unless they are not a list a key-value group (a planted
        # fault's): the reference then selects for itself
        by_group = all(
            ours[1].shape == theirs[1].shape for ours, theirs in zip(taken, own)
        )
        lists = iter(taken)
        given = [
            next(lists)[1] if sparse and taken and by_group else None
            for sparse in c.sparse_layers
        ]
        want = reference.forward(stored, t, c, None, a_layer_at_a_time, given)
        out = {"loss": got_loss, "own_loss": own_loss, **sums(got, want, t)}
        del got, want
        # a head's own list is held to its group's
        spread = lambda x, like: jnp.repeat(x, like.shape[2] // x.shape[2], axis=2)
        out["selection"] = [
            selection_sums(*ours, *(spread(x, ours[0]) for x in theirs))
            for ours, theirs in zip(taken, own)
        ]
        out["selections_made"] = np.array([len(taken), len(own)])
        return out

    # -- the cores --------------------------------------------------------------
    # functions of this call's own: jit's cache goes by the function, and a
    # planted fault is a different trace of the same one
    exact = lambda *xs: tuple(x.astype(jnp.float32) for x in xs)

    @jax.jit
    def run_lightning(q, k, v, w):
        if fault == "no_decay":
            f = lambda q, k, v: lightning_attention.lightning_attention(
                q, k, v, log_decay=(0.0,) * q.shape[2]
            )
        else:
            f = lightning_attention.lightning_attention
        o, pull = jax.vjp(f, q, k, v)
        return (o,) + pull(w)

    def reference_lightning(q, k, v, w):
        """EAGERLY, a pass a program, as the reference's forward pass is
        run (its docstring)."""
        with jax.default_matmul_precision("highest"):
            o, pull = jax.vjp(
                lambda q, k, v: reference.lightning(
                    q, k, v, reference.slopes(q.shape[2]), c.scan_block, True
                ), q, k, v,
            )
            return (o,) + pull(w)

    @jax.jit
    def run_sparse(q, k, v, w):
        sel = attended = sparse_attention.select_blocks(q, k, sc)
        T = q.shape[1]
        nb = -(-T // sc.block)
        if fault == "dense_attention":  # every causal block, whatever was selected
            causal = jnp.arange(nb)[None, :] <= (jnp.arange(T) // sc.block)[:, None]
            attended = sparse_attention.make_selection(
                jnp.broadcast_to(causal, (q.shape[0], k.shape[2], T, nb)), sc, q.dtype
            )
        o, pull = jax.vjp(
            lambda q, k, v: sparse_attention.sparse_attention(q, k, v, attended),
            q, k, v,
        )
        seen = sel.visible[:, :, :T, :nb] > 0.5
        return (o,) + pull(w), jnp.moveaxis(seen, 1, 2)

    def reference_sparse(q, k, v, w, seen):
        with jax.default_matmul_precision("highest"):
            o, pull = jax.vjp(
                lambda q, k, v: reference.sparse_attention(
                    q, k, v, seen, sc.block, c.query_block, True
                ), q, k, v,
            )
            return (o,) + pull(w)

    @jax.jit
    def core_sums(got, want):
        """(1 + operands, positions) each: the squared differences and the
        reference's squares, summed over all but the positions."""
        by_position = lambda x: jnp.sum(x, axis=(0, 2, 3))
        got = exact(*got)
        return {
            "diff2": jnp.stack([by_position((a - b) ** 2) for a, b in zip(got, want)]),
            "want2": jnp.stack([by_position(b**2) for b in want]),
        }

    # The operands as given, float32 or the timed bfloat16, through the
    # system; the same VALUES in float32 through the reference.
    def lightning_core(q, k, v, w):
        with _planted(fault):
            got = run_lightning(q, k, v, w)
        return core_sums(got, reference_lightning(*exact(q, k, v, w)))

    def sparse_core(q, k, v, w):
        got, seen = run_sparse(q, k, v, w)
        return core_sums(got, reference_sparse(*exact(q, k, v, w), seen))

    # -- the gradients and one optimizer step ----------------------------------------
    c_grad = c._replace(checkpoint_layers=True)

    # ``_tap_norms`` taps a layer through AFMoE's six-argument ``_layer``
    # that returns (x, picks); this family's returns x.
    def reference_layer(x, w, c, r, _sliding, sparse):
        return reference._layer(x, w, c, r, sparse), None

    def tapped_plain_loss(p, t, layer_fn):
        return reference.loss(
            p, t, c_grad, compute_dtype,
            lambda x, w, c, r, sparse, seen: layer_fn(x, w, c, r, False, sparse)[0],
        )

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

    def plain_adamw(p, g):
        """adamw's FIRST step written out in float32, optax's defaults: the
        moments start from zero, so their bias corrections cancel and the
        step is ``g / (|g| + eps)`` - the gradient's SIGN wherever it is
        well above 1e-8 - plus the decay."""
        p32, g = exact(p, g)
        change = g / (jnp.abs(g) + 1e-8) + 1e-4 * p32
        return (p32 - lr * change).astype(p.dtype)

    @jax.jit
    def update_sums(before, moved, row):
        """From the reference's gradients at ``before`` (which reads the
        STORED weights, as the system does: a leaf's cotangent is rounded to
        the storage dtype once, 1e-5 of its norm), a row a leaf: the squares
        of the two changes' difference, of the plain step's change and of
        the system's; the elements the plain step moves and those of them
        the system moves the same way; the difference's squares where the
        system moves the other way, and where it does not move; then each
        leaf's gradient norm.  One program, so that the gradients are its
        temporaries: as arrays beside ``before`` and ``moved`` they were a
        third copy of the model, and the run's ``memory_peak_bytes`` read
        the check's 7.07 GiB of arrays, not the train state's 6.77 (my chip
        runs, PR 39)."""
        grads = jax.grad(lambda p: reference.loss(p, row, c_grad))(before)

        def leaf(p, new, g):
            (want,) = exact(plain_adamw(p, g))
            p, new = exact(p, new)
            got, want = new - p, want - p
            diff2, moves = (got - want) ** 2, want != 0
            return jnp.stack([
                jnp.sum(diff2), jnp.sum(want**2), jnp.sum(got**2),
                jnp.sum(moves), jnp.sum(moves & (got * want > 0)),
                jnp.sum(jnp.where(got * want < 0, diff2, 0.0)),
                jnp.sum(jnp.where(moves & (got == 0), diff2, 0.0)),
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

    return {
        "errors": errors, "lightning_core": lightning_core,
        "sparse_core": sparse_core, "got_norms": got_norms, "update": update,
    }


#: What a comparison is made of (:func:`compare_with_reference`'s ``parts``).
PARTS = ("forward", "gradients", "cores")


def compare_with_reference(cfg, seed: int, compute_dtype=None, fault=None,
                           parts=PARTS) -> dict:
    """The model the window trains - ``cfg`` as it stands - against the
    float32 reference on the same seeded weights (``cfg``'s storage dtype)
    and ``CHECK_ROWS`` seeded rows of ``cfg.max_seq`` tokens (the module's
    docstring).  Stand-ins for the system, which a limit must refuse: with
    ``compute_dtype`` the reference computed in that precision; with
    ``fault`` the system with that fault planted."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    programs = check_programs(cfg, compute_dtype, fault)
    init = jax.jit(lambda key: model.init_params(cfg, key))
    again = lambda: init(jax.random.fold_in(jax.random.key(seed), 39))
    tokens = jnp.asarray(
        np.random.default_rng([seed, 39]).integers(
            0, cfg.vocab, (CHECK_ROWS, cfg.max_seq), dtype=np.int32
        )
    )
    out = {}
    if "forward" in parts:
        out.update(compare_forward(cfg, programs, again(), tokens))
    if "gradients" in parts:
        row = tokens[:1, : min(GRAD_TOKENS, cfg.max_seq)]
        out.update(compare_gradients(cfg, programs, again, row))
    if "cores" in parts:
        out.update(compare_cores(cfg, seed, programs))
    return out


def compare_forward(cfg, programs, stored, tokens) -> dict:
    """Logits, losses and selections of ``tokens``, a step's rows at a time."""
    import jax
    import numpy as np

    pairs = [
        jax.device_get(programs["errors"](stored, tokens[lo : lo + PAIR_ROWS]))
        for lo in range(0, CHECK_ROWS, PAIR_ROWS)
    ]
    join = lambda key: np.concatenate([p[key] for p in pairs]).astype(np.float64)
    diff2, want2 = join("diff2"), join("want2")
    rms = np.sqrt(want2.sum() / (tokens.size * cfg.vocab))
    loss, own, reference_loss = join("loss"), join("own_loss"), join("reference_loss")
    out = {
        "logits_rel_rms": float(np.sqrt(diff2.sum() / want2.sum())),
        # a position's own: the median and the worst
        "logits_rel_rms_median_position": float(np.median(np.sqrt(diff2 / want2))),
        "logits_rel_rms_worst_position": float(np.max(np.sqrt(diff2 / want2))),
        "logits_rel_max": float(
            np.sqrt(max(float(p["diff2_max"]) for p in pairs)) / rms
        ),
        "loss": loss.tolist(), "reference_loss": reference_loss.tolist(),
        "reference_own_lists_loss": own.tolist(),
        "loss_rel_diff": float(np.max(np.abs(loss - reference_loss) / reference_loss)),
        "own_lists_loss_rel_diff": float(np.max(np.abs(loss - own) / own)),
    }
    picked = [s for p in pairs for s in p["selection"]]
    made = sum(p["selections_made"] for p in pairs)
    out.update(selections_made=int(made[0]), reference_selections_made=int(made[1]))
    if picked:
        total = lambda key: float(sum(np.float64(s[key]) for s in picked))
        out.update(
            selection_layers_rows=len(picked),
            selection_scores_rel_rms=float(
                np.sqrt(total("scores_diff2") / total("scores_want2"))
            ),
            selection_agreement=total("picks_both") / max(total("picks_want"), 1.0),
            selection_disputed_gap=float(max(float(s["disputed_gap"]) for s in picked)),
        )
    return out


def compare_gradients(cfg, programs, again, row) -> dict:
    """On the prefix ``row``: every gradient leaf's norm against the
    reference's, and one optimizer step of the train loop's program against
    a plain adamw step of the reference's gradients.  ``again()`` makes the
    stored weights, a program's own each time: the step consumes its.
    The programs run one after the other and hold at most the train state
    (6.62 GiB) and a step's temporaries on a prefix, or three copies of the
    stored model (2.21 GiB each) and the reference's residuals: the check
    has to stay under the timed step's own peak, or the run's
    ``memory_peak_bytes`` reads the check's."""
    import numpy as np

    got_norms = programs["got_norms"](again(), row)
    (diff2, want2, got2, moved, same, flipped2, still2), want_norms = (
        programs["update"](again, row)
    )
    rel = {
        k: abs(float(got_norms[k]) - float(w)) / float(w)
        for k, w in want_norms.items()
    }
    worst = max(rel, key=rel.get)
    past = {k: round(v, 4) for k, v in rel.items() if v > GRAD_LEAF_TOLERANCE}
    fragile = {
        f"['layers'][{n}]['{name}']"
        for n, kind in enumerate(cfg.mixer_types) if kind == model.LIGHTNING
        for name in FRAGILE
    }
    assert fragile < set(rel), fragile - set(rel)
    sturdy = max(set(rel) - fragile, key=rel.get)
    return {
        "grad_tokens": int(row.shape[1]), "grad_leaves": len(rel),
        "grad_norm_rel_diff_median": float(np.median(list(rel.values()))),
        "grad_outlier_share": len(past) / len(rel), "grad_outliers": past,
        # recorded, not limited (GRAD_MEDIAN_LIMIT's comment)
        "grad_norm_rel_diff": rel[worst], "grad_norm_worst_leaf": worst,
        # the worst of the leaves off a lightning head's q / k side, limited
        # by the tolerance or the worst on it (FRAGILE's comment)
        "grad_norm_rel_diff_sturdy": rel[sturdy], "grad_norm_worst_sturdy_leaf": sturdy,
        "grad_norm_rel_diff_fragile": max(rel[k] for k in fragile),
        # | change - plain change | / | plain change |: 1 where nothing moved
        "update_rel_diff": float(np.sqrt(diff2 / want2)),
        "update_norm_ratio": float(np.sqrt(got2 / want2)),
        "update_sign_agreement": float(same / max(moved, 1.0)),
        # the difference's squares by kind: the system moved an element the
        # other way; did not move it; the rest moved it further or less far
        "update_diff_share_flipped": float(flipped2 / max(diff2, 1e-300)),
        "update_diff_share_unmoved": float(still2 / max(diff2, 1e-300)),
    }


def compare_cores(cfg, seed: int, programs) -> dict:
    """The two cores alone (``check_programs``' ``lightning_core`` and
    ``sparse_core``) on :func:`core_inputs`, over the mix's WHOLE row:
    every chunk the timed step's chains run, forward and reverse, and every
    tile the sparse kernels visit: root mean square of the differences over
    the reference's, of the output and of each operand's gradient (the worst
    is what the limit reads).  Twice: the operands in float32, and rounded
    to bfloat16 - the kernels as the timed step compiles them, against the
    float32 forms fed the same rounded values."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    lightning, sparse = core_inputs(cfg, seed)
    out = {"core_tokens": cfg.max_seq}
    cores = [("lightning", programs["lightning_core"], lightning)]
    if cfg.max_seq > cfg.dense_len:
        cores.append(("sparse", programs["sparse_core"], sparse))
    for name, core, operands in cores:
        for tag, dtype in (("core", jnp.float32), ("core_bf16", jnp.bfloat16)):
            found = jax.device_get(core(*(x.astype(dtype) for x in operands)))
            diff2, want2 = (found[k].astype(np.float64) for k in ("diff2", "want2"))
            rel = np.sqrt(diff2.sum(axis=1) / want2.sum(axis=1))
            grads = dict(zip(("dq", "dk", "dv"), rel[1:].tolist()))
            worst = max(grads, key=grads.get)
            out.update({
                f"{name}_{tag}_rel_rms": float(rel[0]),
                f"{name}_{tag}_grad_rel_rms": grads,
                f"{name}_{tag}_grad_rel_rms_worst": grads[worst],
                f"{name}_{tag}_grad_worst_operand": worst,
            })
    return out


def problems_of(found: dict, rehearsal: bool) -> list:
    """What of a comparison is outside the limits."""
    at_most = [
        ("logits_rel_rms", "LOGITS_RMS_LIMIT", "logits differ from the reference's "
         "(given the system's lists) by this share of their rms"),
        ("loss_rel_diff", "LOSS_REL_LIMIT", "a row's loss differs from the "
         "reference's (given the system's lists)"),
        ("own_lists_loss_rel_diff", "OWN_LOSS_REL_LIMIT", "a row's loss differs "
         "from the reference's with its own lists"),
        ("grad_norm_rel_diff_median", "GRAD_MEDIAN_LIMIT", "the gradient leaves' "
         "norms differ, their median"),
        ("grad_outlier_share", "GRAD_OUTLIER_SHARE_LIMIT", "this share of the "
         "gradient leaves differs in norm by more than 5%"),
        ("update_rel_diff", "UPDATE_REL_LIMIT", "one optimizer step's change of "
         "the parameters differs from a plain adamw step of the reference's "
         "gradients by this share of its norm (1: nothing moved)"),
        ("selection_scores_rel_rms", "SELECT_SCORES_RMS_LIMIT", "the selection's "
         "block scores differ from the reference's by this share of their rms"),
        ("selection_disputed_gap", "DISPUTED_GAP_LIMIT", "a disputed pick lies "
         "between blocks whose reference scores differ by this share"),
        ("lightning_core_rel_rms", "LIGHTNING_CORE_LIMIT", "the scan differs from "
         "the recurrence in float32"),
        ("lightning_core_grad_rel_rms_worst", "LIGHTNING_CORE_GRAD_LIMIT", "a "
         "gradient of the scan differs from the recurrence's in float32"),
        ("sparse_core_rel_rms", "SPARSE_CORE_LIMIT", "the sparse attention "
         "differs from the masked softmax in float32"),
        ("sparse_core_grad_rel_rms_worst", "SPARSE_CORE_GRAD_LIMIT", "a gradient "
         "of the sparse attention differs from the masked softmax's in float32"),
        ("lightning_core_bf16_rel_rms", "LIGHTNING_BF16_LIMIT", "the scan on "
         "bfloat16 operands differs from the recurrence"),
        ("lightning_core_bf16_grad_rel_rms_worst", "LIGHTNING_BF16_GRAD_LIMIT", "a "
         "gradient of the scan on bfloat16 operands differs from the recurrence's"),
        ("sparse_core_bf16_rel_rms", "SPARSE_BF16_LIMIT", "the sparse attention "
         "on bfloat16 operands differs from the masked softmax"),
        ("sparse_core_bf16_grad_rel_rms_worst", "SPARSE_BF16_GRAD_LIMIT", "a gradient "
         "of the sparse attention on bfloat16 operands differs from the masked "
         "softmax's"),
    ]
    problems = [
        f"{what}: {found[key]:.4g}, limit {limit(name, rehearsal):.4g}"
        for key, name, what in at_most
        if key in found and not found[key] <= limit(name, rehearsal)
    ]
    if "grad_norm_rel_diff_sturdy" in found:
        bound = max(GRAD_LEAF_TOLERANCE, found["grad_norm_rel_diff_fragile"])
        if not found["grad_norm_rel_diff_sturdy"] <= bound:
            problems.append(
                f"the norm of {found['grad_norm_worst_sturdy_leaf']}, a gradient leaf "
                f"off the lightning heads' q / k side, differs by "
                f"{found['grad_norm_rel_diff_sturdy']:.4g}, limit {bound:.4g}"
            )
    if found.get("selections_made") != found.get("reference_selections_made"):
        problems.append(
            f"the system selected key blocks {found['selections_made']} times "
            f"where the reference did {found['reference_selections_made']} times"
        )
    floor = limit("MIN_SELECTION_AGREEMENT", rehearsal)
    if "selection_agreement" in found and not found["selection_agreement"] >= floor:
        problems.append(
            f"the selection holds {found['selection_agreement']:.4g} of the "
            f"reference's picks, floor {floor}"
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
