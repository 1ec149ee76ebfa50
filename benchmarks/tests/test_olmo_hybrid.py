"""What the Olmo-Hybrid cell adds to the benchmark: the FLOP and byte
functions against counts made by hand, the chain kernels' calls in the
program's own step against the count the roofline reader uses, the four
readers, the configuration file against the catalog row, the entries of
``BENCHMARK.json``, and the reference check inside the runner."""

import collections
import json
import os
import re
import subprocess
import sys

import pytest

from benchmarks.lib import cells, gdn_flops

CELL = "olmo-hybrid-7b.tokens-16k"
RUN = os.path.join(cells.HERE, "run.py")
LINEAR, FULL = gdn_flops.LINEAR, gdn_flops.FULL


def _config():
    with open(os.path.join(cells.HERE, "configs", "olmo-hybrid-7b.json")) as f:
        return json.load(f)


def test_model_flops_by_hand():
    # d=8, 2 linear heads of 4-wide keys and 6-wide values, kernel 4; a
    # linear layer then a full one; MLP 16; vocabulary slice 32; seq 4.
    c = {
        "hidden_size": 8, "intermediate_size": 16, "linear_num_key_heads": 2,
        "linear_num_value_heads": 2, "linear_key_head_dim": 4,
        "linear_value_head_dim": 6, "linear_conv_kernel_dim": 4,
        "layer_types": [LINEAR, FULL], "vocab_size": 32,
    }
    qk, vv = 2 * 4, 2 * 6
    linear = (
        2 * 8 * (qk + qk + vv + vv + 2 + 2)  # the six projections
        + 2 * vv * 8  # Wo
        + 2 * 4 * (qk + qk + vv)  # three convolutions, 4 taps
        + 2 * 6 * 4 * 6  # the recurrence, 6 d_k d_v a head
    )
    pairs = 1 + 2 + 3 + 4
    full = 4 * 2 * 8 * 8 + 2 * 2 * 8 * pairs / 4  # q k^T and p v over d_model
    mlp = 3 * 2 * 8 * 16
    want = 3 * (linear + full + 2 * mlp + 2 * 8 * 32)
    assert gdn_flops.olmo_hybrid_flops_per_token(c, 4) == pytest.approx(want)
    assert gdn_flops.recurrence_flops(c) == {"fwd": 144.0, "bwd": 288.0}
    assert gdn_flops.recurrence_bytes(c) == {
        "fwd": 2 * (4 + 4 + 6 + 6) + 8, "bwd": 2 * (4 + 4 + 6 + 6) + 2 * (4 + 4 + 6) + 16,
    }


def test_model_flops_of_the_configuration():
    c, mix = _config(), cells.load_cell(CELL).mix
    per_token = gdn_flops.olmo_hybrid_flops_per_token(c, mix["seq"])
    # ISSUE 36's count: 5.69 GFLOP a token; forward MFLOP by part
    assert per_token / 1e9 == pytest.approx(5.69, abs=0.005)
    d, ff = 3840, 11008
    proj = 2 * d * (2 * 2880 + 2 * 5760 + 60) + 2 * 5760 * d
    assert proj / 1e6 == pytest.approx(177.5, abs=0.1)
    assert 30 * gdn_flops.recurrence_flops(c)["fwd"] / 1e6 == pytest.approx(3.3, abs=0.05)
    assert 3 * 2 * d * ff / 1e6 == pytest.approx(253.6, abs=0.1)
    # the recurrence is half a percent of the model FLOPs
    share = 3 * 30 * gdn_flops.recurrence_flops(c)["fwd"] / (per_token / 3)
    assert 0.004 < share < 0.006
    from benchmarks.families import olmo_hybrid

    assert olmo_hybrid.flops_per_sample(c, mix) == per_token
    assert olmo_hybrid.samples_per_row(c, mix) == 16384
    assert olmo_hybrid.sizes(c, mix) == {"seq": 16384, "vocab": 12544}


def test_the_least_time_by_hand():
    c = _config()
    got = gdn_flops.gdn_least_seconds_per_step(c, 1, 16384, "selective", 197e12, 819e9)
    units = 16384 * 30 * 3
    # 1,160 B against 110,592 FLOP a head and token: ~95 FLOP a byte, under
    # the ridge at 240: the bytes decide, 0.70 ms a layer forward
    assert gdn_flops.recurrence_bytes(c)["fwd"] == 1160
    assert got["fwd"] == pytest.approx(units * 1160 / 819e9)
    assert got["fwd"] / 3 == pytest.approx(0.70e-3, abs=0.005e-3)
    assert got["bwd"] == pytest.approx(units * 1936 / 819e9)
    full = gdn_flops.gdn_least_seconds_per_step(c, 1, 16384, "full", 197e12, 819e9)
    assert full["fwd"] == pytest.approx(2 * got["fwd"]) and full["bwd"] == got["bwd"]
    # a chip with more bandwidth than the ridge needs: the FLOPs decide
    fast = gdn_flops.gdn_least_seconds_per_step(c, 1, 16384, "none", 1e12, 1e15)
    assert fast["fwd"] == pytest.approx(units * 110592 / 1e12)


@pytest.mark.parametrize("remat", sorted(gdn_flops.GDN_CALLS_PER_LAYER))
def test_the_call_count_is_the_programs(remat, monkeypatch):
    """The program's own train step under each remat policy, lowered for
    the TPU: the chain kernels' calls a linear layer are the table's."""
    import jax
    import jax.numpy as jnp

    from ddl_tpu.models import olmo_hybrid

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = olmo_hybrid.OlmoHybridConfig(
        vocab=256, d_model=256, n_heads=2, d_ff=256, n_linear_heads=2,
        linear_key_dim=96, linear_value_dim=192, max_seq=1024,
        param_dtype=jnp.bfloat16, remat=remat,
    )
    params = jax.eval_shape(lambda: olmo_hybrid.init_params(cfg, jax.random.key(0)))
    text = jax.jit(jax.value_and_grad(
        lambda p, t: olmo_hybrid.next_token_loss(p, t, cfg)
    )).trace(params, jax.ShapeDtypeStruct((1, 1024), jnp.int32)).lower(
        lowering_platforms=("tpu",)).as_text()
    got = collections.Counter(re.findall(r'kernel_name = "(ddl_gdn_\w+)"', text))
    calls = gdn_flops.GDN_CALLS_PER_LAYER[remat]
    assert dict(got) == {
        family: 3 * calls[which] for family, which in gdn_flops.PASS_OF_FAMILY.items()
    }


# -- the readers --------------------------------------------------------------------


class _Table:
    """What the readers ask of ``scopes.Table``."""

    def __init__(self, own, window_s=10.0):
        self.own, self.window_s = own, window_s

    def seconds(self, select):
        return sum(s for key, s in self.own.items() if select(*key))


OWN = {
    ("ddl.gdn_scan", "ddl_gdn_fwd", "forward", "ddl_gdn_fwd"): 0.05,
    ("ddl.gdn_scan", "ddl_gdn_bwd", "backward", "ddl_gdn_bwd"): 0.07,
    ("ddl.gdn_scan", "ddl.gdn_scan", "forward", "fusion"): 0.4,
    ("ddl.gdn_scan", "ddl.gdn_scan", "recompute", "fusion"): 0.3,
    ("ddl.gdn_proj", "ddl.gdn_proj", "backward", "fusion"): 0.6,
    ("ddl.gdn_conv", "ddl.gdn_conv", "forward", "fusion"): 0.1,
    ("ddl.gdn_out", "ddl.gdn_out", "forward", "multiply_add_fusion"): 0.2,
    ("ddl.attn", "ddl_flash_fwd", "forward", "ddl_flash_fwd"): 0.5,
    ("ddl.mlp", "ddl.mlp", "forward", "fusion"): 4.0,
    (None, None, "forward", "copy"): 0.1,
}


def measured(table, programs=4, config=None):
    return {
        "chips": 1, "steps_per_window": 2, "peak_flops": 197e12,
        "config": config or _config(), "mix": {"batch_rows": 1, "seq": 16384},
        "trace": {"window_s": 10.0, "step_program_busy_s": [2.4] * programs},
        "_table": table,
    }


@pytest.fixture
def tables(monkeypatch):
    from benchmarks.lib import scopes

    monkeypatch.setattr(scopes, "table_of_run", lambda m: m.get("_table"))


def test_the_four_readers_on_a_made_up_window(tables):
    m = measured(_Table(OWN))
    share = cells.layer_reader("gdn_device_share")
    scan = cells.layer_reader("gdn_scan_device_share")
    dense = cells.layer_reader("gdn_dense_device_share")
    roof = cells.layer_reader("gdn_roofline_share")
    assert share(m) == pytest.approx(100 * 0.12 / 10.0)
    assert scan(m) == pytest.approx(100 * (0.4 + 0.3) / 10.0)
    assert dense(m) == pytest.approx(100 * (0.6 + 0.1 + 0.2) / 10.0)
    per_step = gdn_flops.gdn_least_seconds_per_step(
        _config(), 1, 16384, "selective", 197e12, 819e9)
    # 4 programs x 2 steps of both passes against the recurrence AS EXECUTED:
    # the kernels' 0.12 s and the 0.7 s XLA runs around them under the scope
    want = 100 * 8 * sum(per_step.values()) / (0.12 + 0.4 + 0.3)
    assert roof(m) == pytest.approx(want) and 3 < want < 100
    # The traced window's first execution is cut short: counted by time.
    cut = measured(_Table(OWN))
    cut["trace"]["step_program_busy_s"] = [1.2, 2.4, 2.4, 2.4]
    assert roof(cut) == pytest.approx(want * 3.5 / 4)


def test_the_roofline_share_does_not_see_where_the_kernels_line_runs(tables):
    """Work moved from XLA into a kernel at the same speed leaves the share
    alone (and moves between the two device shares); a faster whole raises
    it: numerator and denominator are the same work."""
    roof = cells.layer_reader("gdn_roofline_share")
    scan = cells.layer_reader("gdn_scan_device_share")
    share = cells.layer_reader("gdn_device_share")
    before = measured(_Table(OWN))
    moved = dict(OWN)
    moved[("ddl.gdn_scan", "ddl.gdn_scan", "forward", "fusion")] -= 0.3
    moved[("ddl.gdn_scan", "ddl_gdn_fwd", "forward", "ddl_gdn_fwd")] += 0.3
    after = measured(_Table(moved))
    assert roof(after) == pytest.approx(roof(before))
    assert scan(after) == pytest.approx(scan(before) - 3.0)
    assert share(after) == pytest.approx(share(before) + 3.0)
    fused = dict(OWN)  # the parallel part inside the kernel, and faster for it
    fused[("ddl.gdn_scan", "ddl.gdn_scan", "forward", "fusion")] = 0.05
    fused[("ddl.gdn_scan", "ddl.gdn_scan", "recompute", "fusion")] = 0.05
    fused[("ddl.gdn_scan", "ddl_gdn_fwd", "forward", "ddl_gdn_fwd")] = 0.15
    after = measured(_Table(fused))
    assert share(after) > share(before)  # the kernels' time rose ...
    assert roof(after) > 2 * roof(before)  # ... and the share with the speed
    # a kernel outside the scope's frame (a trace that lost the path) counts
    lost = {(None if k[3].startswith("ddl_gdn") else k[0],) + k[1:]: v
            for k, v in OWN.items()}
    assert roof(measured(_Table(lost))) == pytest.approx(roof(before))


def test_the_readers_find_nothing_where_there_is_nothing(tables):
    readers = [cells.layer_reader(n) for n in (
        "gdn_device_share", "gdn_dense_device_share", "gdn_roofline_share",
        "gdn_scan_device_share")]
    for read in readers:
        assert read({"trace": None}) is None  # a rehearsal: no device plane
        assert read(measured(None)) is None  # a trace without a scope table
    # A program without the kernels or the scopes (another family's cell,
    # or the parent of the PR that brought them): none of the families.
    others = _Table({k: v for k, v in OWN.items() if not (k[0] or "").startswith("ddl.gdn")})
    for read in readers:
        assert read(measured(others)) is None
    with open(os.path.join(cells.HERE, "configs", "mistral-7b-v0.3.json")) as f:
        mistral = json.load(f)  # another family's configuration
    assert readers[2](measured(_Table(OWN), config=mistral)) is None


# -- the entries --------------------------------------------------------------------


def _run_of(names, wanted):
    """Where ``wanted`` stands in ``names`` as a contiguous run (a later PR
    may append behind it: a tail is not compared)."""
    start = names.index(wanted[0])
    assert names[start : start + len(wanted)] == list(wanted)
    return start


def test_the_entries_name_the_layer_and_the_cell():
    bench = cells.benchmark_file()
    new = ["gdn_device_share", "gdn_dense_device_share", "gdn_roofline_share",
           "gdn_scan_device_share"]
    _run_of([e["name"] for e in bench["per_layer"]], new)
    by_name = {e["name"]: e for e in bench["per_layer"]}
    for name, better in zip(new, ("lower", "lower", "higher", "lower")):
        e = by_name[name]
        assert (e["layer"], e["moves"], e["source"], e["unit"], e["better"]) == (
            by_name["flash_device_share"]["layer"], "mfu", "device_trace", "%", better)
        assert e["workloads"] == [CELL]
    at = _run_of([w["name"] for w in bench["workloads"]],
                 ["kanana-2-30b-a3b.tokens-8k", CELL])
    entry = bench["workloads"][at + 1]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "olmo-hybrid-7b", "tokens-16k", 1)
    _run_of([c["name"] for c in bench["configs"]], ["kanana-2-30b-a3b", "olmo-hybrid-7b"])
    config = next(c for c in bench["configs"] if c["name"] == "olmo-hybrid-7b")
    assert config["reduced"] == list(_config()["reduced"])
    assert config["source"] == _config()["source"]
    # the scope shares every decoder cell reports, and the rate
    # all three ``ddl_flash_*`` families stood inside the reduction's ten
    # largest in every traced run (PERF.md section 5; ``ddl_flash_fwd`` tenth)
    for name in ("flash_device_share",
                 "attn_dense_device_share", "mlp_device_share", "head_device_share",
                 "optimizer_device_share", "recompute_device_share",
                 "unscoped_device_share"):
        assert CELL in by_name[name]["workloads"]
    rate = next(e for e in bench["end_to_end"] if e["name"] == "tokens_per_s")
    assert CELL in rate["workloads"]
    # no routed layer, no latent attention; ``flash_roofline_share``'s
    # count (``lib/afmoe_flops.py``) takes every entry of ``layer_types``
    # for an attention layer and reads a ``head_dim`` this configuration
    # does not state: not this cell's without an edit to an accepted file
    for name in ("gmm_device_share", "gmm_roofline_share", "moe_dispatch_device_share",
                 "mla_roofline_share", "flash_roofline_share", "held_choice_share"):
        assert CELL not in by_name[name]["workloads"]
    # seven cells, one of them on four chips
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    cell = cells.load_cell(CELL)
    assert cell.chips == 1 and cell.mix["name"] == "tokens-16k"
    assert {m["name"] for m in cell.end_to_end} == {"tokens_per_s", "mfu", "setup_s"}
    assert set(new) | {"step_device_ms", "mfu_busy", "device_idle_share",
                       "peak_hbm_GiB"} <= {m["name"] for m in cell.per_layer}


def test_the_mix_is_the_issues():
    mix = cells.load_cell(CELL).mix
    assert (mix["seq"], mix["batch_rows"], mix["window_rows"], mix["n_producers"]) == (
        16384, 1, 2, 2)
    assert mix["warmup_windows"] == 4 and mix["mesh"] == {"dp": 1}
    assert cells.load_cell(CELL, rehearsal=True).mix["seq"] == 128


def test_every_width_is_the_catalog_rows():
    c = _config()
    period = [LINEAR, LINEAR, LINEAR, FULL]
    row = {  # architectures.jsonl, Olmo-Hybrid-7B, ``config``
        "model_type": "olmo_hybrid", "vocab_size": 100352, "hidden_size": 3840,
        "intermediate_size": 11008, "num_hidden_layers": 32,
        "num_attention_heads": 30, "num_key_value_heads": 30, "hidden_act": "silu",
        "max_position_embeddings": 65536, "attention_bias": False,
        "rms_norm_eps": 1e-06, "tie_word_embeddings": False,
        "layer_types": period * 8, "linear_num_key_heads": 30,
        "linear_num_value_heads": 30, "linear_key_head_dim": 96,
        "linear_value_head_dim": 192, "linear_conv_kernel_dim": 4,
        "linear_allow_neg_eigval": True, "rope_parameters": {"rope_theta": None},
    }
    reduced = ["num_hidden_layers", "layer_types", "vocab_size"]
    assert list(c["reduced"]) == reduced
    assert {k: c[k] for k in row if k not in reduced} == {
        k: v for k, v in row.items() if k not in reduced
    }
    assert (c["num_hidden_layers"], c["layer_types"], c["vocab_size"]) == (
        4, period, 12544)
    assert c["published"]["num_hidden_layers"] == 32
    assert c["published"]["vocab_size"] == row["vocab_size"] == 8 * c["vocab_size"]
    # The floors: a whole period, at least four layers, an eighth of the vocabulary.
    assert c["layer_types"] == row["layer_types"][:4]
    assert c["deployment"]["chips_per_layer"] == 8
    assert {"norm_placement", "qk_norm", "positions", "conv_bias", "initialisation",
            "layer_equations", "param_dtype", "learning_rate"} <= set(c["assumed"])
    assert c["training"] == {
        "optimizer": "adamw", "learning_rate": 3e-4, "param_dtype": "bfloat16",
        "remat": "selective", "attn_impl": "auto",
    }
    for key in ("departures", "notes", "guarantees", "loss_tolerance", "rehearsal"):
        assert c[key]


def test_the_check_compares_the_mixs_window():
    import inspect

    from benchmarks.families import olmo_hybrid
    from ddl_tpu.models import olmo_hybrid as model

    # The check takes the loss of the logits it compares, in one pass: the
    # same function the model's train loss is.
    assert "next_token_cross_entropy(forward(" in inspect.getsource(
        model.next_token_loss
    )
    mix = cells.load_cell(CELL).mix
    assert olmo_hybrid.CHECK_ROWS == mix["window_rows"] == 2 * mix["batch_rows"]
    assert olmo_hybrid.PAIR_ROWS == mix["batch_rows"]
    assert olmo_hybrid.GRAD_TOKENS == 48 * 64 < mix["seq"]  # 48 chunks
    # ... and the core's check runs the mix's whole row, every head, a pass
    # of ``gated_delta_rule``'s own at a time
    from ddl_tpu.ops import gated_delta

    cfg = olmo_hybrid.model_config(cells.load_cell(CELL).config, mix)
    assert cfg.max_seq == mix["seq"] and cfg.n_linear_heads == 30
    assert olmo_hybrid.core_heads(cfg) == 6 == gated_delta._heads_per_pass(
        mix["batch_rows"], mix["seq"], cfg.n_linear_heads)
    assert mix["seq"] % olmo_hybrid.CORE_BLOCK == 0


# -- the reference check inside the runner ---------------------------------------


def _run(*argv, code=None):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    cmd = [sys.executable, RUN, *argv] if code is None else [sys.executable, "-c", code]
    proc = subprocess.run(cmd, cwd=cells.ROOT, env=env, capture_output=True,
                          text=True, timeout=900)
    lines = [json.loads(ln) for ln in proc.stdout.splitlines() if ln.startswith("{")]
    return proc, lines


REHEARSE = ("--workload", CELL, "--seed", "2147483659", "--seconds", "0.5",
            "--trace", "1", "--rehearsal", "cpu")


def test_the_rehearsal_holds_the_system_to_the_reference_before_it_trains():
    """``Trainer.fit(window_stream=True, mode="process")`` of the cell at
    its rehearsal size on the CPU, the check first."""
    from benchmarks.families import olmo_hybrid

    proc, lines = _run(*REHEARSE)
    assert proc.returncode == 0, proc.stderr[-2000:]
    tags = [ln.get("line") for ln in lines]
    check = lines[tags.index("reference_check")]
    assert tags.index("reference_check") < tags.index("weights")
    assert check["problems"] == [] and check["seed"] == 2147483659
    cell = cells.load_cell(CELL, rehearsal=True)
    # The model the window trains, not a slice of it: every layer, the
    # configured remat; every leaf's gradient.
    assert check["layers"] == cell.config["num_hidden_layers"] == 4
    assert check["remat"] == cell.config["training"]["remat"] == "selective"
    assert check["grad_leaves"] == 3 + 3 * 18 + 11 and check["grad_tokens"] == 128
    assert check["core_tokens"] == 128 and check["core_heads"] == 4
    assert check["core_rel_rms"] < 1e-5 and check["core_grad_rel_rms_worst"] < 1e-5
    assert set(check["core_grad_rel_rms"]) == set(olmo_hybrid.CORE_OPERANDS)
    steady = lines[tags.index("steady")]
    assert steady["problems"] == []
    assert steady["loss_rel_diff"] <= cell.config["loss_tolerance"]["relative"] == 1.5e-4
    assert lines[-1]["correct"] is True and lines[-1]["metrics"] == {}


def _in_the_runner(patch: str):
    """The rehearsal's command with ``patch`` run first in its process."""
    return (
        "import sys, runpy\n"
        f"sys.path.insert(0, {cells.ROOT!r})\n"
        + patch +
        f"sys.argv = {[RUN, *REHEARSE]!r}\n"
        f"runpy.run_path({RUN!r}, run_name='__main__')\n"
    )


@pytest.mark.parametrize("fault", ["no_decay", "beta_not_doubled", "bf16_state"])
def test_a_planted_fault_is_a_non_zero_exit_and_no_result(fault):
    """The system with a fault of the gated delta rule planted for the
    whole run: the check refuses it before a weight exists."""
    proc, lines = _run(code=_in_the_runner(
        "from benchmarks.families import olmo_hybrid as f\n"
        f"planted = f._planted({fault!r})\n"  # kept: a dropped one un-plants
        "planted.__enter__()\n"
    ))
    assert proc.returncode != 0
    assert "not the float32 reference" in proc.stderr
    tags = [ln.get("line") for ln in lines]
    assert "reference_check" in tags and "weights" not in tags
    assert not any("correct" in ln for ln in lines)


def test_a_trainer_that_skips_its_update_reads_correct_false():
    """``loss_tolerance`` is tight enough to see the optimizer: the second
    step's loss is taken on the first step's update, so a Trainer that
    throws its updates away leaves the plain loop's first-window loss by
    more than the tolerance (``tools/probe_gdn_controls.py
    --skipped-update`` is the same run on the chip)."""
    proc, lines = _run(code=_in_the_runner(
        "import optax\n"
        "from ddl_tpu import trainer\n"
        "init = trainer.Trainer.__init__\n"
        "def skipping(self, *a, optimizer, **kw):\n"
        "    init(self, *a, optimizer=optax.chain(optimizer, optax.scale(0.0)), **kw)\n"
        "trainer.Trainer.__init__ = skipping\n"
    ))
    assert proc.returncode == 0, proc.stderr[-2000:]  # the run ends, its verdict is the line's
    assert lines[-1]["correct"] is False
    steady = next(ln for ln in lines if ln.get("line") == "steady")
    assert any("first-window loss" in p for p in steady["problems"]), steady["problems"]
    tol = _config()["loss_tolerance"]["relative"]
    assert steady["loss_rel_diff"] > 2 * tol  # 5.0e-4 on the chip, 3.3 times


def test_a_program_without_the_model_refuses_the_cell_at_once():
    """The parent commit with this PR's benchmark files laid over it: the
    family's import fails while the runner loads the cell."""
    proc, lines = _run(code=(
        "import sys, runpy\n"
        f"sys.path.insert(0, {cells.ROOT!r})\n"
        "sys.modules['ddl_tpu.models.olmo_hybrid'] = None\n"
        f"sys.argv = {[RUN, *REHEARSE]!r}\n"
        f"runpy.run_path({RUN!r}, run_name='__main__')\n"
    ))
    assert proc.returncode != 0 and lines == []
    assert "ImportError" in proc.stderr or "ModuleNotFoundError" in proc.stderr


def test_the_limits_refuse_every_stand_in():
    """bf16 is what the configuration states: the reference computed in
    float8_e4m3fn is outside the limits, and so is the system with the
    decay left out, with beta not doubled, or with the chain's state
    carried in bfloat16 - each by one of the limits."""
    import jax.numpy as jnp

    from benchmarks.families import olmo_hybrid

    cell = cells.load_cell(CELL, rehearsal=True)
    cfg = olmo_hybrid.model_config(cell.config, cell.mix)
    as_configured = olmo_hybrid.compare_with_reference(cfg, seed=5)
    assert olmo_hybrid.problems_of(as_configured, rehearsal=True) == []
    stand_ins = [dict(compute_dtype=jnp.float8_e4m3fn)] + [
        dict(fault=fault) for fault in olmo_hybrid.FAULTS
    ]
    for kw in stand_ins:
        found = olmo_hybrid.compare_with_reference(cfg, seed=5, **kw)
        assert olmo_hybrid.problems_of(found, rehearsal=True), kw
        assert olmo_hybrid.problems_of(found, rehearsal=False), kw
        if kw.get("fault") == "bf16_state":
            assert found["core_rel_rms"] > 3 * olmo_hybrid.REHEARSAL_CORE_RMS_LIMIT
            assert (found["core_grad_rel_rms_worst"]
                    > 3 * olmo_hybrid.REHEARSAL_CORE_GRAD_RMS_LIMIT)
        else:
            assert found["logits_rel_rms"] > olmo_hybrid.REHEARSAL_LOGITS_RMS_LIMIT, kw
    # The limits the chip is held to are tighter than the rehearsal's.
    assert olmo_hybrid.LOSS_REL_LIMIT < olmo_hybrid.REHEARSAL_LOSS_REL_LIMIT
    assert olmo_hybrid.GRAD_NORM_LIMIT < olmo_hybrid.REHEARSAL_GRAD_NORM_LIMIT
    assert olmo_hybrid.LOGITS_RMS_LIMIT < olmo_hybrid.REHEARSAL_LOGITS_RMS_LIMIT
    # ... but for the core's own: the chip's exp is what the recurrence's
    # 16,384 decays accumulate there, the CPU's is exact to an ulp.
    assert olmo_hybrid.REHEARSAL_CORE_RMS_LIMIT < olmo_hybrid.CORE_RMS_LIMIT
    assert olmo_hybrid.REHEARSAL_CORE_GRAD_RMS_LIMIT < olmo_hybrid.CORE_GRAD_RMS_LIMIT
