"""What the Xing4.0-29B-A4B cell adds to the benchmark: the FLOP and byte
functions against counts made by hand, the three readers on a made-up
window, the entries, the configuration file against the catalog row, and the
reference check inside the runner - the rehearsal, and the limits against
the stand-ins at the rehearsal's size."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks.lib import cells, xing4_flops

CELL = "xing4.0-29b-a4b.tokens-8k-b1"
RUN = os.path.join(cells.HERE, "run.py")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _config():
    with open(os.path.join(cells.HERE, "configs", "xing4.0-29b-a4b.json")) as f:
        return json.load(f)


# -- FLOPs and bytes ----------------------------------------------------------------


def test_model_flops_by_hand():
    # d=8, 2 heads of 4 + 2 score and 4 value width over a 6-wide latent behind
    # a 5-wide query step, 2 streams, seq 4: a dense layer, a routed layer
    # holding 2 of the router's 8 experts, 4 a token, and the module; vocab 32.
    c = {
        "hidden_size": 8, "num_attention_heads": 2, "qk_nope_head_dim": 4,
        "qk_rope_head_dim": 2, "v_head_dim": 4, "kv_lora_rank": 6, "q_lora_rank": 5,
        "intermediate_size": 24, "moe_intermediate_size": 16, "n_routed_experts": 2,
        "n_shared_experts": 1, "num_experts_per_tok": 4, "first_k_dense_replace": 1,
        "num_hidden_layers": 2, "num_nextn_predict_layers": 1, "hc_mult": 2,
        "vocab_size": 32, "published": {"n_routed_experts": 8},
    }
    pairs = 1 + 2 + 3 + 4
    attn = (2 * 8 * 5 + 2 * 5 * 2 * 6 + 2 * 8 * (6 + 2) + 2 * 6 * 2 * 8 + 2 * 2 * 4 * 8
            + 2 * 2 * (4 + 2 + 4) * pairs / 4)
    wrap = 2 * 2 * 8 * (4 + 4) + 2 * 2 * 8 + 2 * 4 * 8 + 2 * 2 * 8
    expert = 3 * 2 * 8 * 16
    routed = 2 * 8 * 8 + expert + (4 * 2 / 8) * expert
    dense = 3 * 2 * 8 * 24
    want = 3 * (2 * 2 * 8 * 32 + (attn + 2 * wrap + dense) + (attn + 2 * wrap + routed)
                + (2 * 16 * 8 + attn + 2 * wrap + routed))
    assert xing4_flops.xing4_flops_per_token(c, seq=4) == want
    assert xing4_flops.held_experts_per_token(c) == 1.0
    assert xing4_flops.stack_layers(c) == 3


def test_model_flops_of_the_configuration():
    """5.575 GFLOP a token and step at 8,192 with 1 + 6 layers and the
    module: the causal pairs at 192 / 128 over eight layers 36%, both heads
    13%, the wraps' projections and products 0.7% (their cost is bandwidth)."""
    c = _config()
    assert xing4_flops.causal_pairs(8192) == 33_558_528
    assert xing4_flops.held_experts_per_token(c) == 0.5
    total = xing4_flops.xing4_flops_per_token(c, 8192)
    layers = xing4_flops.stack_layers(c)
    assert layers == c["num_hidden_layers"] + 1
    d, n = 3584, 4
    pairs = 3 * layers * 2 * 32 * 320 * xing4_flops.causal_pairs(8192) / 8192
    heads = 3 * 2 * 2 * d * 16384
    wraps = 3 * layers * 2 * (2 * n * d * 24 + 2 * n * d + 2 * n * n * d + 2 * n * d)
    assert pairs / total == pytest.approx(0.36, abs=0.02)
    assert heads / total == pytest.approx(0.13, abs=0.01)
    assert wraps / total == pytest.approx(0.0074, abs=0.001)
    if layers == 8:
        assert total == pytest.approx(5.575e9, rel=0.001)


def test_the_wraps_floor_by_hand():
    c = _config()
    one = 5 * 3584 * 2 + 4 * 24
    assert xing4_flops.hc_pass_bytes(c) == one == 35_936
    floor = xing4_flops.hc_least_seconds_per_step(c, 1, 8192, "selective", 819e9)
    layers = xing4_flops.stack_layers(c)
    assert floor["pre_fwd"] == pytest.approx(4 * layers * 8192 * one / 819e9)
    assert floor["post_fwd"] == pytest.approx(3 * layers * 8192 * one / 819e9)
    assert floor["ends"] == pytest.approx(4 * 2 * 8192 * 5 * 3584 * 2 / 819e9)
    none = xing4_flops.hc_least_seconds_per_step(c, 1, 8192, "none", 819e9)
    assert sum(none.values()) < sum(floor.values())


# -- the readers on a made-up window ------------------------------------------------


class _Table:
    def __init__(self, own, window_s=10.0):
        self.own, self.window_s = own, window_s

    def seconds(self, select):
        return sum(s for key, s in self.own.items() if select(*key))


OWN = {
    ("ddl.hc_pre", "ddl.hc_pre", "forward", "fusion"): 0.5,
    ("ddl.hc_pre", "ddl.hc_pre", "recompute", "fusion"): 0.25,
    ("ddl.hc_post", "ddl.hc_post", "backward", "fusion"): 0.75,
    ("ddl.attn", "ddl.attn", "forward", "fusion"): 2.0,
    ("ddl.attn", "ddl_flash_mla_fwd", "forward", "ddl_flash_mla_fwd"): 3.0,
    (None, None, "forward", "copy"): 0.1,
}


def measured(table, config=None, monkeypatch=None):
    from benchmarks.lib import scopes

    m = {
        "trace": {"window_s": 10.0, "step_program_busy_s": [1.0] * 5} if table else None,
        "config": config or _config(), "mix": cells.load_cell(CELL).mix,
        "chips": 1, "steps_per_window": 2, "peak_flops": 197e12,
    }
    if monkeypatch is not None:
        monkeypatch.setattr(scopes, "table_of_run", lambda m_: table)
    return m


def test_the_path_readers_on_a_made_up_window(monkeypatch):
    hc_share = cells.layer_reader("hc_device_share")
    roofline = cells.layer_reader("hc_roofline_share")
    m = measured(_Table(OWN), monkeypatch=monkeypatch)
    assert hc_share(m) == pytest.approx(100 * 1.5 / 10.0)
    # five executions of a 2-step program: ten steps' floors over 1.5 s
    floor = sum(xing4_flops.hc_least_seconds_per_step(
        _config(), 1, 8192, "selective", 819e9).values())
    assert roofline(m) == pytest.approx(100 * 10 * floor / 1.5)
    assert 0 < roofline(m) < 100


def test_the_readers_find_nothing_where_there_is_nothing(monkeypatch):
    readers = [cells.layer_reader(n) for n in (
        "hc_device_share", "hc_roofline_share", "mtp_device_share")]
    for read in readers:
        assert read({"trace": None}) is None  # a rehearsal: no device plane
    m = measured(None, monkeypatch=monkeypatch)
    m["trace"] = {"window_s": 10.0, "step_program_busy_s": [1.0]}
    for read in readers:
        assert read(m) is None  # a trace without a scope table
    # A program without the scopes (another family's cell, or the parent):
    others = _Table({k: v for k, v in OWN.items()
                     if not (k[0] or "").startswith("ddl.hc_")})
    m = measured(others, monkeypatch=monkeypatch)
    assert readers[0](m) is None and readers[1](m) is None
    with open(os.path.join(cells.HERE, "configs", "kanana-2-30b-a3b.json")) as f:
        kanana = json.load(f)  # another family's configuration
    assert readers[1](measured(_Table(OWN), config=kanana,
                               monkeypatch=monkeypatch)) is None


def test_the_module_reader_cuts_a_path_down_to_its_outer_frame(tmp_path, monkeypatch):
    """``mtp_device_share`` re-tabulates the trace with every path that holds
    ``ddl.mtp`` renamed to it: the module's attention counts as the module's."""
    import tempfile

    from benchmarks.layers import mtp_device_share
    from benchmarks.lib import scopes

    def plane():
        meta = {
            1: scopes.OpMeta("%fusion.1", "jit(_run)/jvp(ddl.mtp)/ddl.attn/dot_general"),
            2: scopes.OpMeta("%fusion.2", "jit(_run)/jvp(ddl.attn)/dot_general"),
            3: scopes.OpMeta("%fusion.3", "jit(_run)/transpose(jvp(ddl.mtp))/mul"),
        }
        ops, t = [], 0.0
        for _ in range(3):  # three executions of the step program
            ops += [(t, t + 0.1, 1), (t + 0.1, t + 0.4, 2), (t + 0.4, t + 0.5, 3)]
            t += 1.0
        modules = [(float(i), i + 0.5, "jit__run") for i in range(3)]
        return scopes.DevicePlane(chip=0, stats={}, meta=meta, ops=ops, modules=modules)

    whole = scopes.tabulate([plane()])
    assert whole is not None
    trace_dir = tmp_path / "ddl_bench_x" / "trace"
    trace_dir.mkdir(parents=True)
    (trace_dir / "a.xplane.pb").write_bytes(b"")
    monkeypatch.setattr(tempfile, "gettempdir", lambda: str(tmp_path))
    monkeypatch.setattr(scopes, "read_planes", lambda path: [plane()])
    monkeypatch.setattr(scopes, "table_of_run", lambda m: whole)
    m = {"trace": {"window_s": whole.window_s}}
    got = mtp_device_share.read(m)
    inner = whole.seconds(lambda scope, *_: scope == "ddl.mtp")
    both = whole.seconds(lambda scope, frame, which, family: family != "fusion.2"
                         and scope in ("ddl.mtp", "ddl.attn"))
    assert got is not None and got > 100 * inner / whole.window_s
    assert 0 < got < 100 and both > inner


# -- the entries and the configuration ---------------------------------------------


def test_the_entries():
    bench = cells.benchmark_file()
    assert bench["configs"][-1]["name"] == "xing4.0-29b-a4b"
    assert bench["configs"][-1]["reduced"] == [
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts", "vocab_size"]
    assert bench["workloads"][-1] == {
        "name": CELL, "config": "xing4.0-29b-a4b", "traffic": "tokens-8k-b1",
        "chips": 1, "why": bench["workloads"][-1]["why"]}
    assert len(bench["workloads"][-1]["why"]) <= 200
    assert len(bench["configs"][-1]["why"]) <= 200
    assert [m["name"] for m in bench["per_layer"][-3:]] == [
        "hc_device_share", "hc_roofline_share", "mtp_device_share"]
    for m in bench["per_layer"][-3:]:
        assert m["workloads"] == [CELL] and m["moves"] == "mfu"
        assert m["layer"] == "model + kernels" and m["source"] == "device_trace"
    cell = cells.load_cell(CELL)
    names = {m["name"] for m in cell.per_layer}
    assert {"flash_device_share", "attn_dense_device_share", "mlp_device_share",
            "moe_dispatch_device_share", "moe_overflow_device_share",
            "head_device_share", "optimizer_device_share",
            "recompute_device_share", "unscoped_device_share",
            "mla_roofline_share", "hc_device_share", "hc_roofline_share",
            "mtp_device_share", "peak_hbm_GiB", "device_idle_share"} <= names
    assert {m["name"] for m in cell.end_to_end} == {"tokens_per_s", "mfu", "setup_s"}
    for name in names:
        cells.layer_reader(name)  # every reader is a file


def test_the_configuration_against_the_catalog_row():
    c = _config()
    with open(CATALOG) as f:
        row = next(json.loads(ln) for ln in f if '"Xing4.0-29B-A4B"' in ln)
    assert c["source"] == row["source_url"]
    reduced = {"num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
               "vocab_size"}
    assert set(c["reduced"]) == reduced
    assert {k: c[k] for k in row["config"] if k not in reduced} == {
        k: v for k, v in row["config"].items() if k not in reduced}
    assert c["published"] == {k: row["config"][k] for k in reduced}
    assert c["num_hidden_layers"] in (5, 6, 7) and c["first_k_dense_replace"] == 1
    assert (c["n_routed_experts"], c["vocab_size"]) == (8, 16384)
    assert c["published"]["vocab_size"] == 8 * c["vocab_size"]
    assert c["published"]["n_routed_experts"] == 8 * c["n_routed_experts"]
    assert c["deployment"]["chips_per_layer"] == 8
    assert {"stream_open_close", "wrap_equations", "wrap_initialisation", "sub_blocks",
            "layer_equations", "yarn", "rope_form", "routing", "expert_bias", "mtp",
            "mtp_loss_weight", "param_dtype", "initialisation", "router_training",
            "learning_rate"} <= set(c["assumed"])
    assert c["training"] == {
        "optimizer": "adamw", "learning_rate": 3e-5, "param_dtype": "bfloat16",
        "remat": "selective", "attn_impl": "auto",
    }
    for key in ("departures", "notes", "guarantees", "loss_tolerance", "rehearsal"):
        assert c[key]
    assert set(c["guarantees"]) == {
        "delivery", "no_fallback", "isolation", "steady", "arithmetic", "dropless",
        "reference"}


def test_the_mix_is_tokens_8k_with_one_row_a_step():
    here = os.path.join(cells.HERE, "jobs")
    with open(os.path.join(here, "tokens-8k.json")) as f:
        base = json.load(f)
    with open(os.path.join(here, "tokens-8k-b1.json")) as f:
        mix = json.load(f)
    changed = {k for k in base if base[k] != mix.get(k)}
    assert changed == {"name", "why", "window_rows", "batch_rows", "rehearsal"}
    assert (mix["seq"], mix["batch_rows"], mix["window_rows"]) == (8192, 1, 2)


def test_the_check_compares_the_mixs_window():
    from benchmarks.families import xing4

    mix = cells.load_cell(CELL).mix
    assert (xing4.CHECK_ROWS, xing4.PAIR_ROWS) == (2 * mix["window_rows"], mix["batch_rows"])
    assert xing4.GRAD_TOKENS == 3072 <= mix["seq"]
    for name, loose in xing4.REHEARSAL.items():
        tight = getattr(xing4, name)
        # The limits the chip is held to are no looser than the rehearsal's.
        assert tight >= loose if name == "MIN_AGREE_SHARE" else tight <= loose, name
    assert len(xing4.NOISE_LEAVES) == 12


# -- the runner -----------------------------------------------------------------------


def _run(*args):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, RUN, *args], cwd=cells.ROOT, env=env,
                          capture_output=True, text=True, timeout=1500)
    lines = [json.loads(ln) for ln in proc.stdout.splitlines() if ln.startswith("{")]
    return proc, lines


REHEARSE = ("--workload", CELL, "--seed", "2147483659", "--seconds", "0.5",
            "--trace", "1", "--rehearsal", "cpu")


def test_the_rehearsal_holds_the_system_to_the_reference_before_it_trains():
    """``Trainer.fit(window_stream=True, mode="process")`` of the cell at its
    rehearsal size on the CPU, the check first; no metric is printed."""
    proc, lines = _run(*REHEARSE)
    assert proc.returncode == 0, proc.stderr[-2000:]
    tags = [ln.get("line") for ln in lines]
    check = lines[tags.index("reference_check")]
    assert tags.index("reference_check") < tags.index("weights")
    assert check["problems"] == [] and check["seed"] == 2147483659
    cell = cells.load_cell(CELL, rehearsal=True)
    assert check["layers"] == cell.config["num_hidden_layers"] == 3
    assert check["mtp"] == 1 and check["remat"] == "selective"
    assert check["held"] == [0, cell.config["n_routed_experts"]]
    assert check["frozen_leaves"] == 6 and check["frozen_grad_norm"] == 0.0
    assert len(check["held_choice_share_by_layer"]) == 3  # two routed + the module's
    assert 0 < check["update_rel_diff"] < 0.7
    assert check["hres_row_sum_off"] < 1e-5 and check["hres_col_sum_off"] < 0.05
    assert len(check["loss"]) == len(check["mtp_loss"]) == 4
    steady = lines[tags.index("steady")]
    assert steady["problems"] == [] and steady["loss_rel_diff"] <= 1e-4
    assert lines[-1]["correct"] is True and lines[-1]["metrics"] == {}


@pytest.mark.parametrize("which", [
    "float8", "no_column_step", "sinkhorn_bf16", "mtp_shift_one", "no_yarn_scale",
    "no_mtp_term", "skipped_update"])
def test_the_limits_refuse_every_stand_in_at_the_rehearsals_size(which):
    import jax.numpy as jnp

    from benchmarks.families import xing4

    cell = cells.load_cell(CELL, rehearsal=True)
    cfg = xing4.model_config(cell.config, cell.mix)
    kw = {"compute_dtype": jnp.float8_e4m3fn} if which == "float8" else {"fault": which}
    parts = ("gradients",) if which in ("no_mtp_term", "skipped_update") else xing4.PARTS
    found = xing4.compare_with_reference(cfg, 7, parts=parts, **kw)
    assert xing4.problems_of(found, rehearsal=True), found
    assert xing4.problems_of(found, rehearsal=False)
