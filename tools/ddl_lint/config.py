"""Lint configuration: defaults, ``[tool.ddl_lint]`` loading, path ignores.

The config layer answers three questions for the runner:

- which checks are enabled (``enable`` / ``disable``),
- which paths get which codes ignored (``per_path_ignores``),
- checker parameters that are repo policy rather than universal truth
  (the lock hierarchy, the hot-path class list).

Loading prefers stdlib ``tomllib`` (3.11+); on 3.10 (this container) a
minimal TOML-subset reader handles the ``[tool.ddl_lint]`` tables, whose
values are restricted to strings, booleans, and arrays of strings — all of
which are also valid Python literals.
"""

from __future__ import annotations

import ast
import dataclasses
import re
from pathlib import Path
from typing import Dict, List, Optional, Tuple

#: Every shipped check code, in numeric order.  ``ALL_CODES`` is the
#: default ``enable`` set; the registry in ``checkers/`` must stay in sync
#: (``test_lint.py`` asserts it does).
ALL_CODES: Tuple[str, ...] = (
    "DDL001",  # host sync inside jit
    "DDL002",  # tracer-leaking closure write inside jit
    "DDL003",  # constant PRNGKey in a loop
    "DDL004",  # unbounded sleep-poll loop
    "DDL005",  # time.sleep on a hot-path class
    "DDL006",  # lock acquisition against the declared hierarchy
    "DDL007",  # broad except swallows ShutdownRequested/KeyboardInterrupt
    "DDL008",  # ctypes binding missing restype/argtypes
    "DDL009",  # non-exhaustive enum dispatch without a default
    "DDL010",  # jax.jit constructed inside a loop
    "DDL011",  # fresh staging copy/allocation in an ingest hot path
    "DDL012",  # unbounded blocking wait (no timeout) on a framework path
    "DDL013",  # unbounded module/instance-level dict cache (no eviction)
    "DDL014",  # jax.checkpoint/remat without an explicit policy
    "DDL015",  # materialize-then-copy into the producer window view
    "DDL016",  # host round-trip in a device-distribution hot path
    "DDL017",  # train-step jax.jit without donate_argnums/donate_argnames
    "DDL018",  # cluster loop with no deadline or lease-expiry check
    "DDL019",  # blocking wait inside a per-tenant serve loop
    "DDL020",  # host sync inside a fused compute/ingest step function
    "DDL021",  # wire-path decode-then-requantize / unbounded codec call
    "DDL022",  # bare checkpoint write bypassing atomic temp+rename
    "DDL023",  # unbounded obs event buffer / span emission per sample
    "DDL024",  # bare threading.Lock()/RLock()/Condition() without identity
    "DDL025",  # raw control-command send bypassing the acked envelope seam
    "DDL026",  # direct FairShareScheduler mutation outside the fabric seam
    "DDL027",  # hardcoded tuning constant bypassing the tune seam
)


@dataclasses.dataclass
class LintConfig:
    enable: List[str] = dataclasses.field(
        default_factory=lambda: list(ALL_CODES)
    )
    disable: List[str] = dataclasses.field(default_factory=list)
    #: Classes whose methods form a consumer/producer hot path: any
    #: ``time.sleep`` inside them is DDL005.
    hot_path_classes: List[str] = dataclasses.field(
        default_factory=lambda: ["DistributedDataLoader", "DataPusher"]
    )
    #: Declared lock hierarchy, outermost first.  A ``with`` acquiring a
    #: lock while one LATER in this list is held is DDL006.
    lock_order: List[str] = dataclasses.field(
        default_factory=lambda: [
            "_build_lock", "_cond", "_lock", "_sweep_lock", "_spill_lock",
        ]
    )
    #: Functions (bare name or ``Class.method``) forming the per-batch
    #: ingest feed into ``device_put``: fresh copies/allocations inside
    #: them are DDL011 (stage through the StagingPool instead).
    ingest_hot_path_functions: List[str] = dataclasses.field(
        default_factory=lambda: [
            "DeviceIngestor.put",
            "DeviceIngestor.put_batch",
            "PrefetchIterator.__next__",
            "TransferExecutor._run",
        ]
    )
    #: Producer fill functions (bare name or ``Class.method``) whose
    #: ``my_ary`` may be a LIVE ring-slot view (inplace fill): writing a
    #: freshly materialized temp into it is DDL015 (gather straight into
    #: the view instead).
    producer_fill_functions: List[str] = dataclasses.field(
        default_factory=lambda: [
            "ArrayProducer._fill",
            "FileShardProducer._load_next",
            "WebDatasetProducer._fill",
            "TokenStreamProducer._fill",
            "PackedTokenProducer._fill",
            "TFRecordTokenProducer._fill",
        ]
    )
    #: Device-distribution functions (bare name or ``Class.method``)
    #: moving device-resident windows between devices (the ICI tier):
    #: ``jax.device_get`` / blocking ``np.asarray`` host round-trips
    #: inside them are DDL016 (the hop must stay on ICI).
    device_path_functions: List[str] = dataclasses.field(
        default_factory=lambda: [
            "IciDistributor.put",
            "IciDistributor.distribute",
            "IciDistributor._distribute_planned",
            "IciDistributor._onto_mesh",
            "fanout_replicate",
            "fanout_shard",
            "replicated_view",
            "_as_ring_input",
        ]
    )
    #: Train-step builder functions (bare name or ``Class.method``): a
    #: ``jax.jit``/``functools.partial(jax.jit, ...)`` inside them that
    #: omits ``donate_argnums``/``donate_argnames`` is DDL017 (undonated
    #: params + optimizer state double peak HBM across the update).
    train_step_functions: List[str] = dataclasses.field(
        default_factory=lambda: [
            "make_train_step",
            "make_multistep",
        ]
    )
    #: Cluster control-plane functions (bare name or ``Class.method``):
    #: every ``while`` loop inside them must consult a deadline or
    #: lease expiry (DDL018) — an unbounded heartbeat/retry spin on a
    #: silent peer is exactly the hang the control plane exists to kill.
    cluster_loop_functions: List[str] = dataclasses.field(
        default_factory=lambda: [
            "ClusterSupervisor.run",
            "ClusterSupervisor._run",
            "ClusterSupervisor.wait_for_epoch",
            "probe_link_costs",
            "measure_assignment",
        ]
    )
    #: Serve control-plane functions (bare name or ``Class.method``):
    #: scheduler/admission loops iterating the TENANT set.  A blocking
    #: wait inside a per-tenant ``for`` body is DDL019 — per-iteration
    #: timeouts multiply by the tenant count, which is unbounded by
    #: design (block once per pass, outside the fan-out).
    serve_loop_functions: List[str] = dataclasses.field(
        default_factory=lambda: [
            "FairShareScheduler.admit",
            "FairShareScheduler._advance_round_if_stuck",
            "FairShareScheduler.revoke_inflight",
            "Autoscaler.step",
            "Autoscaler._run",
            "AdmissionController.report",
        ]
    )
    #: Fused compute/ingest step functions (bare name or
    #: ``Class.method``): the host must never wait on the device inside
    #: them — a stray ``block_until_ready``/``device_get``/
    #: ``float(array)``/``.item()`` re-serializes the data plane behind
    #: compute (DDL020).
    fused_step_functions: List[str] = dataclasses.field(
        default_factory=lambda: [
            "Trainer._fused_stream_loop",
            "DistributedDataLoader.gate_release_on",
            "DistributedDataLoader._sweep_release_backlog",
            "IciDistributor._distribute_planned",
            "IciDistributor._track_in_flight",
        ]
    )
    #: Wire-path functions (bare name or ``Class.method``): they sit
    #: between a wire encode and the send.  A decode-family result
    #: feeding an encode-family call (the decode-then-requantize temp)
    #: or a codec call without its explicit ``level``/``max_output``
    #: bound is DDL021.
    wire_path_functions: List[str] = dataclasses.field(
        default_factory=lambda: [
            "DataPusher._encode_and_commit",
            "ThreadExchangeShuffler._encode_lane",
            "ThreadExchangeShuffler._decode_lane",
            "IciDistributor._distribute_planned",
            "CodecBackend.open",
            "pack_rows",
            "unpack_rows",
        ]
    )
    #: Checkpoint writer functions (bare name or ``Class.method``):
    #: every file write inside them must route through the atomic
    #: temp+rename helper (``ddl_tpu.checkpoint.atomic_file_write``) —
    #: a bare ``open(..., "w")``/``np.save`` to the final path is
    #: DDL022 (a crash mid-write tears the NEWEST generation).
    checkpoint_write_functions: List[str] = dataclasses.field(
        default_factory=lambda: [
            "LoaderCheckpoint.save",
            "save_train_state",
            "_write_manifest",
            "AsyncCheckpointer._write_generation",
        ]
    )
    #: Control-command originators (bare name or ``Class.method``):
    #: inside them a raw ``.send``/``.send_control`` of a ``types.py``
    #: control message (``ReplayRequest``/``ShardAdoption``/a
    #: hand-rolled ``ControlEnvelope``) is DDL025 — commands must ride
    #: the acked envelope seam (``send_control_acked``) so delivery is
    #: at-least-once, dedup'd, and fenced against zombie leaders.
    control_send_functions: List[str] = dataclasses.field(
        default_factory=lambda: [
            "ElasticCluster._send_adoptions",
            "ElasticCluster._on_rank_respawned",
            "ConsumerConnection.request_replay",
        ]
    )
    #: Sanctioned FairShareScheduler mutators (bare name or
    #: ``Class.method``): the tenancy facade, the fabric
    #: apply/crash/rebuild path, and HA promotion adopt.  Everywhere
    #: else a direct scheduler mutation is DDL026 — admission state is
    #: supervisor-resident and journaled; unjournaled pokes diverge
    #: across failover.
    fabric_admission_functions: List[str] = dataclasses.field(
        default_factory=lambda: [
            "Tenant.admit",
            "Tenant.note_served",
            "Tenant.note_aborted",
            "Tenant.revoke_inflight",
            "Tenant.clear_revocations",
            "AdmissionController.register",
            "AdmissionController._release",
            "AdmissionController.revoke_inflight",
            "AdmissionController.clear_revocations",
            "IngestFabric._apply",
            "IngestFabric._crash",
            "IngestFabric.from_journal",
            "SupervisorHA.promote",
        ]
    )
    #: Observability event-buffer classes (DDL023 half 1): every
    #: event-growth site inside them must append to a
    #: ``deque(maxlen=...)``-bounded attribute — an armed log on a
    #: week-long run must drop oldest events, never eat the host.
    obs_event_buffer_classes: List[str] = dataclasses.field(
        default_factory=lambda: ["SpanLog", "FlightRecorder", "StartupRecord"]
    )
    #: Per-SAMPLE hot functions (DDL023 half 2): span emission inside
    #: their loops is a finding — per-window spans are sanctioned,
    #: per-sample spans at ingest rates destroy the experiment.
    per_sample_hot_functions: List[str] = dataclasses.field(
        default_factory=lambda: [
            "ArrayProducer._fill",
            "FileShardProducer._load_next",
            "WebDatasetProducer._fill",
            "TokenStreamProducer._fill",
            "PackedTokenProducer._fill",
            "TFRecordTokenProducer._fill",
            "PrefetchIterator.__next__",
        ]
    )
    #: Tuned-knob functions (bare name or ``Class.method``): the path a
    #: tuning knob value takes into the data plane.  A literal
    #: ``depth=``/``prefetch_depth=``/``max_queue=``/``max_per_key=``/
    #: ``wire_dtype=`` default or call keyword inside one is DDL027 —
    #: it pins the knob against every Calibrator/KnobController
    #: decision (route through envspec/TunedConfig instead).
    tuned_knob_functions: List[str] = dataclasses.field(
        default_factory=lambda: [
            "DistributedDataLoader.prefetch",
            "PrefetchIterator.__init__",
            "StagingPool.__init__",
            "TransferExecutor.__init__",
            "Trainer.fit",
        ]
    )
    #: Modules allowed to construct bare threading primitives — the
    #: named-lock factory itself (DDL024 exempts these; everything else
    #: constructs through ``ddl_tpu.concurrency.named_*``).
    lock_factory_modules: List[str] = dataclasses.field(
        default_factory=lambda: ["ddl_tpu/concurrency.py"]
    )
    #: path-prefix (repo-relative, '/'-separated) -> codes ignored under it.
    per_path_ignores: Dict[str, List[str]] = dataclasses.field(
        default_factory=dict
    )

    def enabled_codes(self) -> List[str]:
        return [c for c in self.enable if c not in set(self.disable)]

    def ignored_for(self, rel_path: str) -> set:
        rel = rel_path.replace("\\", "/")
        out: set = set()
        for prefix, codes in self.per_path_ignores.items():
            if rel.startswith(prefix.rstrip("/") + "/") or rel == prefix:
                out.update(codes)
        return out


_SECTION = "tool.ddl_lint"


def _parse_toml_subset(
    text: str, section: str = _SECTION
) -> Dict[str, Dict[str, object]]:
    """Parse just enough TOML for one ``[tool.<name>]`` section family.

    Handles ``[section]`` headers and ``key = <literal>`` lines where the
    literal is a (possibly multi-line) array of strings, a quoted string,
    or a boolean.  Everything outside ``<section>*`` tables is skipped
    without parsing, so the rest of pyproject.toml may use any TOML
    feature.  ``tools/ddl_verify`` reuses this with its own section.
    """
    tables: Dict[str, Dict[str, object]] = {}
    cur = None
    pending_key: Optional[str] = None
    pending_val = ""
    for raw in text.splitlines():
        # Comments may trail any line, including continuation lines of a
        # multi-line array — strip them (quote-aware) BEFORE joining, or
        # the first inline comment would comment out the rest of the
        # joined literal and the key would silently fall back to default.
        line = _strip_inline_comment(raw).strip()
        if pending_key is not None:
            pending_val += " " + line
            if _literal_complete(pending_val):
                tables[cur][pending_key] = _eval_literal(pending_val)
                pending_key = None
            continue
        if not line or line.startswith("#"):
            continue
        m = re.match(r"^\[([^\]]+)\]$", line)
        if m:
            name = m.group(1).strip()
            if name == section or name.startswith(section + "."):
                cur = name
                tables.setdefault(cur, {})
            else:
                cur = None
            continue
        if cur is None:
            continue
        m = re.match(r"^([A-Za-z0-9_./\"'*-]+)\s*=\s*(.*)$", line)
        if not m:
            continue
        key = m.group(1).strip().strip("\"'")
        val = m.group(2).strip()
        if _literal_complete(val):
            tables[cur][key] = _eval_literal(val)
        else:  # array continues on following lines
            pending_key, pending_val = key, val
    return tables


def _strip_inline_comment(line: str) -> str:
    """Drop a trailing ``# ...`` comment, respecting quoted strings."""
    out = []
    quote = None
    for ch in line:
        if quote is not None:
            out.append(ch)
            if ch == quote:
                quote = None
        elif ch in "\"'":
            quote = ch
            out.append(ch)
        elif ch == "#":
            break
        else:
            out.append(ch)
    return "".join(out)


def _literal_complete(val: str) -> bool:
    if val.startswith("["):
        return val.count("[") == val.count("]")
    return True


def _eval_literal(val: str) -> object:
    val = val.strip()
    if val in ("true", "false"):
        return val == "true"
    try:
        return ast.literal_eval(val)
    except (ValueError, SyntaxError):
        return val  # bare string; tolerated rather than fatal


def _load_tables(
    pyproject: Path, section: str = _SECTION
) -> Dict[str, Dict[str, object]]:
    text = pyproject.read_text()
    tool_key = section.split(".", 1)[1]  # "tool.ddl_lint" -> "ddl_lint"
    try:
        import tomllib  # Python 3.11+

        data = tomllib.loads(text)
        tool = data.get("tool", {}).get(tool_key)
        if tool is None:
            return {}
        tables: Dict[str, Dict[str, object]] = {section: {}}
        for k, v in tool.items():
            if isinstance(v, dict):
                tables[f"{section}.{k}"] = dict(v)
            else:
                tables[section][k] = v
        return tables
    except ModuleNotFoundError:
        return _parse_toml_subset(text, section)


def find_pyproject(start: Path) -> Optional[Path]:
    cur = start.resolve()
    if cur.is_file():
        cur = cur.parent
    for p in (cur, *cur.parents):
        cand = p / "pyproject.toml"
        if cand.is_file():
            return cand
    return None


def load_config(pyproject: Optional[Path]) -> LintConfig:
    """Build a LintConfig from a pyproject.toml (or defaults if absent)."""
    cfg = LintConfig()
    if pyproject is None or not pyproject.is_file():
        return cfg
    tables = _load_tables(pyproject)
    main = tables.get(_SECTION, {})

    def str_list(key: str, cur: List[str]) -> List[str]:
        v = main.get(key)
        if isinstance(v, (list, tuple)) and all(isinstance(s, str) for s in v):
            return list(v)
        return cur

    cfg.enable = str_list("enable", cfg.enable)
    cfg.disable = str_list("disable", cfg.disable)
    cfg.hot_path_classes = str_list("hot_path_classes", cfg.hot_path_classes)
    cfg.lock_order = str_list("lock_order", cfg.lock_order)
    cfg.ingest_hot_path_functions = str_list(
        "ingest_hot_path_functions", cfg.ingest_hot_path_functions
    )
    cfg.producer_fill_functions = str_list(
        "producer_fill_functions", cfg.producer_fill_functions
    )
    cfg.device_path_functions = str_list(
        "device_path_functions", cfg.device_path_functions
    )
    cfg.train_step_functions = str_list(
        "train_step_functions", cfg.train_step_functions
    )
    cfg.cluster_loop_functions = str_list(
        "cluster_loop_functions", cfg.cluster_loop_functions
    )
    cfg.serve_loop_functions = str_list(
        "serve_loop_functions", cfg.serve_loop_functions
    )
    cfg.fused_step_functions = str_list(
        "fused_step_functions", cfg.fused_step_functions
    )
    cfg.wire_path_functions = str_list(
        "wire_path_functions", cfg.wire_path_functions
    )
    cfg.checkpoint_write_functions = str_list(
        "checkpoint_write_functions", cfg.checkpoint_write_functions
    )
    cfg.control_send_functions = str_list(
        "control_send_functions", cfg.control_send_functions
    )
    cfg.fabric_admission_functions = str_list(
        "fabric_admission_functions", cfg.fabric_admission_functions
    )
    cfg.obs_event_buffer_classes = str_list(
        "obs_event_buffer_classes", cfg.obs_event_buffer_classes
    )
    cfg.per_sample_hot_functions = str_list(
        "per_sample_hot_functions", cfg.per_sample_hot_functions
    )
    cfg.tuned_knob_functions = str_list(
        "tuned_knob_functions", cfg.tuned_knob_functions
    )
    cfg.lock_factory_modules = str_list(
        "lock_factory_modules", cfg.lock_factory_modules
    )
    ignores = tables.get(f"{_SECTION}.per_path_ignores", {})
    cfg.per_path_ignores = {
        str(k): [str(c) for c in v]
        for k, v in ignores.items()
        if isinstance(v, (list, tuple))
    }
    return cfg
