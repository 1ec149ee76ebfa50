"""The machine-checked ``DDL_TPU_*`` environment-knob registry.

Every environment variable the framework reads is declared here — name,
type, default, export group, and a one-line doc — and every read in
``ddl_tpu/`` resolves through the typed accessors (:func:`raw`,
:func:`get`, :func:`flag`).  ``tools/ddl_verify`` pass **VP003**
enforces the contract statically: an undeclared read, a raw
``os.environ`` read bypassing the accessors, a spawn-boundary export
function missing one of its group's knobs, or a registered knob nothing
reads are all findings.  ``docs/CONFIG.md`` is generated from this
registry (``python -m ddl_tpu.envspec``) and a test asserts doc ↔
registry agreement, so the operator-facing table can never drift from
the code.

Three knob sources:

- ``env`` — knobs read directly by name somewhere in ``ddl_tpu/``.
- ``config`` — the ``DDL_TPU_<FIELD>`` family ``LoaderConfig.load``
  derives from its dataclass fields (``config.py`` ``_load_layered``).
- ``train`` — the ``DDL_TPU_TRAIN_<FIELD>`` family from ``TrainConfig``.

A knob may be both (``DDL_TPU_MODE`` is read literally in ``env.py``
AND layered by ``LoaderConfig.load``); the registry stores one entry
with the ``config_field`` annotation, and :func:`validate` asserts the
literal default and the dataclass default agree — the drift VP003's
export check catches across the spawn boundary, caught here across the
config boundary.

Sentinel-typed knobs (``default=None``) distinguish *unset* from any
set value; their call sites use :func:`raw` and keep their tri-state
logic (e.g. ``DDL_TPU_WIRE_DTYPE``: unset = per-reader capability
decides, ``"raw"`` = kill switch, lossy value = force the tier).
"""

from __future__ import annotations

import dataclasses
import os
from typing import Any, Dict, Iterable, List, Optional

from ddl_tpu.config import LoaderConfig, TrainConfig

#: Values (lowercased) a boolean knob treats as OFF; anything else set
#: is ON.  One shared falsy set — per-module copies drifted (the
#: original ``utils.env_flag`` contract, now registry-wide).
FALSY = ("0", "off", "false")


class UnknownKnobError(KeyError):
    """An env read named a ``DDL_TPU_*`` variable the registry does not
    declare — register it in :mod:`ddl_tpu.envspec` (VP003's runtime
    twin)."""


@dataclasses.dataclass(frozen=True)
class Knob:
    """One declared environment knob."""

    name: str
    type: str  # "bool" | "int" | "float" | "str"
    default: Any  # typed default; None = sentinel (unset is meaningful)
    doc: str
    #: Spawn-boundary mirror group: ``ddl_tpu.env._export_<group>_knobs``
    #: must cover every knob carrying its group (VP003 checks).
    export: Optional[str] = None
    #: LoaderConfig field this knob mirrors (the DDL_TPU_<FIELD> family).
    config_field: Optional[str] = None
    #: TrainConfig field this knob mirrors (DDL_TPU_TRAIN_<FIELD>).
    train_field: Optional[str] = None
    #: Read outside ddl_tpu/ (the test harness's knob) or only through
    #: a computed name (the config families): VP003 skips its
    #: "registered but never read" hygiene check.
    external: bool = False


def _K(name: str, type: str, default: Any, doc: str, **kw: Any) -> Knob:
    return Knob(name=name, type=type, default=default, doc=doc, **kw)


#: Explicit entries for every knob read by name in ``ddl_tpu/`` (plus
#: the documented test-harness knob).  The config/train families are merged
#: in below from the dataclasses themselves, so a new config field can
#: never ship unregistered.
_EXPLICIT: List[Knob] = [
    # -- topology / spawn ------------------------------------------------
    _K("DDL_TPU_MODE", "str", "thread",
       "Producer realisation: thread | process | multihost.",
       config_field="mode"),
    _K("DDL_TPU_N_PRODUCERS", "int", 2,
       "Producer workers per consumer instance.",
       config_field="n_producers"),
    _K("DDL_TPU_NSLOTS", "int", 2,
       "Ring slots (window buffers) per producer.",
       config_field="nslots"),
    _K("DDL_TPU_HOST_ID", "int", None,
       "Physical host id of this consumer (unset = auto-detect: SLURM "
       "node vars, then procs-per-host arithmetic).",
       export="cluster", config_field="host_id"),
    _K("DDL_TPU_N_HOSTS", "int", None,
       "Physical host count (unset = auto-detect).",
       export="cluster", config_field="n_hosts"),
    _K("DDL_TPU_PROCS_PER_HOST", "int", None,
       "Consumer processes per host for host-identity arithmetic "
       "(unset = SLURM_NTASKS_PER_NODE, then 1).",
       export="cluster", config_field="procs_per_host"),
    # -- transport rings -------------------------------------------------
    _K("DDL_TPU_FORCE_PY_RING", "bool", False,
       "Force the pure-Python ring even where the native shm ring "
       "builds (test/debug escape hatch)."),
    _K("DDL_TPU_UNSAFE_PY_RING", "bool", False,
       "Allow the Python ring cross-process without the native build "
       "(testing only; spin-waits instead of futex waits)."),
    _K("DDL_TPU_INPLACE", "bool", True,
       "Write-once producer fills straight into live ring slots "
       "(0 = staging copy per window)."),
    _K("DDL_TPU_INTEGRITY", "bool", True,
       "Checksummed window trailers + drain-time verification "
       "(0/off disables)."),
    _K("DDL_TPU_MAX_REPLAYS", "int", 2,
       "Replay attempts per quarantined corrupt window before "
       "IntegrityError escalation."),
    # -- staging / ingest ------------------------------------------------
    _K("DDL_TPU_STAGED", "bool", True,
       "Staged-ingest engine (0 = inline device_put per batch)."),
    _K("DDL_TPU_SHM_STAGING", "bool", True,
       "Alias staging straight from shm ring slots (PROCESS mode)."),
    _K("DDL_TPU_STAGING_POOL_CAP", "int", 8,
       "StagingPool buffer cap per geometry."),
    _K("DDL_TPU_STAGING_QUEUE", "int", 4,
       "TransferExecutor queue depth (in-flight staged transfers)."),
    _K("DDL_TPU_STAGING_RETRIES", "int", 2,
       "Staged-transfer retries before the inline fallback."),
    _K("DDL_TPU_DISTRIBUTE", "str", "auto",
       "Device distribution tier: ici | xla | auto (auto on accelerator "
       "meshes routes each window by its plan: the ICI ring where a "
       "byte must reach more than one chip, one direct sharded put for "
       "a pure split; xla on CPU)."),
    _K("DDL_TPU_ICI_INGEST", "bool", True,
       "auto-mode kill switch for the ICI fan-out tier (0 = xla)."),
    _K("DDL_TPU_FUSED", "bool", None,
       "Fused compute/ingest stream (unset = on where planned; 0 "
       "restores the synchronous step everywhere)."),
    # -- shard cache -----------------------------------------------------
    _K("DDL_TPU_CACHE", "bool", False,
       "Shard cache gate (docs/CACHING.md).",
       export="cache", config_field="cache"),
    _K("DDL_TPU_CACHE_RAM_MB", "int", 256,
       "RAM tier budget, MiB.", export="cache",
       config_field="cache_ram_mb"),
    _K("DDL_TPU_CACHE_SPILL_DIR", "str", None,
       "Disk spill directory (unset = RAM tier only).",
       export="cache", config_field="cache_spill_dir"),
    _K("DDL_TPU_CACHE_SPILL_MB", "int", 1024,
       "Disk spill budget, MiB.", export="cache",
       config_field="cache_spill_mb"),
    _K("DDL_TPU_CACHE_WARM", "bool", True,
       "Background warmer thread prefetching the shard schedule.",
       export="cache", config_field="cache_warm"),
    _K("DDL_TPU_CACHE_CODEC", "str", None,
       "Lossless codec for spilled cache entries (unset/none = raw "
       "bytes; zlib always available, zstd/lz4 gated on the host "
       "library).", export="cache", config_field="cache_codec"),
    _K("DDL_TPU_CACHE_RETRIES", "int", 3,
       "Backend fetch retries before IntegrityError."),
    _K("DDL_TPU_CACHE_BACKOFF_S", "float", 0.05,
       "Base backoff between backend fetch retries, seconds."),
    # -- wire format -----------------------------------------------------
    _K("DDL_TPU_WIRE_DTYPE", "str", None,
       "Wire transport override: raw = kill switch, bf16/int8 = force "
       "the lossy tier (unset = per-reader capability decides).  On "
       "the mesh it encodes ICI ring routes only: a window put "
       "directly to its chips arrives exact.",
       export="wire", config_field="wire_dtype"),
    _K("DDL_TPU_WIRE_CODEC", "str", None,
       "Lossless wire codec for the shuffle exchange + shard reads "
       "(none = explicit off; unset = no opinion).",
       export="wire", config_field="wire_codec"),
    # -- global shuffle --------------------------------------------------
    _K("DDL_TPU_DEVICE_SHUFFLE", "str", "auto",
       "Device-tier exchange gate: auto = engage when plannable (THREAD "
       "topology, raw wire, in-process fabric), 0/off/false = host "
       "exchange only.", export="shuffle", config_field="device_shuffle"),
    _K("DDL_TPU_SHUFFLE_IMPL", "str", "ring",
       "Device exchange implementation: ring = Pallas remote-DMA ring "
       "(double-buffered, slot-ridable), xla = jitted ppermute lanes.",
       export="shuffle", config_field="shuffle_impl"),
    # -- readers ---------------------------------------------------------
    _K("DDL_TPU_TFRECORD_CRC", "bool", True,
       "CRC32C verification of TFRecord length/payload frames."),
    # -- resilience ------------------------------------------------------
    _K("DDL_TPU_CKPT_ASYNC", "bool", True,
       "AsyncCheckpointer (D2H-only stall) vs synchronous writes."),
    _K("DDL_TPU_PREEMPT_NOTICE", "str", None,
       "Out-of-band preemption notice: set non-empty (optionally "
       "'<grace_s>') to trigger the graceful-drain ladder."),
    _K("DDL_TPU_PREEMPT_DEADLINE_S", "float", 30.0,
       "Default drain deadline after a preemption notice, seconds."),
    # -- control-plane survivability (cluster.supervision) ---------------
    _K("DDL_TPU_SUPERVISOR_LEASE_S", "float", 2.0,
       "Supervisor leadership lease budget, seconds: a standby "
       "promotes itself when the leader's lease goes unrenewed this "
       "long (ddl_tpu.cluster.supervision)."),
    _K("DDL_TPU_SUPERVISOR_STANDBYS", "int", 1,
       "Hot-standby supervisor count the HA tier provisions alongside "
       "the leader (ddl_tpu.cluster.supervision)."),
    _K("DDL_TPU_CTRL_RETRIES", "int", 5,
       "Acked control-envelope retry cap per send "
       "(ddl_tpu.transport.envelope); past it the send surfaces its "
       "last transport error."),
    _K("DDL_TPU_CTRL_BACKOFF_S", "float", 0.02,
       "Initial acked control-envelope retry backoff, seconds "
       "(doubles per retry; ddl_tpu.transport.envelope)."),
    _K("DDL_TPU_FABRIC_QUANTUM_BYTES", "int", 4194304,
       "DRR quantum of the fabric's resident fair-share scheduler, "
       "bytes of credit per job per replenish round "
       "(ddl_tpu.serve.fabric)."),
    _K("DDL_TPU_FABRIC_SNAPSHOT_EVERY", "int", 1,
       "Applied admission decisions between full scheduler snapshots "
       "in the supervisor journal (ddl_tpu.serve.fabric; 1 = every "
       "decision, the bit-exact failover default; 0 disables periodic "
       "snapshots)."),
    _K("DDL_TPU_FABRIC_ADMIT_TIMEOUT_S", "float", 30.0,
       "Default fabric admission deadline per window, seconds "
       "(ddl_tpu.serve.fabric.FabricJob.admit when the caller passes "
       "none)."),
    _K("DDL_TPU_FABRIC_DRAIN_SLO_S", "float", 2.0,
       "Preemption-drain SLO for fabric job revocation, seconds: how "
       "long revoke waits for in-flight granted windows to finish "
       "(ddl_tpu.serve.fabric)."),
    # -- self-tuning (ddl_tpu.tune) -------------------------------------
    _K("DDL_TPU_TUNE_DEADLINE_S", "float", 2.0,
       "Boot-time calibration budget, seconds (ddl_tpu.tune.Calibrator): "
       "probes not finished by then fall back to declared/default costs "
       "so calibration can never stall training start."),
    _K("DDL_TPU_TUNE_INTERVAL_S", "float", 1.0,
       "Steady-state KnobController poll cadence, seconds "
       "(ddl_tpu.tune.controller; the DDL018 deadline-loop period)."),
    _K("DDL_TPU_TUNE_SUSTAIN_S", "float", 2.0,
       "How long a tuning signal must stay beyond its band before the "
       "KnobController acts (hysteresis; the Autoscaler precedent)."),
    _K("DDL_TPU_TUNE_COOLDOWN_S", "float", 5.0,
       "Minimum spacing between KnobController knob changes, seconds "
       "(also the post-change observation window the never-worse guard "
       "judges before a revert)."),
    _K("DDL_TPU_TUNE_REVERT_TOL", "float", 0.05,
       "Never-worse guard tolerance: a knob change whose post-change "
       "window throughput drops more than this fraction below the "
       "pre-change window is reverted (ddl_tpu.tune.controller)."),
    _K("DDL_TPU_TUNE_PARITY_HEADROOM", "float", 0.5,
       "Lossy-wire safety margin: when max_rel_drift exceeds this "
       "fraction of the loss_parity tolerance, the controller flips "
       "the exchange wire back to raw (ddl_tpu.tune.controller)."),
    # -- chaos / observability ------------------------------------------
    _K("DDL_TPU_FAULT_PLAN", "str", None,
       "JSON-encoded FaultPlan armed at import (the spawn-boundary "
       "chaos carrier; ddl_tpu.faults)."),
    _K("DDL_TPU_TRACE", "int", None,
       "Span tracing armed at import with this event capacity "
       "(unset = tracing disarmed; ddl_tpu.obs.spans)."),
    _K("DDL_TPU_FLIGHT", "int", None,
       "Flight recorder armed at import with this ring capacity "
       "(unset = disarmed; ddl_tpu.obs.recorder)."),
    _K("DDL_TPU_FLIGHT_DIR", "str", None,
       "Flight-record dump directory (default /tmp/ddl_tpu_flight)."),
    _K("DDL_TPU_OBS_SHIP_EVERY", "int", 32,
       "Windows between periodic worker ObsReports (0 = disabled)."),
    # -- harness knob (read by tests/conftest.py, documented here) ------
    _K("DDL_TPU_ONCHIP", "bool", False,
       "Enable @onchip tests (needs a real TPU).",
       external=True),
]

#: One-line docs for config-family knobs that have no explicit entry
#: above (LoaderConfig fields are the source of the name + default).
_CONFIG_FIELD_DOCS: Dict[str, str] = {
    "batch_size": "Samples per batch served to the consumer.",
    "n_epochs": "Epochs before the loader signals exhaustion.",
    "global_shuffle_fraction_exchange":
        "Fraction of each window exchanged in the global shuffle.",
    "exchange_method": "Global-shuffle exchange algorithm.",
    "shuffle_seed": "Seed for the deterministic shuffle schedule.",
    "output": "Consumer output container: jax | numpy | torch.",
    "window_stream": "Zero-copy window streaming (Trainer.fit).",
    "ring_timeout_s": "Ring wait timeout before StallTimeoutError.",
    "stall_budget_s": "Watchdog stall budget per producer.",
    "checkpoint_dir": "Loader checkpoint directory (unset = off).",
    "checkpoint_every_epochs": "Checkpoint cadence (0 = disabled).",
    "prefetch_depth":
        "Device transfers kept in flight by prefetch() (tunable).",
}

_TRAIN_FIELD_DOCS: Dict[str, str] = {
    "remat":
        "Rematerialisation policy: none/full/selective/dots (selective "
        "keeps the attention output and the flash kernels' logsumexp: "
        "the backward re-runs no attention kernel; full and dots do).",
    "schedule": "Pipeline schedule: gpipe | 1f1b.",
    "pp_chunks": "Stage chunks per device for 1f1b (0 = default).",
    "n_microbatches": "Microbatches per pipeline step.",
    "accum_steps": "Gradient-accumulation microbatches per update.",
    "optimizer_sharding": "Optimizer state sharding: none | zero1.",
    "grad_comm": "Gradient comm wire format: fp32 | int8.",
    "grad_comm_block": "int8 block size (0 = collectives default).",
    "stochastic_rounding": "Stochastic rounding on the int8 wire.",
}


def _annot_type(annot: Any) -> str:
    s = str(annot)
    if "bool" in s:
        return "bool"
    if "int" in s:
        return "int"
    if "float" in s:
        return "float"
    return "str"


def _build_registry() -> Dict[str, Knob]:
    reg: Dict[str, Knob] = {}
    for k in _EXPLICIT:
        if k.name in reg:
            raise ValueError(f"duplicate knob {k.name}")
        reg[k.name] = k
    # The DDL_TPU_<FIELD> / DDL_TPU_TRAIN_<FIELD> families, derived from
    # the dataclasses so a new config field auto-registers.
    for cls, docs, field_attr in (
        (LoaderConfig, _CONFIG_FIELD_DOCS, "config_field"),
        (TrainConfig, _TRAIN_FIELD_DOCS, "train_field"),
    ):
        for f in dataclasses.fields(cls):
            if f.name.startswith("_"):
                continue
            name = cls._ENV_PREFIX + f.name.upper()
            if name in reg:
                # Explicit entry covers it; validate() asserts the
                # annotations/defaults agree.
                continue
            reg[name] = Knob(
                name=name,
                type=_annot_type(f.type),
                default=f.default,
                doc=docs.get(
                    f.name, f"{cls.__name__}.{f.name} (see config.py)."
                ),
                external=True,  # read via the computed-prefix layering
                **{field_attr: f.name},
            )
    return reg


REGISTRY: Dict[str, Knob] = _build_registry()


def require(name: str) -> Knob:
    """The registry entry for ``name``, or :class:`UnknownKnobError`."""
    try:
        return REGISTRY[name]
    except KeyError:
        raise UnknownKnobError(
            f"unregistered env knob {name!r}: declare it in "
            "ddl_tpu/envspec.py (tools/ddl_verify VP003)"
        ) from None


def raw(name: str) -> Optional[str]:
    """The raw environment string for a REGISTERED knob (None = unset).

    The accessor for sentinel-typed knobs whose call sites keep their
    own tri-state logic; everything else uses :func:`get`/:func:`flag`.
    """
    require(name)
    return os.environ.get(name)


def get(name: str, override: Any = None) -> Any:
    """Typed read: explicit ``override`` wins, then the environment,
    then the registered default.  Empty-string values fall back to the
    default for non-str knobs (an exported-then-cleared mirror must not
    crash a worker on ``int("")``)."""
    knob = require(name)
    if override is not None:
        return override
    val = os.environ.get(name)
    if knob.type == "bool":
        if val is None or val == "":
            return bool(knob.default)
        return val.lower() not in FALSY
    if val is None or val == "":
        return knob.default
    if knob.type == "int":
        return int(val)
    if knob.type == "float":
        return float(val)
    return val


def flag(name: str, override: Optional[bool] = None) -> bool:
    """Boolean read (the historical ``utils.env_flag`` semantics:
    truthy unless ``0``/``off``/``false``, case-insensitive)."""
    val = get(name, override)
    return bool(val)


def export_group(group: str) -> List[Knob]:
    """Registered knobs a ``_export_<group>_knobs`` mirror must cover."""
    return [k for k in REGISTRY.values() if k.export == group]


def validate() -> None:
    """Cross-check explicit entries against the config dataclasses.

    Raises on drift: an explicit knob naming a ``config_field`` /
    ``train_field`` that does not exist, or whose registered default
    disagrees with the dataclass default.  Called from the tier-1
    reflection test, not at import (a broken registry must fail the
    gate loudly, not break production imports).
    """
    for cls, attr in ((LoaderConfig, "config_field"),
                      (TrainConfig, "train_field")):
        by_name = {f.name: f for f in dataclasses.fields(cls)}
        for knob in REGISTRY.values():
            fname = getattr(knob, attr)
            if fname is None:
                continue
            if fname not in by_name:
                raise AssertionError(
                    f"{knob.name} names unknown {cls.__name__} field "
                    f"{fname!r}"
                )
            f = by_name[fname]
            expect = cls._ENV_PREFIX + fname.upper()
            if knob.name != expect:
                raise AssertionError(
                    f"{knob.name} mirrors {cls.__name__}.{fname} but the "
                    f"layered loader reads {expect}"
                )
            if knob.default is not None and knob.default != f.default:
                # Sentinel knobs (default None) intentionally differ
                # from config sentinels (-1/0/""): skip those.
                if not (f.default in (-1, 0, "", None) and
                        knob.default is None):
                    raise AssertionError(
                        f"{knob.name} default {knob.default!r} != "
                        f"{cls.__name__}.{fname} default {f.default!r}"
                    )


def render_table() -> str:
    """The ``docs/CONFIG.md`` knob table, generated from the registry."""
    lines = [
        "# Environment knobs",
        "",
        "Generated from `ddl_tpu/envspec.py` "
        "(`python -m ddl_tpu.envspec > docs/CONFIG.md`); "
        "`tests/test_verify.py` asserts this file matches the registry, "
        "and `tools/ddl_verify` VP003 asserts every env read resolves "
        "through it.  Precedence everywhere: explicit config/kwargs win "
        "over the environment, which wins over the registered default.",
        "",
        "| Knob | Type | Default | Export mirror | Description |",
        "|---|---|---|---|---|",
    ]
    for name in sorted(REGISTRY):
        k = REGISTRY[name]
        default = "*(unset)*" if k.default is None else repr(k.default)
        export = f"`_export_{k.export}_knobs`" if k.export else ""
        doc = k.doc.replace("|", "\\|")  # literal pipes break the table
        lines.append(
            f"| `{k.name}` | {k.type} | {default} | {export} | {doc} |"
        )
    lines.append("")
    lines.append(
        f"{len(REGISTRY)} registered knobs "
        "(config-derived families included)."
    )
    lines.append("")
    return "\n".join(lines)


if __name__ == "__main__":  # pragma: no cover - doc generator
    print(render_table(), end="")
