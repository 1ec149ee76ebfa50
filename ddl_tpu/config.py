"""Run configuration: one typed dataclass, JSON- and env-overridable.

The reference had no config system — every knob was a constructor argument
plus SLURM env sniffing, and its harness rolled six ad-hoc nested
dataclasses (reference ``tests/run_ddl.py:243-298``, SURVEY §5.6).  This is
the librarified version: defaults → JSON file → ``DDL_TPU_*`` env vars →
explicit kwargs, later layers winning.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Any, Optional

from ddl_tpu.types import RunMode


@dataclasses.dataclass
class LoaderConfig:
    """Everything the pipeline needs, in one place."""

    # topology
    mode: str = RunMode.THREAD.value
    n_producers: int = 2
    nslots: int = 2
    # host identity (ddl_tpu.cluster): with several consumer processes
    # per physical host, jax.process_index() over-counts hosts — the
    # membership view and placement engine need REAL host boundaries.
    # -1/0 = auto-detect (DDL_TPU_HOST_ID/N_HOSTS env, then SLURM node
    # vars, then procs_per_host arithmetic over the process grid —
    # ddl_tpu.env.detect_host_identity).
    host_id: int = -1
    n_hosts: int = 0
    procs_per_host: int = 0  # 0 = auto (SLURM_NTASKS_PER_NODE or 1)
    # batch geometry
    batch_size: int = 32
    n_epochs: int = 1
    # global shuffle
    global_shuffle_fraction_exchange: float = 0.0
    exchange_method: str = "sendrecv_replace"
    shuffle_seed: int = 0
    # consumer output ("jax" — TPU-native default; the bare
    # DistributedDataLoader keeps the reference's torch-first default)
    output: str = "jax"
    # zero-copy window streaming (Trainer.fit window_stream; jax output)
    window_stream: bool = False
    # failure detection
    ring_timeout_s: float = 300.0
    stall_budget_s: float = 120.0
    # checkpointing
    checkpoint_dir: Optional[str] = None
    checkpoint_every_epochs: int = 0  # 0 = disabled
    # shard cache (ddl_tpu.cache; docs/CACHING.md).  Mirrors the
    # DDL_TPU_CACHE* env knobs — distributed_dataloader exports these
    # fields back into the environment so PROCESS-mode producer workers
    # build the same store.
    cache: bool = False
    cache_ram_mb: int = 256
    cache_spill_dir: Optional[str] = None
    cache_spill_mb: int = 1024
    cache_warm: bool = True
    # Lossless codec for spilled cache entries ("" = raw bytes; "zlib"
    # always available, "zstd"/"lz4" gated on the host library).  Was
    # env-only (DDL_TPU_CACHE_CODEC) with no config mirror — the stale
    # spawn-boundary drift ddl-verify VP003 now machine-checks.
    cache_codec: str = ""
    # Wire format (ddl_tpu.wire).
    # ``wire_dtype``: "" = no opinion (the per-reader capability
    # decides), "raw" = kill switch, "bf16"/"int8" = force the lossy
    # tier (A/B runs; licensed by the loss-parity gate).  ``wire_codec``:
    # "" / "none" = off, else a lossless codec name ("zlib" always;
    # "zstd"/"lz4" where the host has the library) for the shuffle
    # exchange wire and compressed shard/cache reads.  Mirrored into
    # DDL_TPU_WIRE_DTYPE / DDL_TPU_WIRE_CODEC ahead of producer spawn
    # (ddl_tpu.env._export_wire_knobs).
    wire_dtype: str = ""
    wire_codec: str = ""
    # Device-tier global shuffle (ddl_tpu.ops.device_shuffle).
    # ``device_shuffle``: "auto" = engage the device exchange when
    # plannable (THREAD topology, raw wire, in-process fabric),
    # "0"/"off"/"false" = host exchange only.  ``shuffle_impl``:
    # "ring" = Pallas remote-DMA ring (double-buffered, rides a landing
    # slot), "xla" = jitted ppermute lanes.  Mirrored into
    # DDL_TPU_DEVICE_SHUFFLE / DDL_TPU_SHUFFLE_IMPL ahead of producer
    # spawn (ddl_tpu.env._export_shuffle_knobs).
    device_shuffle: str = "auto"
    shuffle_impl: str = "ring"
    # Device transfers kept in flight by DistributedDataLoader.prefetch
    # (ddl_tpu.ingest.PrefetchIterator).  A first-class config field —
    # not a call-site literal — so the boot-time Calibrator and the
    # steady-state KnobController (ddl_tpu.tune) have a seam to retune
    # it through, with DDL_TPU_PREFETCH_DEPTH as the env mirror.
    prefetch_depth: int = 2

    _ENV_PREFIX = "DDL_TPU_"

    @classmethod
    def load(cls, path: Optional[str] = None, **overrides: Any) -> "LoaderConfig":
        """defaults → JSON file → env (`DDL_TPU_<FIELD>`) → kwargs."""
        return _load_layered(cls, path, overrides)

    def save(self, path: str) -> None:
        _save_json(self, path)

    def run_mode(self) -> RunMode:
        return RunMode(self.mode)


@dataclasses.dataclass
class TrainConfig:
    """Training hot-path knobs — the consumer-compute half of a run
    (the model/trainer twin of :class:`LoaderConfig`), env-overridable
    as ``DDL_TPU_TRAIN_<FIELD>``.

    ``remat`` names the rematerialisation policy
    (:mod:`ddl_tpu.models.remat`: none/full/selective/dots) and is
    applied to a model config with :meth:`model_config`; ``schedule`` /
    ``pp_chunks`` select the pipeline schedule
    (:func:`ddl_tpu.parallel.pipeline_apply`) and feed the models'
    ``*_pp`` entry points via :meth:`pipeline_kwargs`; ``accum_steps``
    flows into the :class:`~ddl_tpu.trainer.Trainer` constructor; the
    distributed-optimizer knobs (``optimizer_sharding`` / ``grad_comm``
    / ``grad_comm_block`` / ``stochastic_rounding``) flow into the step
    factories via :meth:`optimizer_kwargs`
    (``DDL_TPU_TRAIN_OPTIMIZER_SHARDING=zero1`` etc. from the env).
    """

    #: Remat policy for the backward pass (``ddl_tpu.models.remat``).
    remat: str = "none"
    #: Pipeline schedule: "gpipe" or "1f1b" (interleaved stage chunks).
    schedule: str = "gpipe"
    #: Stage chunks per device for 1f1b (0 = the schedule's default, 2).
    pp_chunks: int = 0
    #: Microbatches per pipeline step (1 = no microbatching).
    n_microbatches: int = 1
    #: Gradient-accumulation microbatches per optimizer update.
    accum_steps: int = 1
    #: Distributed optimizer (``ddl_tpu.parallel.optimizer``): "none"
    #: replicates the optimizer state across dp; "zero1" shards state +
    #: weight update over the dp axis (ZeRO-1 — bit-exact at fp32,
    #: ~dp× less optimizer HBM per replica).
    optimizer_sharding: str = "none"
    #: Gradient/update communication wire format: "fp32" (exact) or
    #: "int8" (blockwise-scaled EQuARX format, licensed by the
    #: loss-curve-parity gate — ``parallel.optimizer.loss_parity``).
    grad_comm: str = "fp32"
    #: int8 block size (values per fp32 scale); 0 = the collectives
    #: default (``parallel.collectives.QUANT_BLOCK``).
    grad_comm_block: int = 0
    #: Stochastic rounding on the int8 wire format (unbiased in
    #: expectation; deterministic given the step's gradient values).
    stochastic_rounding: bool = False

    _ENV_PREFIX = "DDL_TPU_TRAIN_"

    @classmethod
    def load(cls, path: Optional[str] = None, **overrides: Any) -> "TrainConfig":
        """defaults → JSON file → env (`DDL_TPU_TRAIN_<FIELD>`) → kwargs."""
        cfg = _load_layered(cls, path, overrides)
        from ddl_tpu.models import remat as _remat

        _remat.resolve(cfg.remat)  # fail on junk at load time
        if cfg.schedule not in ("gpipe", "1f1b"):
            raise ValueError(f"unknown schedule {cfg.schedule!r}")
        if cfg.optimizer_sharding not in ("none", "zero1"):
            raise ValueError(
                f"unknown optimizer_sharding {cfg.optimizer_sharding!r} "
                "(valid: none, zero1)"
            )
        if cfg.grad_comm not in ("fp32", "int8"):
            raise ValueError(
                f"unknown grad_comm {cfg.grad_comm!r} (valid: fp32, int8)"
            )
        return cfg

    def save(self, path: str) -> None:
        _save_json(self, path)

    def model_config(self, model_cfg: Any) -> Any:
        """The model config with this TrainConfig's remat policy applied
        (works on any of the frozen model config dataclasses)."""
        return dataclasses.replace(model_cfg, remat=self.remat)

    def pipeline_kwargs(self) -> dict:
        """kwargs for the models' ``*_pp`` losses / ``pipeline_apply``."""
        return {
            "schedule": self.schedule,
            "n_chunks": self.pp_chunks or None,
        }

    def optimizer_kwargs(self) -> dict:
        """kwargs for the step factories
        (:func:`ddl_tpu.parallel.train.make_train_step` /
        :func:`~ddl_tpu.parallel.train.make_multistep`): the
        distributed-optimizer knobs, shaped for ``**`` splatting — the
        single hand-off point, so the Trainer and the bench cannot
        plumb a different subset."""
        return {
            "optimizer_sharding": self.optimizer_sharding,
            "grad_comm": self.grad_comm,
            "grad_comm_block": self.grad_comm_block,
            "stochastic_rounding": self.stochastic_rounding,
        }


def _load_layered(cls: Any, path: Optional[str], overrides: dict) -> Any:
    """THE layered-config loader both config classes share: defaults →
    JSON file → env (``<cls._ENV_PREFIX><FIELD>``) → kwargs, later
    layers winning, unknown JSON keys rejected.  One implementation so
    the layering/coercion semantics cannot drift between
    :class:`LoaderConfig` and :class:`TrainConfig`."""
    values: dict = {}
    if path:
        with open(path) as f:
            loaded = json.load(f)
        unknown = set(loaded) - {f.name for f in dataclasses.fields(cls)}
        if unknown:
            raise ValueError(
                f"unknown config keys in {path}: {sorted(unknown)}"
            )
        values.update(loaded)
    # Lazy: envspec imports this module to derive the knob families.
    from ddl_tpu import envspec

    for field in dataclasses.fields(cls):
        if field.name.startswith("_"):
            continue
        # envspec.raw fails loudly on an unregistered name, so a new
        # config field cannot silently bypass the knob registry (the
        # families auto-register from dataclasses.fields).
        env = envspec.raw(cls._ENV_PREFIX + field.name.upper())
        if env is not None:
            values[field.name] = _coerce(env, field.type)
    values.update(overrides)
    return cls(**values)


def _save_json(cfg: Any, path: str) -> None:
    with open(path, "w") as f:
        json.dump(dataclasses.asdict(cfg), f, indent=2)


def _coerce(raw: str, annot: Any) -> Any:
    annot = str(annot)
    if "int" in annot:
        return int(raw)
    if "float" in annot:
        return float(raw)
    if "bool" in annot:
        return raw.lower() in ("1", "true", "yes")
    return raw
