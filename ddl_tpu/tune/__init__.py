"""Self-driving data plane: boot-time calibration + closed-loop tuning.

The loader exposes a dozen performance-critical knobs (wire_dtype and
codec, prefetch depth, staging queue/pool, ici-vs-xla distribution,
placement, autoscaler setpoints) and the PR-15 tracing layer built the
histograms and per-stage spans to judge them — but until this package a
human set every one, and a mis-set knob on an unfamiliar geometry
silently cost the throughput the stack was built to win (ROADMAP item
4).  Cost-model-driven reconfiguration is the established shape here:
arXiv:2105.14088 picks placement from measured link costs and
arXiv:2112.01075 prices redistribution legs before choosing them — this
package does the same for the ingest plane's own knobs, automatically:

- :class:`~ddl_tpu.tune.calibrate.Calibrator` — boot-time: runs the
  wire break-even table against *measured* link speeds (the
  pluggable ``probe_link_costs``) plus a distribution microbenchmark,
  and emits a :class:`~ddl_tpu.tune.calibrate.TunedConfig` overlay onto
  ``LoaderConfig``/envspec.  Every decision carries ``cost_source``
  provenance (measured / declared / default — the placement engine's
  pattern) and the whole pass runs under a deadline budget
  (``DDL_TPU_TUNE_DEADLINE_S``) so calibration can never stall
  training start.
- :class:`~ddl_tpu.tune.controller.KnobController` — steady-state: a
  DDL018-compliant deadline loop watching ``window_latency_p99``, the
  windowed stall fraction, and ``stage_breakdown``, retuning prefetch
  depth and staging capacity under hysteresis (the Autoscaler
  precedent), re-running ``plan_placement`` on measured-cost drift,
  and flipping lossy wire off when ``loss_parity`` headroom shrinks.
  Every decision is flight-recorded (knob, old→new, triggering signal
  values) and guarded never-worse: a knob whose post-change window
  regresses is reverted.

Audit trail: ``tune.decisions`` / ``tune.reverts`` /
``tune.cost_source.*`` counters surface in ``north_star_report`` as
``tune_decisions`` / ``tune_reverts`` / ``tune_cost_source``, and each
decision lands in the flight-recorder ring (docs/TUNING.md walks a
post-mortem).  ``tests/test_tune.py`` holds the decisions, the revert
rule and byte identity of a tuned stream; what tuning is worth is not
measured on the chip (no benchmark cell runs it).
"""

from ddl_tpu.tune.calibrate import (  # noqa: F401
    COST_DECLARED,
    COST_DEFAULT,
    COST_MEASURED,
    Calibrator,
    Decision,
    TunedConfig,
)
from ddl_tpu.tune.controller import (  # noqa: F401
    ControllerPolicy,
    KnobController,
)
from ddl_tpu.tune.knobs import (  # noqa: F401
    TunableKnob,
    env_knob,
    prefetch_knob,
    staging_pool_knob,
    staging_queue_knob,
    wire_dtype_knob,
)

__all__ = [
    "COST_DECLARED",
    "COST_DEFAULT",
    "COST_MEASURED",
    "Calibrator",
    "ControllerPolicy",
    "Decision",
    "KnobController",
    "TunableKnob",
    "TunedConfig",
    "env_knob",
    "prefetch_knob",
    "staging_pool_knob",
    "staging_queue_knob",
    "wire_dtype_knob",
]
