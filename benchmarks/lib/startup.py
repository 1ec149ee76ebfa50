"""The program's start-up record, for the readers of set-up's per-layer
metrics (``benchmarks/layers/setup_*.py``, ``fit_start_s``, ``fit_stop_s``).

The runner hands a reader its ``measured`` dict alone; the record is the
process's own (``ddl_tpu.profiling.startup_record()``), kept since
``bring_up()``.  What "set-up" covers is the program's definition
(``StartupRecord.summary``): everything stamped up to the LAST fit's
``first_dispatch_done``, on the host's ``time.monotonic()``."""

from __future__ import annotations

from typing import Optional


def summary() -> Optional[dict]:
    """The record's summary; ``None`` on a program that keeps none."""
    from ddl_tpu import profiling

    record = getattr(profiling, "startup_record", None)
    return None if record is None else record().summary()


def phase(name: str) -> Optional[float]:
    """Seconds of one phase of set-up (0.0: it took no time)."""
    found = summary()
    return None if found is None else float(found["seconds"][name])
