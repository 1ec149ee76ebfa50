"""Idle time of the idlest chip by the host stage it fell under.

The reduction's ``idle_gaps`` gives, for the chip that idled most, the
seconds of idle gaps under each host span (the innermost one covering
half a gap; the program's ``ddl.*`` stages are named in
``ddl_tpu/profiling.py:STAGES``), the ten largest.  A reader here sums
the names it is for and divides by the traced window: ``None`` without a
trace, 0.0 with a trace and no gap under its names — as on a program
that does not emit them yet.

By construction the data-plane, trainer and unattributed shares, the
benchmark's own ``bench.window_hook`` and the launch gaps add up to
``device_idle_share``, as far as the top-ten cut lets through.
"""

from typing import Optional

#: The data plane's stages: everything under ``next(stream)`` and on the
#: staging executor's threads.
DATA_PLANE = (
    "ddl.window_wait", "ddl.window_acquire", "ddl.transfer_wait",
    "ddl.release_wait", "ddl.staging_", "ddl.ingest_put", "ddl.ici_fanout",
)
RING = ("ddl.window_acquire",)
TRANSFER = (
    "ddl.transfer_wait", "ddl.release_wait", "ddl.staging_copy",
    "ddl.staging_transfer", "ddl.ingest_put_window",
)
FANOUT = ("ddl.ici_fanout",)
#: The train loop's own stages (``bench.window_hook`` is the benchmark's).
TRAINER = ("ddl.step_dispatch", "ddl.loss_readback")
UNATTRIBUTED = ("unattributed",)


def gap_share(m: dict, prefixes) -> Optional[float]:
    """Percent of the traced window idle under spans whose names start
    with one of ``prefixes``."""
    trace = m.get("trace")
    if not trace:
        return None
    secs = sum(s for name, s in trace["idle_gaps"] if name.startswith(prefixes))
    return 100.0 * secs / trace["window_s"]
