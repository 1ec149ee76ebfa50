"""model + kernels: share of the traced window the chips spent in the
fixed-decay recurrence as the program executes it - own time of the step
program's ops under ``ddl.lightning_scan`` (the kernels' padding, the
output's reshape, whatever XLA puts around them) AND of the
``ddl_lightning_*`` kernel families wherever they stand
(``ddl_tpu/ops/lightning_attention.py``: a chunk from q, k, v to o with the
state in VMEM, forward and reverse).  Mean over the chips, whole sums off
the trace's own table.  ``benchmarks/lib/scopes.py`` knows neither the
scope nor the families (it reports them as ``other``), so the selection is
made here.  ``None`` without a trace, and on a program without either."""

from benchmarks.lib import scopes

LIGHTNING_FAMILIES = ("ddl_lightning_",)
SCAN_SCOPE = "ddl.lightning_scan"


def is_lightning_kernel(family: str) -> bool:
    return family.startswith(LIGHTNING_FAMILIES)


def recurrence_seconds(m: dict):
    """Own seconds of the recurrence AS EXECUTED, mean over the chips.
    ``None`` where the trace has no table or nothing of either."""
    table = scopes.table_of_run(m)
    if table is None:
        return None
    secs = table.seconds(
        lambda scope, frame, which, family:
        scope == SCAN_SCOPE or is_lightning_kernel(family)
    )
    return secs or None


def read(m: dict):
    secs = recurrence_seconds(m)
    return 100.0 * secs / m["trace"]["window_s"] if secs else None
