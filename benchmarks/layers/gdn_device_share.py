"""model + kernels: share of the traced window the chips spent in the
gated-delta-rule kernels (op families ``ddl_gdn_*``: the chain over chunk
states, forward and reverse, ``ddl_tpu/ops/gated_delta.py``): own time,
mean over the chips, whole sums off the trace's own table - no top-ten
cut.  ``benchmarks/lib/scopes.py`` does not know these families and counts
them with the scope they stand under (``other``), so the selection by
family is made here.  ``None`` without a trace, and on a program without
such kernels.

The kernels are a PART of the recurrence as the program executes it: what
XLA runs around them stands under ``ddl.gdn_scan``
(``gdn_scan_device_share``), and the two together are what
``gdn_roofline_share`` divides the recurrence's floor by
(:func:`recurrence_seconds`)."""

from benchmarks.lib import scopes

GDN_FAMILIES = ("ddl_gdn_",)
#: The scope the whole of the recurrence stands under in the model
#: (``ddl_tpu/ops/naming.py``): the chunks' parallel part, which XLA runs,
#: and the kernels.
SCAN_SCOPE = "ddl.gdn_scan"


def is_gdn_kernel(family: str) -> bool:
    return family.startswith(GDN_FAMILIES)


def recurrence_seconds(m: dict):
    """Own seconds of the recurrence AS EXECUTED, mean over the chips: every
    op under ``ddl.gdn_scan`` and the ``ddl_gdn_*`` families wherever they
    stand - whatever share of the work a kernel or XLA holds.  ``None``
    where the trace has no table or nothing of either."""
    table = scopes.table_of_run(m)
    if table is None:
        return None
    secs = table.seconds(
        lambda scope, frame, which, family:
        scope == SCAN_SCOPE or is_gdn_kernel(family)
    )
    return secs or None


def read(m: dict):
    table = scopes.table_of_run(m)
    if table is None:
        return None
    secs = table.seconds(lambda scope, frame, which, family: is_gdn_kernel(family))
    return 100.0 * secs / table.window_s if secs else None
