"""model + kernels: share of the traced window the chips spent in the gated
short convolution itself - own time of the step program's ops under
``ddl.shortconv`` (``models/lfm2_moe.py:gated_short_conv``: both gates and
the taps, every pass: forward, the forward computed again under the remat
policy, backward) plus the ``ddl_shortconv_*`` kernel families wherever
they stand (none yet: XLA's fusions run it).  Mean over the chips, whole
sums off the trace's own table - no top-ten cut.
``benchmarks/lib/scopes.py`` reports the scope as ``other`` (it is in none
of its groups), so the selection is made here.  ``None`` without a trace,
and on a program without the scope or the kernels (the parent)."""

from benchmarks.lib import scopes

CONV_SCOPE = "ddl.shortconv"
CONV_FAMILIES = ("ddl_shortconv_",)


def shortconv_seconds(m: dict):
    """Own seconds of the gated short convolution AS EXECUTED, mean over the
    chips; ``None`` where the trace has no table or nothing of it."""
    table = scopes.table_of_run(m)
    if table is None:
        return None
    secs = table.seconds(
        lambda scope, frame, which, family:
        scope == CONV_SCOPE or family.startswith(CONV_FAMILIES)
    )
    return secs or None


def read(m: dict):
    secs = shortconv_seconds(m)
    return None if secs is None else 100.0 * secs / m["trace"]["window_s"]
