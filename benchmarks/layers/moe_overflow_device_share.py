"""model + kernels: share of the traced window the chips spent in a routed
layer's full-width fallback - own time of the step program's ops whose
innermost ``ddl.`` scope is ``ddl.moe_overflow``
(``ddl_tpu/models/moe.py:_held_rows``: a share of a wider router runs its row
passes over a static bound of held rows, and over all N x k rows in the
layers and passes where the router sent it more than the bound).  XLA runs
only the taken branch of a ``conditional``, so the trace says which ran: 0.0
means every routed layer of the traced window took the bounded pass.  Mean
over the chips.  ``benchmarks/lib/scopes.py`` has the scope in no group and
reports it as ``other``, so the selection is made here.

What it costs the neighbour: while the fallback runs, its seconds are NOT in
``moe_dispatch_device_share``, which sums the four older ``ddl.moe*`` scopes
by the innermost frame - a window in overflow reads a low dispatch share
that is none.  Read the two together.  The fallback's grouped matmuls keep
no path at all (``gmm_device_share`` counts them as it does the others).

``None`` without a trace, and on a program whose scope table has no
``ddl.moe_overflow`` (a parent of PR 40: there is no fallback to time)."""

from benchmarks.lib import scopes

OVERFLOW_SCOPE = "ddl.moe_overflow"


def read(m: dict):
    if OVERFLOW_SCOPE not in scopes._program_scopes():
        return None
    return scopes.share(m, lambda table: table.seconds(
        lambda scope, frame, which, family:
        scope == OVERFLOW_SCOPE and not scopes.is_kernel(family)
    ))
