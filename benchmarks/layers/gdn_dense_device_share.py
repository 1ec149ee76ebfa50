"""model + kernels: share of the traced window the chips spent in the
linear-attention blocks OUTSIDE the recurrence - own time of the step
program's ops under ``ddl.gdn_proj`` (the six projections),
``ddl.gdn_conv`` (convolutions, SiLU, L2 norms, gates) and ``ddl.gdn_out``
(gated norm, ``Wo``, residual): large matmuls and what stands between
them.  The recurrence itself has its own two (``gdn_scan_device_share``:
what XLA runs of it; ``gdn_device_share``: the kernels).  Mean over the
chips.  ``benchmarks/lib/scopes.py`` reports these scopes as ``other``
(they are in none of its groups), so the selection is made here.  ``None``
without a trace, and on a program without the scopes."""

from benchmarks.layers.gdn_device_share import is_gdn_kernel
from benchmarks.lib import scopes

DENSE_SCOPES = ("ddl.gdn_proj", "ddl.gdn_conv", "ddl.gdn_out")


def read(m: dict):
    table = scopes.table_of_run(m)
    if table is None:
        return None
    secs = table.seconds(
        lambda scope, frame, which, family:
        scope in DENSE_SCOPES and not is_gdn_kernel(family)
    )
    return 100.0 * secs / table.window_s if secs else None
