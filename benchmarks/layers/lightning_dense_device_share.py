"""model + kernels: share of the traced window the chips spent in the
lightning-attention blocks OUTSIDE the recurrence - own time of the step
program's ops under ``ddl.lightning_proj`` (the input norm, the q, k, v
projections, per-head QK-norm, RoPE) and ``ddl.lightning_out`` (the gate's
projection, the gated output norm, ``Wo``, the residual): large matmuls and
what stands between them.  The recurrence itself is
``lightning_device_share``.  Mean over the chips.
``benchmarks/lib/scopes.py`` reports these scopes as ``other``, so the
selection is made here.  ``None`` without a trace, and on a program without
the scopes."""

from benchmarks.layers.lightning_device_share import is_lightning_kernel
from benchmarks.lib import scopes

DENSE_SCOPES = ("ddl.lightning_proj", "ddl.lightning_out")


def read(m: dict):
    table = scopes.table_of_run(m)
    if table is None:
        return None
    secs = table.seconds(
        lambda scope, frame, which, family:
        scope in DENSE_SCOPES and not is_lightning_kernel(family)
    )
    return 100.0 * secs / table.window_s if secs else None
