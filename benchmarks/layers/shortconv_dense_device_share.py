"""model + kernels: share of the traced window the chips spent in the conv
layers' mixers OUTSIDE the gated short convolution - own time of the step
program's ops under ``ddl.shortconv_proj`` (the operator norm and ``W_in``,
2048 -> 6144) and ``ddl.shortconv_out`` (``W_out`` and the residual add):
two large matmuls and what stands around them.  The convolution itself has
its own (``shortconv_device_share``).  Mean over the chips.
``benchmarks/lib/scopes.py`` reports these scopes as ``other``, so the
selection is made here.  ``None`` without a trace, and on a program without
the scopes (the parent)."""

from benchmarks.lib import scopes

DENSE_SCOPES = ("ddl.shortconv_proj", "ddl.shortconv_out")


def read(m: dict):
    table = scopes.table_of_run(m)
    if table is None:
        return None
    secs = table.seconds(
        lambda scope, frame, which, family:
        scope in DENSE_SCOPES and not scopes.is_kernel(family)
    )
    return 100.0 * secs / table.window_s if secs else None
