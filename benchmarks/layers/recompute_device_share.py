"""model + kernels: share of the traced window the chips spent running
forward ops a second time for the backward pass — own time of the step
program's ops whose path holds ``rematted_computation``, under any scope
and kernels included (what the remat policy did not save), mean over the
chips.  A cut across the other shares, not a part of their sum.  ``None``
as ``attn_dense_device_share`` has it."""

from benchmarks.lib import scopes


def read(m: dict):
    return scopes.share(m, lambda table: table.recompute_s())
