"""model + kernels: share of the traced window the chips spent in the
optimizer — own time of the step program's ops under ``ddl.optimizer``
(adamw's moments and the parameter update), mean over the chips.
``None`` as ``attn_dense_device_share`` has it."""

from benchmarks.lib import scopes


def read(m: dict):
    return scopes.share(m, lambda table: table.group_s("optimizer"))
