"""model + kernels: share of the traced window the chips spent in the
dense MLPs — own time of the step program's ops under ``ddl.mlp`` (norm,
MLP, residual) and ``ddl.moe_shared`` (an expert layer's shared expert),
mean over the chips.  ``None`` as ``attn_dense_device_share`` has it."""

from benchmarks.lib import scopes


def read(m: dict):
    return scopes.share(m, lambda table: table.group_s("mlp"))
