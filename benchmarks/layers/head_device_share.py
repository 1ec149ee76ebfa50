"""model + kernels: share of the traced window the chips spent at the
model's two ends — own time of the step program's ops under ``ddl.embed``
/ ``ddl.patchify`` (token gather and its scatter-add; patchify, patch
projection, position) and ``ddl.head`` (final norm, vocabulary or class
matmul, cross-entropy, the auxiliary losses' reduction), mean over the
chips.  ``None`` as ``attn_dense_device_share`` has it."""

from benchmarks.lib import scopes


def read(m: dict):
    return scopes.share(m, lambda table: table.group_s("head"))
