"""model + kernels: share of the traced window the chips spent in the
routed expert layer outside its grouped matmuls — own time of the step
program's ops under ``ddl.moe``, ``ddl.moe_route``, ``ddl.moe_experts``
and ``ddl.moe_combine``, the ``ragged-dot-*`` / ``ddl_gmm*`` families
left out: pre-norm, router, top-k, sorts, gather, the elementwise passes
over the sorted rows, scatter-add, combine, residual.  Mean over the
chips.  ``None`` as ``attn_dense_device_share`` has it."""

from benchmarks.lib import scopes


def read(m: dict):
    return scopes.share(m, lambda table: table.group_s("moe"))
