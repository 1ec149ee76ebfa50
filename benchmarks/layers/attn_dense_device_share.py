"""model + kernels: share of the traced window the chips spent in the
attention blocks outside the flash kernels — own time of the step
program's ops under ``ddl.attn`` and the scopes nested in it
(``ddl.attn_gate``, ``ddl.mla_q``, ``ddl.mla_kv_up``), the ``ddl_flash_*``
families left out: norms, projections, QK-norm, RoPE, the gate, ``wo``,
the residual and the relayouts XLA puts around the kernels.  Mean over the
chips.  ``None`` without a trace, on a program without the scopes, or on
an executable compiled before them (``benchmarks/lib/scopes.py``)."""

from benchmarks.lib import scopes


def read(m: dict):
    return scopes.share(m, lambda table: table.group_s("attn"))
