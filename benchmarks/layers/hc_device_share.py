"""model + kernels: share of the traced window the chips spent on the
hyper-connected residual path - own time of the step program's ops under
``ddl.hc_pre`` and ``ddl.hc_post`` (``models/hyper_connections.py``: the pass
in front of the mixing matrices, the matrices' arithmetic with its Sinkhorn
rounds, ``h = Hpre X``, ``X' = Hres X + Hpost^T y``, every pass: forward, the
forward computed again under the remat policy, backward; the streams'
replication and closing sum) plus the ``ddl_hc_*`` kernel families wherever
they stand (none yet: XLA's fusions run it).  The multi-token-prediction
module's two wraps are counted here too (the innermost scope names an op).
Mean over the chips, whole sums off the trace's own table - no top-ten cut.
``None`` without a trace, and on a program without the scopes or the
kernels (the parent)."""

from benchmarks.lib import scopes

HC_SCOPES = ("ddl.hc_pre", "ddl.hc_post")
HC_FAMILIES = ("ddl_hc_",)


def hc_seconds(m: dict):
    """Own seconds of the residual path AS EXECUTED, mean over the chips;
    ``None`` where the trace has no table or nothing of it."""
    table = scopes.table_of_run(m)
    if table is None:
        return None
    secs = table.seconds(
        lambda scope, frame, which, family:
        scope in HC_SCOPES or family.startswith(HC_FAMILIES)
    )
    return secs or None


def read(m: dict):
    secs = hc_seconds(m)
    return None if secs is None else 100.0 * secs / m["trace"]["window_s"]
