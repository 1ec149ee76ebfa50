"""model + kernels: share of the traced window the chips spent in the
grouped matmuls of the routed expert layer — own time of the op families
XLA's ``ragged_dot`` kernels run under (``ragged-dot-*``), or of a
grouped-matmul kernel of the repo's own (``ddl_gmm*``), averaged over
the chips.  ``None`` where none of them is among the reduction's ten
largest families (a dense model; a program without such a layer)."""

GMM_PREFIXES = ("ragged-dot-", "ddl_gmm")


def gmm_seconds(m: dict):
    trace = m.get("trace")
    if not trace:
        return None
    secs = [s for name, s in trace["device_ops"] if name.startswith(GMM_PREFIXES)]
    return sum(secs) if secs else None


def read(m: dict):
    s = gmm_seconds(m)
    return None if s is None else 100.0 * s / m["trace"]["window_s"]
