"""device: share of the traced window in which no operation ran, on the
idlest chip."""


def read(m: dict):
    trace = m.get("trace")
    if not trace:
        return None
    return 100.0 * trace["idle_share_worst"]
