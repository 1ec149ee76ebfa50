"""on-mesh distribution: host-observed fan-out time per window
(``ici.fanout``: dispatch to ready as seen when polled — a lead, it can
overstate)."""


def read(m: dict):
    windows = m["counters"].get("ici.windows")
    total = m["counters"].get("ici.fanout.total_s")
    if not windows or total is None:
        return None
    return 1e3 * total / windows
