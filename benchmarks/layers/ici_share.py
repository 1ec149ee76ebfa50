"""on-mesh distribution: share of served windows that rode the ICI
fan-out tier."""


def read(m: dict):
    windows = m["counters"].get("consumer.windows")
    if not windows:
        return None
    return 100.0 * m["counters"].get("ici.windows", 0.0) / windows
