"""staging + H2D: share of ingested windows whose transfer sourced the
ring slot directly (no host memcpy)."""


def read(m: dict):
    windows = m["counters"].get("ingest.windows")
    if not windows:
        return None
    return 100.0 * m["counters"].get("staging.alias_windows", 0.0) / windows
