"""staging + H2D: bytes ingested per second over the link ceiling
``ingest.measure_h2d_bandwidth()`` gave once in set-up."""


def read(m: dict):
    nbytes = m["counters"].get("ingest.bytes")
    if not nbytes or not m.get("link_bytes_per_s"):
        return None
    return 100.0 * nbytes / m["window_s"] / m["link_bytes_per_s"]
