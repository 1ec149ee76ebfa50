"""window rings: share of the steady window a host thread spent waiting
to acquire a committed window (``consumer.wait``).  Host clock: what the
thread waited for, not what the device lost."""


def read(m: dict):
    total = m["counters"].get("consumer.wait.total_s")
    if total is None:
        return None
    return 100.0 * total / m["window_s"]
