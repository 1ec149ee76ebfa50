"""trainer: share of the steady window the train loop's host thread
spent in ``next(stream)`` (``trainer.window_wait``).  Host clock."""


def read(m: dict):
    total = m["counters"].get("trainer.window_wait.total_s")
    if total is None:
        return None
    return 100.0 * total / m["window_s"]
