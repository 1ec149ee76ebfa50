"""trainer: host time to dispatch one window's scanned steps
(``trainer.step_dispatch``), mean over the steady part.  A recompile, a
retrace or a full dispatch queue shows here first.  Host clock."""


def read(m: dict):
    total = m["counters"].get("trainer.step_dispatch.total_s")
    count = m["counters"].get("trainer.step_dispatch.count")
    if total is None or not count:
        return None
    return 1e3 * total / count
