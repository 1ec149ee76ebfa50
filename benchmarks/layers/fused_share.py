"""trainer: share of steady windows that took the fused step."""


def read(m: dict):
    if not m["n_windows"]:
        return None
    return 100.0 * m["counters"].get("trainer.fused_windows", 0.0) / m["n_windows"]
