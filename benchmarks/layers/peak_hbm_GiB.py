"""device: peak HBM on the fullest chip after the window, from
``memory_stats()``: ``peak_bytes_in_use`` (arrays) plus
``peak_bytes_reserved`` (what the runtime sets aside for the programs'
temporaries, the saved activations among them)."""


def read(m: dict):
    if not m.get("memory_peak_bytes"):
        return None
    return m["memory_peak_bytes"] / 2**30
