"""producers: CPU seconds of the process tree (consumer + producers,
``/proc``) per second of the steady window."""


def read(m: dict):
    if not m.get("cpu_s"):
        return None
    return m["cpu_s"] / m["window_s"]
