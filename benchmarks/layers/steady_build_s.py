"""trainer: seconds JAX spent building programs (trace, lower, compile, cache
load) under the Trainer's stages over the steady part - the ``build.*``
timers' deltas.  Expected 0.0: a retrace or a recompile inside the timed
window shows here as seconds, beside ``step_dispatch_host_ms``.  ``None`` on
a program without the timers."""

KINDS = ("trace", "lower", "compile", "cache_load")


def read(m: dict):
    found = [
        m["counters"].get(f"build.{kind}.total_s") for kind in KINDS
    ]
    if all(v is None for v in found):
        return None
    return sum(v for v in found if v is not None)
