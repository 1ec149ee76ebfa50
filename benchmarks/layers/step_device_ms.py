"""model + kernels: device-busy time inside one execution of the step
program (``jit(_run)``, one window), per optimizer step; median over the
traced executions."""


def step_seconds(m: dict):
    busy = (m.get("trace") or {}).get("step_program_busy_s")
    if not busy:
        return None
    return busy[len(busy) // 2] / m["steps_per_window"]


def read(m: dict):
    s = step_seconds(m)
    return None if s is None else 1e3 * s
