"""model + kernels: model FLOPs of a step over what the chips could do
in the time the step program kept them busy: utilisation while the chip
works."""

from benchmarks.layers.step_device_ms import step_seconds


def read(m: dict):
    s = step_seconds(m)
    if not s:
        return None
    return 100.0 * m["flops_per_step"] / (s * m["peak_flops"] * m["chips"])
