"""model + kernels: the gated short convolution, as the program executes
it, against its bandwidth floor.  The least time the chips could take over
its passes of the steps the traced window holds (``lib/lfm2_flops.py``:
forward ``BCx`` in and ``y`` out, 4 d x 2 B a token; backward ``BCx`` and
``dy`` in and ``dBCx`` out, 7 d x 2 B; x the passes the remat policy makes x
the conv layers, over the chip's peak HBM bytes a second) over the device
time found there x chips (``shortconv_device_share.shortconv_seconds``:
every op under ``ddl.shortconv`` and the ``ddl_shortconv_*`` families).

Numerator and denominator are the same work, and the floor is of the work,
not of the implementation: it reads the same whether XLA's fusions or a
kernel run the passes, and rises only when the whole gets faster.  What
the program moves beyond the floor - a padded or float32 copy of the row,
the taps' cotangent reduced in a pass of its own, a pass run again - is in
the time and not in the floor: lost share, so a reading cannot pass 100.
``None`` where the configuration is not of this family or the program has
no such scope or kernels."""

from benchmarks.layers.shortconv_device_share import shortconv_seconds
from benchmarks.lib import lfm2_flops, peaks


def read(m: dict):
    trace, c = m.get("trace"), m.get("config") or {}
    if not trace or c.get("family") != "lfm2_moe" or not m.get("peak_flops"):
        return None
    busy = trace["step_program_busy_s"]
    seconds = shortconv_seconds(m)
    if not busy or seconds is None:
        return None
    peak_bytes = next(
        (row[2] for row in peaks.PEAKS if row[1] == m["peak_flops"]), None
    )
    if peak_bytes is None:
        return None
    mix = m["mix"]
    per_step = lfm2_flops.shortconv_least_seconds_per_step(
        c, mix["batch_rows"], mix["seq"], c["training"]["remat"], peak_bytes
    )
    # Executions of the step program the traced window holds, per chip, by
    # time (``gdn_roofline_share`` counts them the same way).
    programs = sum(busy) / busy[len(busy) // 2] / m["chips"]
    steps = programs * m["steps_per_window"]
    return 100.0 * steps * sum(per_step.values()) / seconds
