"""model + kernels: the hyper-connected residual path, as the program
executes it, against its bandwidth floor.  The least time the chips could
take over its passes of the steps the traced window holds
(``lib/xing4_flops.py``: per wrap and pass the stream read once and written
once, ``h``, ``y`` and the matrices once, 2 x 35,936 B a token at 4 x 3584 in
bfloat16; x the passes the remat policy makes x the layers that carry a
stream, the multi-token-prediction module's among them; the streams' two
ends; over the chip's peak HBM bytes a second) over the device time found
there x chips (``hc_device_share.hc_seconds``: every op under ``ddl.hc_pre`` /
``ddl.hc_post`` and the ``ddl_hc_*`` families).

Numerator and denominator are the same work, and the floor is of the work,
not of the implementation: it reads the same whether XLA's fusions or a
kernel run the passes, and rises only when the whole gets faster.  What the
program moves beyond the floor - the stream read a second time inside a
pass (once for the projections, once for ``h``), a padded or float32 copy,
the Sinkhorn rounds' intermediates, a reduction in a pass of its own - is in
the time and not in the floor: lost share, so a reading cannot pass 100.
``None`` where the configuration is not of this family or the program has no
such scopes or kernels."""

from benchmarks.layers.hc_device_share import hc_seconds
from benchmarks.lib import peaks, xing4_flops


def read(m: dict):
    trace, c = m.get("trace"), m.get("config") or {}
    if not trace or c.get("family") != "xing4" or not m.get("peak_flops"):
        return None
    busy = trace["step_program_busy_s"]
    seconds = hc_seconds(m)
    if not busy or seconds is None:
        return None
    peak_bytes = next(
        (row[2] for row in peaks.PEAKS if row[1] == m["peak_flops"]), None
    )
    if peak_bytes is None:
        return None
    mix = m["mix"]
    per_step = xing4_flops.hc_least_seconds_per_step(
        c, mix["batch_rows"], mix["seq"], c["training"]["remat"], peak_bytes
    )
    # Executions of the step program the traced window holds, per chip, by
    # time (``shortconv_roofline_share`` counts them the same way).
    programs = sum(busy) / busy[len(busy) // 2] / m["chips"]
    steps = programs * m["steps_per_window"]
    return 100.0 * steps * sum(per_step.values()) / seconds
