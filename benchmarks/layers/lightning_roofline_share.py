"""model + kernels: the fixed-decay recurrence, as the program executes it,
against its roofline.  The least time the chips could take over the
recurrence of the steps the traced window holds (``lib/sala_flops.py``: a
pass's ``max(FLOPs / peak FLOP/s, bytes / peak HBM bytes/s)`` in the
RECURRENT form - 4 d^2 FLOPs a head and token forward, twice that backward;
q, k, v in and o out once forward, q, k, v, dO in and three cotangents out
backward - x the passes the remat policy makes x the lightning layers) over
the device time of the recurrence found there x chips
(``lightning_device_share.recurrence_seconds``: the scope and the kernel
families).  At 128 the bytes decide (64 FLOP a byte under the ridge at
240).

Numerator and denominator are the same work: from q, k, v to o, and from
o's cotangent to theirs, whatever implements the scan.  What the chunked
form executes beyond the recurrent form (the intra-chunk products, chunk
states written and read back, the three-pass product that feeds the
state) is in the time and not in the floor: lost share, so a reading
cannot pass 100.  ``None`` where the configuration is not of this shape or
the program has no such scope or kernels."""

from benchmarks.layers.lightning_device_share import recurrence_seconds
from benchmarks.lib import peaks, sala_flops


def read(m: dict):
    trace, c = m.get("trace"), m.get("config") or {}
    if not trace or "lightning_head_dim" not in c or not m.get("peak_flops"):
        return None
    busy = trace["step_program_busy_s"]
    seconds = recurrence_seconds(m)
    if not busy or seconds is None:
        return None
    peak_bytes = next(
        (row[2] for row in peaks.PEAKS if row[1] == m["peak_flops"]), None
    )
    if peak_bytes is None:
        return None
    mix = m["mix"]
    per_step = sala_flops.lightning_least_seconds_per_step(
        c, mix["batch_rows"], mix["seq"], c["training"]["remat"],
        m["peak_flops"], peak_bytes,
    )
    return 100.0 * sala_flops.steps_traced(m) * sum(per_step.values()) / seconds
