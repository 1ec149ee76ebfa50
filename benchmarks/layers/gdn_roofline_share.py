"""model + kernels: the gated delta rule's recurrence, as the program
executes it, against its roofline.  The least time the chips could take
over the recurrence of the steps the traced window holds
(``lib/gdn_flops.py``: a pass's ``max(FLOPs / peak FLOP/s, bytes / peak HBM
bytes/s)`` in the RECURRENT form - 6 d_k d_v FLOPs a head and token
forward, twice that backward; q, k, v, g, beta in and o out once a pass -
x the passes the remat policy makes x the linear layers) over the device
time of the recurrence found there x chips: every op under
``ddl.gdn_scan`` - the chunks' parallel part, which XLA runs - AND the
``ddl_gdn_*`` kernel families (``gdn_device_share.recurrence_seconds``).
At 96 / 192 the bytes decide (~95 FLOP a byte under the ridge at 240).

Numerator and denominator are the same work: from q, k, v, g, beta to o,
and from o's cotangent to theirs.  It reads the same whatever implements
the scan and wherever the line between kernel and XLA runs: work moved
into a kernel leaves the share alone unless the whole gets faster, and
then it rises.  What the chunked form executes beyond the recurrent form
(Gram matrices, the triangular inverse, chunk states written and read
back, a pass run again by a remat) is in the time and not in the floor:
lost share, so a reading cannot pass 100.  ``None`` where the
configuration is not of this shape or the program has no such scope or
kernels."""

from benchmarks.layers.gdn_device_share import recurrence_seconds
from benchmarks.lib import gdn_flops, peaks


def read(m: dict):
    trace, c = m.get("trace"), m.get("config") or {}
    if not trace or "linear_key_head_dim" not in c or not m.get("peak_flops"):
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
    per_step = gdn_flops.gdn_least_seconds_per_step(
        c, mix["batch_rows"], mix["seq"], c["training"]["remat"],
        m["peak_flops"], peak_bytes,
    )
    # Executions of the step program the traced window holds, per chip, by
    # time (``mla_roofline_share`` counts them the same way).
    programs = sum(busy) / busy[len(busy) // 2] / m["chips"]
    steps = programs * m["steps_per_window"]
    return 100.0 * steps * sum(per_step.values()) / seconds
