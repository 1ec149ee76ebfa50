"""model + kernels: the grouped matmuls' share of their roofline.  The
FLOPs they execute in the traced window (``lib/moe_flops.py``: calls per
layer by the remat policy x 2 x rows x hidden x expert width, x the
optimizer steps the traced window holds) over what the chips could do in
the device time of those op families at the matmul peak.  At 2,048 rows
a group the kernel is MXU-bound (arithmetic intensity ~510 FLOP/B
against the ridge at 240), so the roof is the bf16 matmul peak.  A reading
over 100 means the count is wrong.  ``None`` where the configuration has
no experts or the families are outside the reduction's top ten."""

from benchmarks.layers.gmm_device_share import gmm_seconds
from benchmarks.lib import moe_flops


def read(m: dict):
    s = gmm_seconds(m)
    c = m.get("config") or {}
    if s is None or "num_experts" not in c or not m.get("peak_flops"):
        return None
    trace, mix = m["trace"], m["mix"]
    busy = trace["step_program_busy_s"]
    if not busy:
        return None
    # Executions of the step program the traced window holds, per chip.
    # The window opens at the profiler's first event: the first execution
    # in it is usually cut short, so whole ones are counted by time (the
    # sorted list's median is a whole execution's).
    programs = sum(busy) / busy[len(busy) // 2] / m["chips"]
    tokens_per_step = mix["batch_rows"] * mix["seq"]
    flops = (
        programs * m["steps_per_window"]
        * moe_flops.gmm_flops_per_step(c, tokens_per_step, c["training"]["remat"])
    )
    # ``s`` is a mean over the chips; the FLOPs are the whole mesh's.
    return 100.0 * flops / (s * m["chips"] * m["peak_flops"])
