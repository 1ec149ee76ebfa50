"""model + kernels: the blockwise flash-attention kernels' share of their
roofline.  The USEFUL FLOPs of the kernel families found in the traced
window (``lib/afmoe_flops.py``: attended pairs - the band's in a sliding
layer, the causal triangle's in a full one - x rows x heads x 2 x head
size x the passes and calls each kernel makes, x the optimizer steps the
traced window holds) over what the chips could do in the device time of
those families at the bf16 matmul peak (head size 128, blocks of 1,024:
the kernels are MXU-bound).  Useful, not executed, pairs: a block on the
diagonal or on the band's edge computes masked pairs too, and a block
outside the band still costs its grid step, so block-granularity waste
reads as lost share and a reading cannot pass 100.  ``None`` where the
configuration states no ``layer_types`` or none of the families is among
the reduction's ten largest."""

from benchmarks.lib import afmoe_flops


def read(m: dict):
    trace, c = m.get("trace"), m.get("config") or {}
    if not trace or "layer_types" not in c or not m.get("peak_flops"):
        return None
    busy = trace["step_program_busy_s"]
    if not busy:
        return None
    mix = m["mix"]
    per_step = afmoe_flops.flash_flops_per_step(
        c, mix["batch_rows"], mix["seq"], c["training"]["remat"]
    )
    found = [(name, s) for name, s in trace["device_ops"] if name in per_step]
    if not found:
        return None
    # Executions of the step program the traced window holds, per chip, by
    # time (``gmm_roofline_share`` counts them the same way).
    programs = sum(busy) / busy[len(busy) // 2] / m["chips"]
    steps = programs * m["steps_per_window"]
    flops = steps * sum(per_step[name] for name, _ in found)
    seconds = sum(s for _, s in found)  # a mean over the chips
    return 100.0 * flops / (seconds * m["chips"] * m["peak_flops"])
