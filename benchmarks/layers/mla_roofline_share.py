"""model + kernels: the latent-attention flash kernels' share of their
roofline.  The USEFUL FLOPs of the ``ddl_flash_mla_*`` families found in
the traced window (``lib/mla_flops.py``: causal pairs x rows x heads x 2 x
the contraction widths of the passes each kernel makes - 192-wide scores,
128-wide values - x its calls under the remat policy x the layers, x the
optimizer steps the traced window holds) over what the chips could do in
the device time of those families at the bf16 matmul peak (the kernels are
MXU-bound).  Useful, not executed: a block on the diagonal computes masked
pairs too and the MXU pads the 64-deep rotary product to its own depth, so
both read as lost share and a reading cannot pass 100.  ``None`` where the
configuration is not of this shape, the program has no such kernels (the
parent of the PR that brought them) or none of the families is among the
reduction's ten largest."""

from benchmarks.lib import mla_flops


def read(m: dict):
    trace, c = m.get("trace"), m.get("config") or {}
    if not trace or "qk_rope_head_dim" not in c or not m.get("peak_flops"):
        return None
    busy = trace["step_program_busy_s"]
    if not busy:
        return None
    mix = m["mix"]
    per_step = mla_flops.mla_kernel_flops_per_step(
        c, mix["batch_rows"], mix["seq"], c["training"]["remat"]
    )
    found = [(name, s) for name, s in trace["device_ops"] if name in per_step]
    if not found:
        return None
    # Executions of the step program the traced window holds, per chip, by
    # time (``flash_roofline_share`` counts them the same way).
    programs = sum(busy) / busy[len(busy) // 2] / m["chips"]
    steps = programs * m["steps_per_window"]
    flops = steps * sum(per_step[name] for name, _ in found)
    seconds = sum(s for _, s in found)  # a mean over the chips
    return 100.0 * flops / (seconds * m["chips"] * m["peak_flops"])
