"""model + kernels: the selection kernel's share of its roofline.  The
score FLOPs of the traced steps' selections (``lib/sala_flops.py``: every
query head against the compressed keys wholly in its past, 2 x head_dim a
pair, x the sparse layers; forward only) over what the chips could do in
the device time of the ``ddl_sparse_select`` family at the bf16 matmul
peak.  Useful, not executed: the kernel scores every compressed key, the
future ones and the planes that repeat a key included, and spends most of
its time on the softmaxes, not the matmul: all of that reads as lost share,
so a reading cannot pass 100.  ``None`` where the configuration is not of
this shape or the program has no such kernel."""

from benchmarks.layers.sparse_select_device_share import SELECT_FAMILY
from benchmarks.lib import sala_flops, scopes


def read(m: dict):
    c = m.get("config") or {}
    if "sparse_config" not in c or not m.get("peak_flops"):
        return None
    table = scopes.table_of_run(m)
    if table is None or not m["trace"]["step_program_busy_s"]:
        return None
    seconds = table.seconds(lambda s, f, w, family: family == SELECT_FAMILY)
    mix = m["mix"]
    flops = sala_flops.select_flops_per_step(
        c, mix["batch_rows"], mix["seq"], c["training"]["remat"]
    )
    if not seconds or not flops:
        return None
    return 100.0 * sala_flops.steps_traced(m) * flops / (
        seconds * m["chips"] * m["peak_flops"]
    )
