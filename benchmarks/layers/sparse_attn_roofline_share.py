"""model + kernels: the block-sparse flash kernels' share of their
roofline.  The USEFUL FLOPs of the ``ddl_flash_sparse_*`` families found in
the traced window (``lib/sala_flops.py``: the pairs of ``visible(t)`` - a
query of block ``b`` sees ``min(b + 1, 96)`` blocks, its own up to itself:
60.8% of the causal triangle at 16,384 - x the query heads x 2 x head_dim a
matmul pass x the passes each kernel makes x its calls under the remat
policy x the sparse layers x the optimizer steps the traced window holds)
over what the chips could do in the device time of those families at the
bf16 matmul peak.  Useful, not executed: a query tile attends its MERGED
list and masks what a position did not choose, the diagonal block computes
masked pairs, a list of odd length pads its last step: all of it reads as
lost share, so a reading cannot pass 100.  ``None`` where the configuration
is not of this shape, the rows are short enough for the dense kernels, or
the program has no such kernels."""

from benchmarks.lib import sala_flops, scopes


def read(m: dict):
    c = m.get("config") or {}
    if "sparse_config" not in c or not m.get("peak_flops"):
        return None
    table = scopes.table_of_run(m)
    if table is None or not m["trace"]["step_program_busy_s"]:
        return None
    mix = m["mix"]
    per_step = sala_flops.sparse_useful_flops_per_step(
        c, mix["batch_rows"], mix["seq"], c["training"]["remat"]
    )
    seconds = {
        name: table.seconds(lambda s, f, w, family, name=name: family == name)
        for name in per_step
    }
    found = [name for name in per_step if seconds[name]]
    if not found:
        return None
    flops = sala_flops.steps_traced(m) * sum(per_step[name] for name in found)
    return 100.0 * flops / (
        sum(seconds[name] for name in found) * m["chips"] * m["peak_flops"]
    )
