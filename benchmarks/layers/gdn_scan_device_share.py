"""model + kernels: share of the traced window the chips spent in what of
the gated delta rule's recurrence XLA runs around the kernels - own time
of the step program's ops under ``ddl.gdn_scan`` (the chunks' parallel
part: decay sums, Gram matrices, the triangular inverse, ``W``, ``U``, each
chunk's map, the output, the relayouts between them; forward, backward and
recomputed), the ``ddl_gdn_*`` families left out.  Mean over the chips.
With ``gdn_device_share`` it is the recurrence as executed, the time
``gdn_roofline_share`` holds against the recurrence's floor; work moved
from here into a kernel moves between the two and leaves that sum to
judge it.  ``benchmarks/lib/scopes.py`` reports the scope as ``other``, so
the selection is made here.  ``None`` without a trace, and on a program
without the scope."""

from benchmarks.layers.gdn_device_share import SCAN_SCOPE, is_gdn_kernel
from benchmarks.lib import scopes


def read(m: dict):
    table = scopes.table_of_run(m)
    if table is None:
        return None
    secs = table.seconds(
        lambda scope, frame, which, family:
        scope == SCAN_SCOPE and not is_gdn_kernel(family)
    )
    return 100.0 * secs / table.window_s if secs else None
