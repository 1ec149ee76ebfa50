"""model + kernels: share of the traced window the chips spent in the
multi-token-prediction module - own time of every op of the step program
whose path holds the frame ``ddl.mtp`` (``models/xing4.py``: the shifted
embedding, the two norms and ``W_eh``, the module's whole layer - its wraps,
latent attention with its kernels, router and experts -, its head over the
vocabulary slice and its cross-entropy; every pass), mean over the chips.

A cross-cut, as ``recompute_device_share`` is: ``lib/scopes.py`` names an op
by its INNERMOST ``ddl.`` frame, so the module's attention is also in
``attn_dense_device_share``, its head in ``head_device_share``, its wraps in
``hc_device_share``.  Here the whole path is read: the trace's table is made
once more with every path that holds ``ddl.mtp`` cut down to that frame.
XLA's grouped-matmul kernels keep no path at all (``ragged-dot-*``): the
module's are not counted.  ``None`` without a trace, and on a program
without the scope (the parent)."""

import dataclasses
import glob
import os
import tempfile

from benchmarks.lib import scopes

MTP_SCOPE = "ddl.mtp"


def _table(m: dict):
    """The run's table (``scopes.table_of_run``'s file, found the same way)
    with the module's ops renamed to their outermost frame."""
    if scopes.table_of_run(m) is None or MTP_SCOPE not in scopes._program_scopes():
        return None
    want = m["trace"]["window_s"]
    files = glob.glob(os.path.join(
        tempfile.gettempdir(), "ddl_bench_*", "trace", "**", "*.xplane.pb"
    ), recursive=True)
    for path in sorted(files, key=os.path.getmtime, reverse=True):
        planes = scopes.read_planes(path)
        for plane in planes:
            plane.meta = {
                mid: dataclasses.replace(meta, tf_op=MTP_SCOPE)
                if MTP_SCOPE in meta.tf_op else meta
                for mid, meta in plane.meta.items()
            }
        table = scopes.tabulate(planes)
        if table is not None and table.window_s == want:
            return table
    return None


def read(m: dict):
    table = _table(m)
    if table is None:
        return None
    secs = table.seconds(lambda scope, frame, which, family: scope == MTP_SCOPE)
    return 100.0 * secs / table.window_s if secs else None
