"""model + kernels: share of the traced window the chips spent selecting
key blocks - own time of the step program's ops under ``ddl.sparse_select``
(``ddl_tpu/ops/sparse_attention.py``: the compressed keys, the
``ddl_sparse_select`` kernel that writes the block scores, ``top_k``, the
visibility bitmap, the merged lists and their transposes), the kernel
included.  Forward only: the selection has no gradient, and under
``selective`` its lists are saved, so nothing of it runs in a backward
pass.  Mean over the chips.  ``benchmarks/lib/scopes.py`` reports the scope
as ``other``, so the selection is made here.  ``None`` without a trace, and
on a program without the scope."""

from benchmarks.lib import scopes

SELECT_SCOPE = "ddl.sparse_select"
SELECT_FAMILY = "ddl_sparse_select"


def read(m: dict):
    table = scopes.table_of_run(m)
    if table is None:
        return None
    secs = table.seconds(
        lambda scope, frame, which, family:
        scope == SELECT_SCOPE or family == SELECT_FAMILY
    )
    return 100.0 * secs / table.window_s if secs else None
