"""staging + H2D: share of the traced window the idlest chip sat idle
while the host staged, dispatched or waited for a window's transfer
(``ddl.transfer_wait``, ``ddl.release_wait``, ``ddl.staging_copy``,
``ddl.staging_transfer``, ``ddl.ingest_put_window``)."""

from benchmarks.lib import gapshare


def read(m: dict):
    return gapshare.gap_share(m, gapshare.TRANSFER)
