"""device: share of the traced window the idlest chip sat idle while the
host was inside the data plane (``ddl.window_wait`` and every stage
under it, the staging executor's, the ICI fan-out's) — device time lost
waiting for data, on the device's clock."""

from benchmarks.lib import gapshare


def read(m: dict):
    return gapshare.gap_share(m, gapshare.DATA_PLANE)
