"""on-mesh distribution: share of the traced window the idlest chip sat
idle while the host dispatched the ICI fan-out (``ddl.ici_fanout``: lane
pack + ring-kernel launch on the anchor chip)."""

from benchmarks.lib import gapshare


def read(m: dict):
    return gapshare.gap_share(m, gapshare.FANOUT)
