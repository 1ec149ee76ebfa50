"""device: share of the traced window the idlest chip sat idle under no
host span at all — what the program's stages still fail to name."""

from benchmarks.lib import gapshare


def read(m: dict):
    return gapshare.gap_share(m, gapshare.UNATTRIBUTED)
