"""window rings: share of the traced window the idlest chip sat idle
while the loader waited for a committed window (``ddl.window_acquire``:
admission, ring wait, integrity verify)."""

from benchmarks.lib import gapshare


def read(m: dict):
    return gapshare.gap_share(m, gapshare.RING)
