"""trainer: share of the traced window the idlest chip sat idle while
the train loop dispatched a window's steps or read a loss back
(``ddl.step_dispatch``, ``ddl.loss_readback``).  The benchmark's own
``bench.window_hook`` is left out: it is not the trainer's."""

from benchmarks.lib import gapshare


def read(m: dict):
    return gapshare.gap_share(m, gapshare.TRAINER)
