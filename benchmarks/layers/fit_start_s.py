"""producers: the data plane's time to first batch - mean over the fits of
``entered`` -> ``first_window`` less the builds inside it: pool start, state
placement, loader attach, first fill, verify, first H2D.

Set-up as ``benchmarks/lib/startup.py`` has it; ``None`` where the program
keeps no start-up record, 0.0 where the phase took no time."""

from benchmarks.lib import startup


def read(m: dict):
    return startup.phase("fit_start")
