"""producers: mean over the fits of ``last_readback`` -> ``returned``: stream
drain, checkpoint flush, watchdog stop, producers' join, rings unlinked (once
inside ``setup_s``, between the warm-up fit and the measured one).

Set-up as ``benchmarks/lib/startup.py`` has it; ``None`` where the program
keeps no start-up record, 0.0 where the phase took no time."""

from benchmarks.lib import startup


def read(m: dict):
    return startup.phase("fit_stop")
