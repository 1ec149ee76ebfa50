"""model + kernels: programs XLA compiled for a second or more in set-up -
``compile`` rows of the program's start-up record at or over
``profiling.SLOW_COMPILE_S`` (the persistent cache missed, or was not asked:
the row keeps which).  0 on a warm machine: says whether a line's ``setup_s``
is a warm or a cold one.  ROADMAP S10 (b), (g).

Cut at the LAST fit's ``first_dispatch_done``, like the set-up seconds.
``None`` where the program keeps no such record."""

from benchmarks.lib import startup


def read(m: dict):
    found = startup.summary()
    return None if found is None else float(found["slow_compiles"])
