"""model + kernels: seconds XLA COMPILED programs in set-up: backend-compile
extents the persistent cache did not answer with a hit.  ROADMAP S10 (b),
(g).

Set-up as ``benchmarks/lib/startup.py`` has it; ``None`` where the program
keeps no start-up record, 0.0 where the phase took no time."""

from benchmarks.lib import startup


def read(m: dict):
    return startup.phase("compile")
