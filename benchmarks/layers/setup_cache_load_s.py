"""model + kernels: seconds spent retrieving and loading executables the
persistent compile cache held (backend-compile extents with a cache hit
inside).

Set-up as ``benchmarks/lib/startup.py`` has it; ``None`` where the program
keeps no start-up record, 0.0 where the phase took no time."""

from benchmarks.lib import startup


def read(m: dict):
    return startup.phase("cache_load")
