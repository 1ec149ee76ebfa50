"""device: process bring-up to a usable backend - ``ddl.bring_up``'s span
(``bringup.bring_up``: ``import jax``, compile-cache placement and salt, the
first ``jax.devices()``).  ROADMAP S10 (d).

Set-up as ``benchmarks/lib/startup.py`` has it; ``None`` where the program
keeps no start-up record, 0.0 where the phase took no time."""

from benchmarks.lib import startup


def read(m: dict):
    return startup.phase("bring_up")
