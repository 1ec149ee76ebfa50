"""trainer: of set-up's four build kinds, the seconds a Trainer's stage caused
(``ddl.state_init``, ``ddl.step_dispatch``): what a training job pays.  The
rest is the harness's: the weights' program and the reference check.

Set-up as ``benchmarks/lib/startup.py`` has it; ``None`` where the program
keeps no start-up record, 0.0 where the phase took no time."""

from benchmarks.lib import startup


def read(m: dict):
    return startup.phase("trainer_build")
