"""model + kernels: seconds Python spent TRACING jitted programs in set-up
(JAX's ``jaxpr_trace_duration`` extents, outermost on their thread): the
weights', the reference's, the Trainer's.  ROADMAP S10 (a), (c).

Set-up as ``benchmarks/lib/startup.py`` has it; ``None`` where the program
keeps no start-up record, 0.0 where the phase took no time."""

from benchmarks.lib import startup


def read(m: dict):
    return startup.phase("trace")
