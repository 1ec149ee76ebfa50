"""model + kernels: seconds LOWERING jaxprs to MLIR in set-up (JAX's
``jaxpr_to_mlir_module_duration`` extents); Pallas kernel bodies are traced
and lowered inside them.  ROADMAP S10 (a), (c).

Set-up as ``benchmarks/lib/startup.py`` has it; ``None`` where the program
keeps no start-up record, 0.0 where the phase took no time."""

from benchmarks.lib import startup


def read(m: dict):
    return startup.phase("lower")
