"""Shared example-runner plumbing.

The platform is JAX's own choice: ``JAX_PLATFORMS=cpu`` runs an example
on the CPU (the test suite does), nothing set runs it on the chip.  The
one thing the examples configure is where compiled programs are cached
(``ddl_tpu.bringup.configure_compile_cache``).
"""

import multiprocessing


def configure() -> None:
    if multiprocessing.parent_process() is not None:
        return  # a spawned producer re-importing the script: stays off JAX
    from ddl_tpu.bringup import configure_compile_cache

    configure_compile_cache()
