"""Process bring-up: which JAX platform, and where compiles are cached.

The entry scripts (``benchmarks/run.py``, ``chip_smoke.py``,
``tools/probe_flash_tile.py``, ``examples/``, ``__graft_entry__.py``)
call these two functions once,
in the ONE process that will do the device work.  A chip belongs to one
process at a time, so nothing here probes the backend from a child, and
nothing falls back: a run that wants a TPU and finds none stops with
the reason instead of publishing CPU numbers under device names.

Producer workers never call this module (they stay off JAX).
"""

from __future__ import annotations

import os
import time
from typing import Optional

#: The checkout's own cache directory (git-ignored).  A FIXED path: the
#: path is part of the cache key, so a directory that moves never hits.
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache",
)


def configure_compile_cache() -> str:
    """Place JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set the placement is the
    environment's — JAX reads the variable itself and nothing is set in
    code.  Otherwise the cache lives in ``<checkout>/.jax_cache``.
    """
    _salt_cache_key()
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR


def _salt_cache_key() -> None:
    """Make the model-layer scope table a part of every cache key.

    JAX hashes a program with its debug info stripped, and a
    ``jax.named_scope`` is debug info: a program keeps its key when a
    scope is added, and a warm cache then hands back the executable that
    was compiled without it, whose device trace names no scope
    (``benchmarks/lib/scopes.py`` would read every op as unscoped).
    ``jax_compilation_cache_include_metadata_in_key`` is no way out: it
    puts file paths and line numbers into the key, so a checkout in
    another directory never hits.  The key has a hook for a deployment's
    own salt, ``jax._src.cache_key.custom_hook`` (a private module: where
    a later JAX has no such function nothing is salted, and the first
    run after a change of the table wants an empty cache).  The salt is
    ``ops.naming.scope_table_digest()``, so the key moves when the table
    does and at no other time.  Whatever hook is installed already keeps
    its say."""
    try:
        from jax._src import cache_key
    except ImportError:
        return
    hook = getattr(cache_key, "custom_hook", None)
    if hook is None or getattr(hook, "ddl_scope_salt", False):
        return
    from ddl_tpu.ops.naming import scope_table_digest

    def salted() -> str:
        return hook() + "ddl.scopes=" + scope_table_digest()

    salted.ddl_scope_salt = True
    cache_key.custom_hook = salted


def bring_up(request: Optional[str] = None) -> str:
    """Initialise JAX in this process and return its platform.

    ``request`` is what the caller was asked for BY NAME: ``"cpu"``
    selects the CPU backend; ``None``/``""``/``"tpu"`` require a TPU and
    raise ``SystemExit`` with the reason when JAX finds anything else
    (exit code 1, nothing measured).  Also places the compile cache, and
    starts the start-up record's listening (``profiling.startup_record``:
    the whole of this call is its ``ddl.bring_up`` row).
    """
    if request not in (None, "", "cpu", "tpu"):
        raise ValueError(f"platform request must be cpu|tpu, got {request!r}")
    entered = time.monotonic()
    import jax

    from ddl_tpu import profiling
    from ddl_tpu.observability import metrics

    profiling.listen_for_builds()
    with profiling.stage("ddl.bring_up", metrics(), started=entered):
        if request == "cpu":
            jax.config.update("jax_platforms", "cpu")
        configure_compile_cache()
        try:
            platform = jax.devices()[0].platform
        except RuntimeError as e:  # no backend could be initialised
            raise SystemExit(f"JAX found no usable backend: {e}") from e
    if request != "cpu" and platform != "tpu":
        raise SystemExit(
            f"this run needs a TPU and JAX found only {platform!r}; a CPU "
            "run has to be asked for by name"
        )
    return platform
