"""Names of their own for the Pallas kernels on the device trace.

A profiler trace's ``XLA Ops`` events carry the HLO instruction name, and
XLA names a ``pallas_call``'s custom call after the innermost frame of
JAX's name stack: unnamed, the flash kernels read ``jvp__``,
``transpose_jvp___`` or ``checkpoint`` depending on the transforms around
them, and every kernel under a ``shard_map`` reads ``shard_map``.
``pallas_call(name=...)`` alone is not enough — a transform folds the
first frame under it into its own (``transpose(jvp(<name>))``) — so the
call is traced under a ``jax.named_scope`` of the same name as well; the
instruction then reads ``<name>.<n>`` whatever wraps it
(``tests/test_tpu_compile.py`` greps the compiled HLO).

Not every kernel on the trace is the repo's.  XLA makes some itself and
names them itself, stably: ``jax.lax.ragged_dot`` (the grouped matmuls
of ``models/moe.py``'s dropless expert layer) and both of its
transposes compile on a v5e to Mosaic custom calls
``ragged-dot-none.<n>``, each with a small ``ragged-dot-metadata.<n>``
beside it.  Those families need no name from here; the benchmark's
``gmm_device_share`` / ``gmm_roofline_share`` read the ``ragged-dot-``
prefix, and would read ``ddl_gmm*`` should a later PR bring a grouped
matmul of the repo's own (add it to :data:`KERNEL_NAMES` then).
"""

from __future__ import annotations

from typing import Any, Callable

import jax
from jax.experimental import pallas as pl

#: The kernels' names as ``breakdown.device_ops`` shows them; the
#: benchmark's ``flash_device_share`` reads the ``ddl_flash_`` prefix.
KERNEL_NAMES = (
    "ddl_flash_fwd", "ddl_flash_bwd_dq", "ddl_flash_bwd_dkv",
    # the same kernels with a sliding window's band (``window=``)
    "ddl_flash_swa_fwd", "ddl_flash_swa_bwd_dq", "ddl_flash_swa_bwd_dkv",
    # ... and with latent attention's rotary product (``q_rope=``)
    "ddl_flash_mla_fwd", "ddl_flash_mla_bwd_dq", "ddl_flash_mla_bwd_dkv",
    "ddl_flash_tile_fwd", "ddl_flash_tile_bwd",
    "ddl_ici_bcast", "ddl_ici_scatter", "ddl_shuffle_exchange",
)


def named_pallas_call(name: str, kernel: Callable, **kwargs: Any) -> Callable:
    """``pl.pallas_call(kernel, **kwargs)`` whose custom call is named
    ``name`` in the compiled HLO."""
    assert name in KERNEL_NAMES, name
    call = pl.pallas_call(kernel, name=name, **kwargs)

    def run(*args: Any) -> Any:
        with jax.named_scope(name):
            return call(*args)

    return run
