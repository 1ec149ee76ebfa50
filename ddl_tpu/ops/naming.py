"""Names on the device trace: the Pallas kernels' own, and the model-layer
scopes every op of a train step stands under (:data:`SCOPE_NAMES`).

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

import contextlib
import hashlib
from typing import Any, Callable

import jax
from jax.experimental import pallas as pl

#: The kernels' names as ``breakdown.device_ops`` shows them; the
#: benchmark's ``flash_device_share`` reads the ``ddl_flash_`` prefix.
#: Of the three blockwise families ``*_bwd_dkv`` is the WHOLE backward pass
#: since PR 45 - the dK/dV grid carrying dQ (and the latent form's dQ_rope)
#: in VMEM, five products a block pair - and ``*_bwd_dq`` appears only where
#: a row is too long for that (``flash_attention._BWD_ROW_BYTES``: none of
#: the benchmark's cells): a trace that holds a ``*_bwd_dq`` family took
#: the two-kernel side.  (The block-sparse kernels keep their own pair.)
KERNEL_NAMES = (
    "ddl_flash_fwd", "ddl_flash_bwd_dq", "ddl_flash_bwd_dkv",
    # the same kernels with a sliding window's band (``window=``)
    "ddl_flash_swa_fwd", "ddl_flash_swa_bwd_dq", "ddl_flash_swa_bwd_dkv",
    # ... and with latent attention's rotary product (``q_rope=``)
    "ddl_flash_mla_fwd", "ddl_flash_mla_bwd_dq", "ddl_flash_mla_bwd_dkv",
    "ddl_flash_tile_fwd", "ddl_flash_tile_bwd",
    # the gated delta rule's chunks from q, k, v, decay sums and beta to o
    # (the WY preparation, then ``Vn = U - W H``, ``O = Qg H + P Vn``, ``H <-
    # a H + Kd^T Vn``, the state in VMEM for the whole row) and the backward
    # pass, the chunks last to first (``ops/gated_delta.py``);
    # ``gdn_device_share`` reads ``ddl_gdn_``
    "ddl_gdn_fwd", "ddl_gdn_bwd",
    # linear attention with a fixed decay a head: a chunk from q, k, v to o,
    # the state in VMEM for the whole row, and its backward pass
    # (``ops/lightning_attention.py``)
    "ddl_lightning_fwd", "ddl_lightning_bwd",
    # block-sparse attention over a per-query selection of key blocks
    # (``ops/sparse_attention.py``): the selection's block scores, and the
    # flash kernels whose key blocks come from scalar-prefetched lists
    "ddl_sparse_select",
    "ddl_flash_sparse_fwd", "ddl_flash_sparse_bwd_dq", "ddl_flash_sparse_bwd_dkv",
    # a hyper-connected wrap's passes over the four-row stream, one read of
    # it each (``ops/hyper_connections.py``); the benchmark's
    # ``hc_device_share`` / ``hc_roofline_share`` read ``ddl_hc_`` beside the
    # ``ddl.hc_pre`` / ``ddl.hc_post`` scopes
    "ddl_hc_pre_fwd", "ddl_hc_pre_bwd", "ddl_hc_post_fwd", "ddl_hc_post_bwd",
    "ddl_ici_bcast", "ddl_ici_scatter", "ddl_shuffle_exchange",
)

#: The model-layer scopes of the step program: every op of a train step
#: is traced under one of them, at the shared routine where there is one
#: (``docs/OBSERVABILITY.md`` has the table of what each encloses, and a
#: test keeps the two equal).  A scope is a frame of JAX's name stack and
#: reaches the device trace as a part of each op's ``tf_op`` path,
#: whatever transform wraps it (``jvp(ddl.attn)/...``,
#: ``transpose(jvp(...))/checkpoint/ddl.attn/...``,
#: ``.../checkpoint/rematted_computation/ddl.attn/...``): the innermost
#: ``ddl.`` / ``ddl_`` frame names the op's layer, the wrappers its pass.
#: ``benchmarks/lib/scopes.py`` reads them.  Compile-time metadata only:
#: no op, no run-time cost, no knob.
SCOPE_NAMES = (
    "ddl.embed", "ddl.patchify",
    "ddl.hc_pre", "ddl.hc_post",
    "ddl.attn", "ddl.attn_gate", "ddl.mla_q", "ddl.mla_kv_up",
    "ddl.gdn_proj", "ddl.gdn_conv", "ddl.gdn_scan", "ddl.gdn_out",
    "ddl.lightning_proj", "ddl.lightning_scan", "ddl.lightning_out",
    "ddl.sparse_select",
    "ddl.shortconv_proj", "ddl.shortconv", "ddl.shortconv_out",
    "ddl.mlp",
    "ddl.moe", "ddl.moe_route", "ddl.moe_experts", "ddl.moe_combine",
    "ddl.moe_overflow", "ddl.moe_shared",
    "ddl.head", "ddl.mtp", "ddl.optimizer",
)

#: Raise when a scope moves to other ops and the table stays as it is:
#: the compile cache is keyed by the table (:func:`scope_table_digest`),
#: not by where its names are used.
SCOPE_PLACEMENT_REV = 2


def scope(name: str) -> contextlib.AbstractContextManager:
    """``jax.named_scope(name)`` for a name of :data:`SCOPE_NAMES`."""
    assert name in SCOPE_NAMES, name
    return jax.named_scope(name)


def scope_table_digest() -> str:
    """What ``ddl_tpu.bringup.configure_compile_cache`` salts the compile
    cache's key with (JAX hashes a program without its name stack: the
    docstring there)."""
    text = "\n".join((str(SCOPE_PLACEMENT_REV),) + SCOPE_NAMES)
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def named_pallas_call(name: str, kernel: Callable, **kwargs: Any) -> Callable:
    """``pl.pallas_call(kernel, **kwargs)`` whose custom call is named
    ``name`` in the compiled HLO."""
    assert name in KERNEL_NAMES, name
    call = pl.pallas_call(kernel, name=name, **kwargs)

    def run(*args: Any) -> Any:
        with jax.named_scope(name):
            return call(*args)

    return run
