"""Ahead-of-time Mosaic compiles for a DESCRIBED ``v5e:2x2`` topology.

Interpret mode proves bytes; only the TPU compiler can refuse a kernel
(a slice off the tiling, too much fast memory, a misused semaphore).
libtpu compiles for a chip that is described and not attached, so the
main path's kernels are compiled here at their real sizes — the smoke
geometry of ``chip_smoke.py`` — on every tier-1 run, at no chip time.
Nothing executes: a passing compile is not a chip run.

Each compile also shows the kernel under a NAME OF ITS OWN in the
compiled HLO (``ddl_tpu.ops.naming``): the instruction name is what a
profiler trace's ``XLA Ops`` events and the benchmark's
``breakdown.device_ops`` carry.

Skipped where the topology cannot be described (no libtpu).  The
persistent compile cache is off around the compiles: an entry written
for a described device cannot be read back without one, and the retry
warns.
"""

import importlib
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs under /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from ddl_tpu.ops import device_shuffle, flash_attention, ici_fanout
from ddl_tpu.ops.naming import KERNEL_NAMES


def kernel_names(compiled_text):
    """The Mosaic custom calls' HLO instruction names, less XLA's
    ``.<n>`` suffix — the op family ``benchmarks/lib/tracered.py`` shows."""
    names = set()
    for line in compiled_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            m = re.match(r"\s*(?:ROOT )?%?([\w\-]+?)(?:\.\d+)* = ", line)
            assert m, line[:120]
            names.add(m.group(1))
    assert names, "no tpu_custom_call in the compiled program"
    return names


def mosaic_grids(lowered_text):
    """{grid: count} over the Mosaic kernels of a lowered program: each
    ``tpu_custom_call`` carries its kernel as MLIR bytecode, whose
    ``iteration_bounds`` is the grid the chip runs."""
    import base64
    import collections

    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir

    grids = collections.Counter()
    for body in re.findall(r'\\22body\\22: \\22([^\\]*)\\22', lowered_text):
        ctx = mlir.make_ir_context()
        ctx.allow_unregistered_dialects = True  # Mosaic's, versioned
        with ctx:
            module = str(ir.Module.parse(base64.b64decode(body)))
        bounds, = re.findall(r"iteration_bounds = array<i64: ([\d, ]+)>", module)
        grids[tuple(int(n) for n in bounds.split(","))] += 1
    return dict(grids)


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except (RuntimeError, ValueError, NotImplementedError, ImportError) as e:
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield tuple(topo.devices)
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


# The chip_smoke.py train-leg attention geometry: batch 4 x seq 2048,
# 16 query / 8 kv heads x 128, bf16.
B, T, H, HKV, D = 4, 2048, 16, 8, 128
# What a blockwise call's forward + backward compile to: the forward kernel
# and ONE backward kernel, the dK/dV grid carrying dQ (PR 45) - no
# ``ddl_flash_bwd_dq`` family at any shape compiled here.
BLOCK_KERNELS = {"ddl_flash_fwd", "ddl_flash_bwd_dkv"}


def _attn_args(device, packed):
    one = SingleDeviceSharding(device)
    q = jax.ShapeDtypeStruct((B, T, H, D), jnp.bfloat16, sharding=one)
    kv = jax.ShapeDtypeStruct((B, T, HKV, D), jnp.bfloat16, sharding=one)
    seg = jax.ShapeDtypeStruct((B, T), jnp.int32, sharding=one)
    return (q, kv, kv) + ((seg,) if packed else ())


@pytest.mark.parametrize("packed", [False, True], ids=["plain", "packed"])
@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd_bwd"])
def test_flash_attention_compiles(v5e, grad, packed):
    def attn(q, k, v, seg=None):
        # interpret=False: the Mosaic kernel, whatever backend this
        # process defaults to.
        return flash_attention(
            q, k, v, causal=True, kv_repeat=H // HKV, interpret=False,
            segment_ids=seg,
        )

    fn = attn
    if grad:
        def fn(q, k, v, *seg):
            return jax.grad(
                lambda q, k, v: attn(q, k, v, *seg)
                .astype(jnp.float32).sum(),
                argnums=(0, 1, 2),
            )(q, k, v)

    text = jax.jit(fn).lower(*_attn_args(v5e[0], packed)).compile().as_text()
    # forward once, one backward kernel: the dK/dV grid carrying dQ
    assert kernel_names(text) == (BLOCK_KERNELS if grad else {"ddl_flash_fwd"})


def test_the_blockwise_flash_kernels_compile_at_head_width_64(v5e):
    """LFM2-24B-A2B's attention layer as the ``lfm2-24b-a2b.tokens-8k`` cell
    runs it: 2 rows x 8,192 positions, 32 query heads over 8 key heads of
    64 - half a lane tile a head, a 64-deep score product, ``kv_repeat`` 4 -
    through the blockwise kernels (T past the one-block path), forward and
    the one backward kernel.  Until PR 43 the blockwise kernels had compiled
    and run at head widths 128 and 192 / 128 only; a block spec or a scratch
    shape that assumed 128 lanes a head fails here, not on the chip."""
    one = SingleDeviceSharding(v5e[0])
    q = jax.ShapeDtypeStruct((2, 8192, 32, 64), jnp.bfloat16, sharding=one)
    kv = jax.ShapeDtypeStruct((2, 8192, 8, 64), jnp.bfloat16, sharding=one)

    def attn(q, k, v):
        return flash_attention(q, k, v, causal=True, kv_repeat=4, interpret=False)

    def grads(q, k, v):
        return jax.grad(
            lambda *a: attn(*a).astype(jnp.float32).sum(), argnums=(0, 1, 2)
        )(q, k, v)

    lowered = jax.jit(grads).lower(q, kv, kv)
    assert kernel_names(lowered.compile().as_text()) == BLOCK_KERNELS
    # the row's default blocks: 8 query blocks x 8 key blocks a query head,
    # in both kernels
    assert mosaic_grids(lowered.as_text()) == {(2, 32, 8, 8): 2}


def _gdn_calls(lowered_text):
    """{kernel name: (operand types, result types)} of the ``ddl_gdn_*``
    custom calls of a lowered program."""
    calls = {}
    for line in lowered_text.splitlines():
        m = re.search(r'kernel_name = "(ddl_gdn_\w+)"', line)
        if m:
            operands, results = line.rsplit(" : ", 1)[1].split(" -> ")
            calls[m.group(1)] = tuple(
                re.findall(r"tensor<([\w]+)>", part) for part in (operands, results)
            )
    return calls


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd_bwd"])
@pytest.mark.parametrize("dtype,heads", [(jnp.bfloat16, 30), (jnp.float32, 6)],
                         ids=["cell_bf16_30_heads", "core_check_f32_6_heads"])
def test_gated_delta_rule_compiles_at_olmo_hybrids_geometry(v5e, grad, dtype, heads):
    """One row of 16,384 positions, heads of 96-wide keys and 192-wide
    values (neither fills 128 lanes): the cell's 30 in bfloat16 - no passes
    of heads: five groups of six on one grid - and the benchmark's core
    check's six in float32, two groups of three.  A tile of two chunks is
    prepared (decay, Gram matrices, the triangular inverse, ``W``, ``U``,
    ``P``) and its chunks meet their state inside the kernel, forward and
    (tiles and chunks last to first, prepared again and transposed in
    place) backward, each compiled once on a grid of (groups of heads, 128
    tiles): a VMEM overflow or a relayout Mosaic does not have fails here,
    not on the chip."""
    from ddl_tpu.ops.gated_delta import gated_delta_rule

    one = SingleDeviceSharding(v5e[0])
    T_, DK, DV = 16384, 96, 192
    args = (
        jax.ShapeDtypeStruct((1, T_, heads, DK), dtype, sharding=one),
        jax.ShapeDtypeStruct((1, T_, heads, DK), dtype, sharding=one),
        jax.ShapeDtypeStruct((1, T_, heads, DV), dtype, sharding=one),
        jax.ShapeDtypeStruct((1, T_, heads), jnp.float32, sharding=one),
        jax.ShapeDtypeStruct((1, T_, heads), jnp.float32, sharding=one),
    )

    def scan(*a):
        return gated_delta_rule(*a, interpret=False)

    fn = scan
    if grad:
        def fn(*a):
            return jax.grad(
                lambda *a: scan(*a).astype(jnp.float32).sum(), argnums=range(5)
            )(*a)

    lowered = jax.jit(fn).lower(*args)
    want = {"ddl_gdn_fwd", "ddl_gdn_bwd"} if grad else {"ddl_gdn_fwd"}
    assert kernel_names(lowered.compile().as_text()) == want
    assert want <= set(KERNEL_NAMES)
    # the backward pass reads the saved chunk states: no second forward kernel
    text = lowered.as_text()
    a_step = 6 if dtype == jnp.bfloat16 else 3
    assert mosaic_grids(text) == {(heads // a_step, 128): 2 if grad else 1}
    calls = _gdn_calls(text)
    assert set(calls) == want
    low = "bf16" if dtype == jnp.bfloat16 else "f32"
    for operands, results in calls.values():
        # the states a kernel writes and reads, one a chunk, are in the
        # operands' dtype ...
        assert f"{heads}x256x96x192x{low}" in operands + results
        # ... and nothing of a chunk's preparation crosses HBM: besides q, k,
        # v, o (tiles of 128 positions), the states and their cotangents a
        # kernel takes and hands back only the decay sums and beta, (2, 128)
        # float32 a tile
        for t in operands + results:
            assert re.fullmatch(
                rf"{heads}x128x128x(96|192)x{low}|{heads}x256x96x192x{low}"
                rf"|{heads}x128x2x128xf32", t
            ), (t, calls)


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd_bwd"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["cell_bf16", "core_check_f32"])
def test_lightning_attention_compiles_at_minicpm_salas_geometry(v5e, grad, dtype):
    """One row of 16,384 positions, 32 heads of 128: a chunk from q, k, v to
    o in one kernel a pass, the operands read where the projections left
    them (no transpose in HBM), on a grid of (head groups, 128 chunks)."""
    from ddl_tpu.ops.lightning_attention import lightning_attention

    one = SingleDeviceSharding(v5e[0])
    x = jax.ShapeDtypeStruct((1, 16384, 32, 128), dtype, sharding=one)

    def scan(q, k, v):
        return lightning_attention(q, k, v, interpret=False)

    fn = scan
    if grad:
        def fn(q, k, v):
            return jax.grad(
                lambda *a: scan(*a).astype(jnp.float32).sum(), argnums=(0, 1, 2)
            )(q, k, v)

    lowered = jax.jit(fn).lower(x, x, x)
    want = {"ddl_lightning_fwd", "ddl_lightning_bwd"} if grad else {"ddl_lightning_fwd"}
    assert kernel_names(lowered.compile().as_text()) == want
    assert want <= set(KERNEL_NAMES)
    groups = 4 if dtype == jnp.bfloat16 else 8  # 8 heads a step, or 4 in float32
    assert mosaic_grids(lowered.as_text()) == {(groups, 128): 2 if grad else 1}


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd_bwd"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["cell_bf16", "core_check_f32"])
def test_sparse_attention_compiles_at_minicpm_salas_geometry(v5e, grad, dtype):
    """One row of 16,384 positions, 32 query heads over 2 key-value heads of
    128, blocks of 64, top-64: the selection kernel and the three flash
    kernels whose key blocks come from scalar-prefetched lists.  The forward
    and dq grids' inner axis is the longest list a tile can hold, two
    blocks a step (128 of 256 blocks); the dkv grid's the query tiles."""
    from ddl_tpu.ops import sparse_attention as S

    one = SingleDeviceSharding(v5e[0])
    q = jax.ShapeDtypeStruct((1, 16384, 32, 128), dtype, sharding=one)
    kv = jax.ShapeDtypeStruct((1, 16384, 2, 128), dtype, sharding=one)
    sc = S.SparseConfig()

    def attend(q, k, v):
        sel = S.select_blocks(q, k, sc, interpret=False)
        return S.sparse_attention(q, k, v, sel, interpret=False)

    fn = attend
    if grad:
        def fn(q, k, v):
            return jax.grad(
                lambda *a: attend(*a).astype(jnp.float32).sum(), argnums=(0, 1, 2)
            )(q, k, v)

    lowered = jax.jit(fn).lower(q, kv, kv)
    want = {"ddl_sparse_select", "ddl_flash_sparse_fwd"}
    if grad:
        want |= {"ddl_flash_sparse_bwd_dq", "ddl_flash_sparse_bwd_dkv"}
    assert kernel_names(lowered.compile().as_text()) == want
    assert want <= set(KERNEL_NAMES)
    grids = {(1, 2, 128): 1, (1, 2, 128, 128): 3 if grad else 1}
    assert mosaic_grids(lowered.as_text()) == grids


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd_bwd"])
def test_windowed_flash_attention_compiles_at_trinity_minis_geometry(v5e, grad):
    """2 x 8192 tokens, 32 query / 4 kv heads x 128, window 2048, bf16:
    the band's kernels under names of their own, beside the causal-full
    ones of the model's full_attention layer."""
    one = SingleDeviceSharding(v5e[0])
    q = jax.ShapeDtypeStruct((2, 8192, 32, 128), jnp.bfloat16, sharding=one)
    kv = jax.ShapeDtypeStruct((2, 8192, 4, 128), jnp.bfloat16, sharding=one)

    def both(q, k, v):
        banded = flash_attention(q, k, v, kv_repeat=8, interpret=False, window=2048)
        full = flash_attention(q, k, v, kv_repeat=8, interpret=False)
        return (banded + full).astype(jnp.float32).sum()

    fn = jax.grad(both, argnums=(0, 1, 2)) if grad else both
    lowered = jax.jit(fn).lower(q, kv, kv)
    text = lowered.compile().as_text()
    want = {"ddl_flash_fwd", "ddl_flash_swa_fwd"}
    if grad:
        want |= {"ddl_flash_bwd_dkv", "ddl_flash_swa_bwd_dkv"}
    assert kernel_names(text) == want and want <= set(KERNEL_NAMES)
    # The banded kernels' inner grid axis runs over the band's blocks
    # (5 of 16 at the windowed default of 512 x 512), the causal-full
    # ones' over the whole row; a forward and ONE backward kernel each.
    band = importlib.import_module(
        "ddl_tpu.ops.flash_attention").band_grid(8192, 2048, 512, 512)
    assert (band.nqb, band.nk, band.nq, band.steps, band.live) == (16, 5, 5, 80, 70)
    n = 2 if grad else 1
    assert mosaic_grids(lowered.as_text()) == {
        (2, 32, 16, 5): n, (2, 32, 8, 8): n,
    }


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd_bwd"])
def test_latent_flash_attention_compiles_at_kanana_2s_geometry(v5e, grad):
    """2 x 8192 tokens, 32 heads, 128-wide q/k/v + a 64-wide rotary
    product with one shared key, bf16: the latent kernels under names of
    their own, at the row's 1024 x 1024 blocks - which need a scoped VMEM
    limit past Mosaic's default 16 MiB (the forward's the latent path's own,
    the one backward kernel's ``_BWD_VMEM_LIMIT``) - on the causal grid; the
    rotary key's gradient comes back as ONE key a position."""
    one = SingleDeviceSharding(v5e[0])
    shape = lambda h, d: jax.ShapeDtypeStruct(  # noqa: E731
        (2, 8192, h, d), jnp.bfloat16, sharding=one)
    args = (shape(32, 128),) * 3 + (shape(32, 64), shape(1, 64))

    def latent(q, k, v, q_rope, k_rope):
        return flash_attention(
            q, k, v, interpret=False, q_rope=q_rope, k_rope=k_rope
        ).astype(jnp.float32).sum()

    fn = jax.grad(latent, argnums=(0, 1, 2, 3, 4)) if grad else latent
    lowered = jax.jit(fn).lower(*args)
    compiled = lowered.compile()
    want = {"ddl_flash_mla_fwd"}
    if grad:
        want |= {"ddl_flash_mla_bwd_dkv"}
        assert [o.shape for o in jax.tree.leaves(compiled.out_info)] == [
            a.shape for a in args]
    assert kernel_names(compiled.as_text()) == want and want <= set(KERNEL_NAMES)
    assert mosaic_grids(lowered.as_text()) == {(2, 32, 8, 8): 2 if grad else 1}


#: The one-kernel backward at the benchmark's cells' shapes: (B, T, query /
#: key heads, D, window, rotary width), the grid of its one kernel, and the
#: least ``vmem_limit_bytes`` in MiB under which Mosaic compiles it for the
#: described v5e (bisected, PR 45): the kernel's VMEM, read to a MiB.
BACKWARD_AT_THE_CELLS = {
    "trinity_mini_full": ((2, 8192, 32, 4, 128, None, None), (2, 32, 8, 8), 17),
    "trinity_mini_band": ((2, 8192, 32, 4, 128, 2048, None), (2, 32, 16, 5), 9),
    "kanana_2_latent": ((2, 8192, 32, 32, 128, None, 64), (2, 32, 8, 8), 25),
    "olmo_hybrid_16k": ((1, 16384, 30, 30, 128, None, None), (1, 30, 16, 16), 22),
    "lfm2_width_64": ((2, 8192, 32, 8, 64, None, None), (2, 32, 8, 8), 17),
}


@pytest.mark.parametrize("cell", list(BACKWARD_AT_THE_CELLS))
def test_the_one_kernel_backward_compiles_within_its_vmem_at_the_cells_shapes(
        v5e, monkeypatch, cell):
    """The backward pass of one blockwise call alone, bf16, at the row's
    default blocks: ONE Mosaic kernel under the family's ``*_bwd_dkv`` name
    on the dK/dV grid, whose dQ rows (4 MiB of float32 a (batch, head) at
    8,192 x 128, as much again for the latent form's lane-padded rotary
    rows, 8 MiB at 16,384) fit the VMEM the kernel asks for with room: it
    compiles under the pinned least limit, is refused a MiB below it for
    VMEM and nothing else, and that reading is under the limit the kernel
    sets itself by at least the half it leaves to a longer row."""
    blockwise = importlib.import_module("ddl_tpu.ops.flash_attention")
    (B, T, H, Hkv, D, window, R), grid, least_mib = BACKWARD_AT_THE_CELLS[cell]
    one = SingleDeviceSharding(v5e[0])
    bf16 = lambda *s: jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=one)  # noqa: E731
    row = jax.ShapeDtypeStruct((B, H, T), jnp.float32, sharding=one)
    q, kv = bf16(B, T, H, D), bf16(B, T, Hkv, D)
    rope = (bf16(B, T, H, R), bf16(B, T, 1, R)) if R else ()
    bq, bk = blockwise._default_blocks(T, None, None, window)

    def backward(q, k, v, out, lse, do, dlse, *rope):
        res = (q, k, v, blockwise._offsets_arr(0, 0), out, lse, False, bq, bk,
               None, None)
        return blockwise._bwd_impl(
            True, H // Hkv, None, None, None, res, (do, dlse), window=window,
            rope=rope or None)[:-1]  # but the offsets' float0

    def lower():  # anew each time: the limit is read at trace time
        return jax.jit(lambda *a: backward(*a)).lower(
            q, kv, kv, q, row, q, row, *rope)

    lowered = lower()
    family = "ddl_flash_" + ("mla_" if R else "swa_" if window else "")
    assert kernel_names(lowered.compile().as_text()) == {family + "bwd_dkv"}
    assert mosaic_grids(lowered.as_text()) == {grid: 1}
    assert blockwise._dq_row_bytes(T, *((D, R) if R else (D,))) \
        <= blockwise._BWD_ROW_BYTES
    assert 2 * least_mib * 2**20 <= blockwise._BWD_VMEM_LIMIT
    monkeypatch.setattr(blockwise, "_BWD_VMEM_LIMIT", least_mib * 2**20)
    lower().compile()
    monkeypatch.setattr(blockwise, "_BWD_VMEM_LIMIT", (least_mib - 1) * 2**20)
    with pytest.raises(Exception, match="vmem"):
        lower().compile()


def test_flash_names_survive_remat_and_shard_map(v5e):
    """What used to rename the kernels: ``jax.checkpoint`` (``checkpoint``,
    ``rematted_computation``), autodiff (``jvp__``, ``transpose_jvp___``)
    and the dp mesh's ``shard_map`` — the benchmark's three cells."""
    from jax import shard_map
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import numpy as np

    mesh = Mesh(np.array(v5e), ("dp",))
    spec = P("dp", None, None, None)

    def attn(q, k, v):
        return flash_attention(
            q, k, v, causal=False, kv_repeat=H // HKV, interpret=False
        )

    def loss(q, k, v):
        local = shard_map(
            jax.checkpoint(
                attn, policy=jax.checkpoint_policies.nothing_saveable
            ),
            mesh=mesh, in_specs=(spec,) * 3,
            out_specs=spec, check_vma=False,
        )
        return local(q, k, v).astype(jnp.float32).sum()

    sh = NamedSharding(mesh, spec)
    q = jax.ShapeDtypeStruct((B, T, H, D), jnp.bfloat16, sharding=sh)
    kv = jax.ShapeDtypeStruct((B, T, HKV, D), jnp.bfloat16, sharding=sh)
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv
    ).compile().as_text()
    assert kernel_names(text) == BLOCK_KERNELS


# ViT-B/16's attention at the benchmark's batch: 128 rows x 196 patches,
# 12 heads x 64, bf16, non-causal -- a sequence that fits one block
# (``ops/flash_tile.py``).
VB, VT, VH, VD = 128, 196, 12, 64
TILE_KERNELS = {"ddl_flash_tile_fwd", "ddl_flash_tile_bwd"}


def _value_and_grads(attn):
    # The value keeps the forward kernel in: the one-block backward reads
    # nothing of it.
    return jax.value_and_grad(
        lambda q, k, v: attn(q, k, v).astype(jnp.float32).sum(),
        argnums=(0, 1, 2),
    )


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd_bwd"])
def test_one_block_attention_compiles_at_vits_geometry(v5e, grad):
    """64-lane head slices of a 196-row block, off every tile: Mosaic
    takes both directions, under the names the trace reduction reads, and
    the program needs less device memory than the blockwise kernels at
    the same shape (no transposed, padded copies; no ``lse``)."""
    # The module: ``ddl_tpu.ops.flash_attention`` the attribute is the function.
    blockwise = importlib.import_module("ddl_tpu.ops.flash_attention")

    def tile(q, k, v):
        return flash_attention(q, k, v, causal=False, interpret=False)

    def block(q, k, v):
        return blockwise._flash_core(
            q, k, v, blockwise._offsets_arr(0, 0), False, 1, 512, 1024, False
        )[0]

    x = jax.ShapeDtypeStruct(
        (VB, VT, VH, VD), jnp.bfloat16, sharding=SingleDeviceSharding(v5e[0])
    )
    compiled = {
        name: jax.jit(_value_and_grads(fn) if grad else fn)
        .lower(x, x, x).compile()
        for name, fn in (("tile", tile), ("block", block))
    }
    assert kernel_names(compiled["tile"].as_text()) == (
        TILE_KERNELS if grad else {"ddl_flash_tile_fwd"}
    )
    assert kernel_names(compiled["block"].as_text()) == (
        BLOCK_KERNELS if grad else {"ddl_flash_fwd"}
    )
    temp = {
        name: c.memory_analysis().temp_size_in_bytes
        for name, c in compiled.items()
    }
    assert temp["tile"] < temp["block"], temp


def test_one_block_attention_compiles_under_vits_dp_mesh(v5e):
    """``vit-b16.images-224-dp4``: 128 rows a chip inside
    ``sharded_local_attention``'s shard_map, forward and backward."""
    from unittest import mock

    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import numpy as np

    from ddl_tpu.parallel.ring_attention import sharded_local_attention

    mesh = Mesh(np.array(v5e), ("dp",))
    sh = NamedSharding(mesh, P("dp", None, None, None))
    x = jax.ShapeDtypeStruct(
        (VB * len(v5e), VT, VH, VD), jnp.bfloat16, sharding=sh
    )
    # The kernels themselves, not the interpreter this process's CPU
    # backend would ask for.
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        text = jax.jit(_value_and_grads(
            lambda q, k, v: sharded_local_attention(
                q, k, v, mesh, causal=False, use_flash=True)
        )).lower(x, x, x).compile().as_text()
    assert kernel_names(text) == TILE_KERNELS
    assert " all-gather(" not in text and " all-to-all(" not in text


@pytest.mark.parametrize("shape,dtype,want", [
    # What ``flash_tile.fits`` takes beyond ViT's geometry: three 64-lane
    # heads (one static group off the 128 lanes), an odd number of groups,
    # 128- and 256-lane heads at the longest T, float32, a single token.
    ((2, 24, 3, 64), jnp.bfloat16, TILE_KERNELS),
    ((2, 197, 20, 32), jnp.bfloat16, TILE_KERNELS),
    ((2, 512, 16, 128), jnp.bfloat16, TILE_KERNELS),
    ((2, 512, 4, 256), jnp.bfloat16, TILE_KERNELS),
    ((2, 512, 16, 128), jnp.float32, TILE_KERNELS),
    ((2, 196, 12, 64), jnp.float32, TILE_KERNELS),
    ((2, 1, 2, 32), jnp.bfloat16, TILE_KERNELS),
    # Heads that neither divide 128 lanes nor are a multiple of them
    # (ViT-H/14's 80): Mosaic cannot prove their lane offsets aligned, so
    # the rule leaves them to the blockwise kernels, as before.
    ((8, 257, 16, 80), jnp.bfloat16, BLOCK_KERNELS),
    ((8, 196, 16, 96), jnp.bfloat16, BLOCK_KERNELS),
    ((8, 128, 4, 192), jnp.bfloat16, BLOCK_KERNELS),
], ids=lambda x: "x".join(map(str, x)) if isinstance(x, tuple) else None)
@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
def test_short_sequences_compile_on_the_path_the_rule_gives(
        v5e, shape, dtype, want, causal):
    """Forward and backward of ``flash_attention`` at one-block shapes:
    whichever kernels the rule picks, Mosaic takes them."""
    x = jax.ShapeDtypeStruct(
        shape, dtype, sharding=SingleDeviceSharding(v5e[0])
    )
    text = jax.jit(_value_and_grads(
        lambda q, k, v: flash_attention(q, k, v, causal=causal,
                                        interpret=False)
    )).lower(x, x, x).compile().as_text()
    assert kernel_names(text) == want


@pytest.mark.parametrize("remat", ["none", "selective", "full", "dots"])
def test_grouped_matmuls_are_xlas_own_kernels_under_a_stable_name(v5e, remat):
    """``jax.lax.ragged_dot`` and both of its transposes compile to XLA's
    own Mosaic kernels on a v5e, instructions ``ragged-dot-none.<n>``
    (+ a small ``ragged-dot-metadata.<n>``): the op family the benchmark's
    ``gmm_device_share`` / ``gmm_roofline_share`` read.  A layer and step
    makes 9 such calls, 12 where the remat policy re-runs the forward:
    the count ``benchmarks/lib/moe_flops.py`` multiplies FLOPs by."""
    from benchmarks.lib import moe_flops
    from ddl_tpu.models import moe

    cfg = moe.MoeConfig(
        vocab=512, d_model=256, n_layers=1, n_heads=2, n_kv_heads=2,
        d_ff=256, n_experts=8, topk=2, max_seq=256,
        param_dtype=jnp.bfloat16, attn_impl="dense", qk_norm=True,
        norm_topk_prob=False, router_aux_all_slots=True,
        router_z_weight=0.001, remat=remat,
    )
    one = SingleDeviceSharding(v5e[0])
    params = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one),
        moe.param_shapes(cfg),
    )
    tokens = jax.ShapeDtypeStruct((2, 256), jnp.int32, sharding=one)
    text = jax.jit(jax.value_and_grad(
        lambda p, t: moe.next_token_loss(p, t, cfg)
    )).lower(params, tokens).compile().as_text()
    calls = re.findall(r"%(ragged-dot-[a-z]+)(?:\.\d+)* = \S+ custom-call\(", text)
    assert calls.count("ragged-dot-none") == moe_flops.GMM_CALLS_PER_LAYER[remat]
    assert set(calls) <= {"ragged-dot-none", "ragged-dot-metadata"}
    assert "ragged-dot-none" in kernel_names(text)


# The 64 MiB float32 stream window (65536 x 256) over the four chips.
ROWS, COLS = 65536, 256


def test_broadcast_kernel_compiles(v5e):
    compiled = ici_fanout._bcast_call(
        v5e, ROWS, COLS, "float32", 0, ici_fanout.DEFAULT_CHUNKS, False
    )
    assert kernel_names(compiled.as_text()) == {"ddl_ici_bcast"}


@pytest.mark.parametrize("slot", range(ici_fanout.N_SLOTS))
def test_scatter_kernel_compiles(v5e, slot):
    compiled = ici_fanout._scatter_call(
        v5e, ROWS, COLS, "float32", 0, False, slot
    )
    assert kernel_names(compiled.as_text()) == {"ddl_ici_scatter"}
    # No fast-memory transit: the only device memory beyond the SPMD
    # input block is this device's output block.
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes == 0
    assert mem.output_size_in_bytes == ROWS // len(v5e) * COLS * 4


def test_exchange_kernel_compiles(v5e):
    # 64 MiB per instance: two lanes of half the rows each.
    compiled = device_shuffle._exchange_call(
        v5e, ROWS // 2, COLS, "float32", False
    )
    assert kernel_names(compiled.as_text()) == {"ddl_shuffle_exchange"}


@pytest.mark.parametrize("kernel,block,dtype", [
    # A dp-sharded token window of the smoke's Trainer: ONE row of
    # 2 steps x 2048 int32 tokens a device.
    ("scatter", (1, 4096), "int32"),
    ("scatter", (16, 520), "uint8"),  # an int8-wire window: rows + scales
    ("bcast", (10, 256), "float32"),  # rows off the 8-sublane tile
    ("exchange", (3, 3), "float32"),  # a toy shuffle pool's lane
])
def test_blocks_off_the_tiling_compile_through_the_lane_view(
    v5e, kernel, block, dtype
):
    """Mosaic refuses to slice these shapes out of HBM; the wrappers
    move them as (R, 128) lane views, which it takes."""
    n = len(v5e)
    assert not ici_fanout.tile_aligned(*block, dtype)
    if kernel == "scatter":
        rows, cols = ici_fanout.kernel_view(n, *block, dtype)
        compiled = ici_fanout._scatter_call(
            v5e, rows, cols, dtype, 0, False
        )
    elif kernel == "bcast":
        rows, cols = ici_fanout.kernel_view(1, *block, dtype)
        compiled = ici_fanout._bcast_call(
            v5e, rows, cols, dtype, 0, ici_fanout.DEFAULT_CHUNKS, False
        )
    else:
        rows, cols = ici_fanout.kernel_view(2, *block, dtype)
        compiled = device_shuffle._exchange_call(
            v5e, rows // 2, cols, dtype, False
        )
    assert cols == ici_fanout.LANES
    assert kernel_names(compiled.as_text()) <= set(KERNEL_NAMES)


def test_compiler_refusal_is_a_build_error(v5e):
    """What the TPU compiler refuses (here: 32 GiB on a 16 GB chip)
    surfaces as KernelBuildError from the builder — the type the
    runtime fault ladders do not absorb."""
    from ddl_tpu.exceptions import KernelBuildError

    too_big = jax.ShapeDtypeStruct(
        (1 << 33,), jnp.float32, sharding=SingleDeviceSharding(v5e[0])
    )
    with pytest.raises(KernelBuildError, match="failed to compile"):
        ici_fanout.compile_kernel(jax.jit(lambda x: x * 2), too_big)


def test_a_share_compiles_under_its_remat_plan_and_stays_under_its_budget(
        v5e, monkeypatch):
    """Trinity-Mini's widths at a cut depth (one dense layer, one expert
    layer of a 16-of-128 share) and an eighth of a step's tokens:
    ``selective`` given room for the router's results, what the sandwich
    norms read, the projections and the first SwiGLU plans exactly those,
    the step compiles for the chip with the kernels it always had, and
    XLA's own count of its temporaries stays under what the plan was told
    is free (``models/remat.py``: the reckoning errs high)."""
    from ddl_tpu.models import afmoe, remat
    from ddl_tpu.observability import metrics

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    T = 2048
    cfg = afmoe.AfmoeConfig(
        vocab=8192, d_model=2048, n_heads=32, n_kv_heads=4, head_dim=128,
        d_ff=6144, d_expert=1024, n_experts=128, topk=8, n_shared_experts=1,
        layer_types=(afmoe.SLIDING, afmoe.FULL), n_dense_layers=1,
        sliding_window=1024, route_scale=2.826, held_experts=(0, 16),
        max_seq=T, param_dtype=jnp.bfloat16, attn_impl="flash",
        remat="selective",
    )
    one = SingleDeviceSharding(v5e[0])
    params = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one),
        jax.eval_shape(lambda: afmoe.init_params(cfg, jax.random.key(0))),
    )
    tokens = jax.ShapeDtypeStruct((1, T), jnp.int32, sharding=one)
    loss = lambda p, t: afmoe.next_token_loss(p, t, cfg)

    counted = []
    real = remat.plan
    monkeypatch.setattr(
        remat, "plan", lambda layers, free: counted.extend(layers) or real(layers, free))
    with remat.free_hbm(1 << 40):  # count the layers; everything fits
        jax.make_jaxpr(jax.value_and_grad(loss))(params, tokens)
    dense, routed = counted
    wanted = (routed.by_name[remat.ROUTER]
              + dense.by_name[remat.NORMED] + routed.by_name[remat.NORMED]
              + dense.by_name[remat.PROJ] + routed.by_name[remat.PROJ]
              + dense.by_name[remat.SWIGLU])
    free = remat.reserved(counted, 4 * T * cfg.vocab) + wanted

    def step(p, t):
        with remat.free_hbm(free):
            return jax.value_and_grad(loss)(p, t)

    compiled = jax.jit(step).lower(params, tokens).compile()
    assert metrics().gauge("remat.extra_bytes") == wanted
    assert metrics().gauge("remat.extra_layers") == 2
    assert metrics().gauge("remat.budget_bytes") == wanted
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes <= free, (mem.temp_size_in_bytes, free)
    assert kernel_names(compiled.as_text()) == (
        BLOCK_KERNELS | {k.replace("flash", "flash_swa") for k in BLOCK_KERNELS}
        | {"ragged-dot-none", "ragged-dot-metadata"})


def test_a_hyper_connected_wrap_compiles_without_a_float32_stream(v5e):
    """One wrap, forward and backward, at the Xing4.0 cell's shape (1 x 4 x
    8,192 x 3,584, bfloat16) for a described v5e: every pass over the stream is
    one of the four ``ddl_hc_*`` kernels (``ops/hyper_connections.py``), NO
    float32 array of the stream's or of a row's size is written, the stream's
    two cotangents meet inside ``ddl_hc_pre_bwd`` (handed through as a layer
    hands it: no ``add_any`` over a stream), and the whole program moves, by
    XLA's own count, under 1.3 x what one read a pass has to (835 KB a token
    with XLA's fusions in the kernels' place, PR 49's tree)."""
    from unittest import mock

    from ddl_tpu.models import hyper_connections as hc

    sharding = SingleDeviceSharding(v5e[0])
    n, T, C = 4, 8192, 3584
    settings = hc.HyperConnections()
    spec = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    wrap = {row.name.split(".")[1]: spec(row.shape, row.dtype)
            for row in hc.wrap_rows("w", n, C)}

    def step(X, y, wrap):
        def loss(X, y, wrap):
            h, post, res, X = hc.hc_pre(X, wrap, settings)
            out = hc.hc_post(X, (y * h).astype(y.dtype), post, res)
            return jnp.sum(out.astype(jnp.float32) ** 2)

        return jax.value_and_grad(loss, argnums=(0, 1, 2))(X, y, wrap)

    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        compiled = jax.jit(step).lower(
            spec((1, n, T, C), jnp.bfloat16), spec((1, T, C), jnp.bfloat16), wrap
        ).compile()
    text = compiled.as_text()
    entry = text[text.index("ENTRY"):]
    wide = [ln.strip()[:160] for ln in entry.splitlines()
            if re.search(r"= f32\[[^\]]*8192,3584\]", ln)
            or ("add_any" in ln and "8192,3584" in ln)]
    assert not wide, wide
    assert kernel_names(text) == {
        "ddl_hc_pre_fwd", "ddl_hc_pre_bwd", "ddl_hc_post_fwd", "ddl_hc_post_bwd"}
    assert re.search(r"f32\[1,24,8192\]", entry)  # the projections, token-last
    # One read a pass, a token: the four kernels' operands and results (35.8 +
    # 64.5 + 64.5 + 100.4 KB), the stream's second cotangent into
    # ``ddl_hc_pre_bwd`` (a stream), the rounds on the small arrays forward and
    # backward (16.7 each way), and the test's own loss: ``y * h``, its
    # backward and the sum of squares with its cotangent (three rows, two rows
    # and y's cotangent, two streams).
    row = 2 * C
    one_read = 265.2e3 + n * row + 2 * 16.7e3 + (3 + 3) * row + 2 * n * row
    moved = compiled.cost_analysis()["bytes accessed"] / T
    assert moved < 1.3 * one_read, (moved, one_read)
    # the stream in and its cotangent out, a row each way, and under half a
    # GiB of temporaries beside them
    assert compiled.memory_analysis().temp_size_in_bytes < 2**29
