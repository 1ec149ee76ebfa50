"""Pallas kernel correctness vs the dense attention oracle.

Runs in interpret mode on the CPU test backend (conftest); on a real TPU
the same code path compiles via Mosaic.
"""

import collections
import hashlib
import importlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddl_tpu.ops import flash_attention
from ddl_tpu.parallel.ring_attention import attention_reference


def _qkv(rng, B=2, T=128, H=4, Hkv=None, D=32, dtype=jnp.float32):
    Hkv = Hkv or H
    q = jnp.asarray(rng.standard_normal((B, T, H, D)), dtype)
    k = jnp.asarray(rng.standard_normal((B, T, Hkv, D)), dtype)
    v = jnp.asarray(rng.standard_normal((B, T, Hkv, D)), dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [True, False])
def test_flash_matches_dense(rng, causal):
    q, k, v = _qkv(rng, T=128)
    out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64)
    ref = attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_flash_gqa(rng):
    q, k, v = _qkv(rng, H=4, Hkv=2, T=64)
    out = flash_attention(q, k, v, kv_repeat=2, block_q=32, block_k=32)
    ref = attention_reference(q, k, v, kv_repeat=2)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def _segments(rng, B, T, max_docs=4):
    """Random packed-document layout: sorted segment ids per row."""
    ids = np.zeros((B, T), np.int32)
    for b in range(B):
        cuts = np.sort(rng.choice(np.arange(1, T), size=max_docs - 1,
                                  replace=False))
        ids[b] = np.searchsorted(cuts, np.arange(T), side="right")
    return jnp.asarray(ids)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_segment_ids_matches_dense(rng, causal):
    """Packed-sequence masking: tokens attend only within their own
    document; causality applies on top."""
    q, k, v = _qkv(rng, T=128)
    seg = _segments(rng, 2, 128)
    out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64,
                          segment_ids=seg)
    ref = attention_reference(q, k, v, causal=causal, segment_ids=seg)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_flash_segment_ids_gqa_ragged(rng):
    """Segments compose with GQA and non-block-multiple lengths."""
    q, k, v = _qkv(rng, T=100, H=4, Hkv=2)
    seg = _segments(rng, 2, 100, max_docs=3)
    out = flash_attention(q, k, v, kv_repeat=2, block_q=32, block_k=32,
                          segment_ids=seg)
    ref = attention_reference(q, k, v, kv_repeat=2, segment_ids=seg)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_flash_segment_ids_grads_match_dense(rng):
    q, k, v = _qkv(rng, T=96)
    seg = _segments(rng, 2, 96, max_docs=3)

    def loss_flash(q, k, v):
        return jnp.sum(
            flash_attention(q, k, v, block_q=32, block_k=32,
                            segment_ids=seg) ** 2
        )

    def loss_ref(q, k, v):
        return jnp.sum(attention_reference(q, k, v, segment_ids=seg) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(a, b, atol=5e-4, rtol=5e-4)


def test_flash_segment_isolation(rng):
    """Perturbing document 0's keys must not change document 1's outputs
    at all — exact isolation, not just tolerance-level agreement."""
    B, T = 1, 64
    q, k, v = _qkv(rng, B=B, T=T)
    seg = jnp.asarray(
        np.concatenate([np.zeros(32, np.int32), np.ones(32, np.int32)])
    )[None]
    out1 = flash_attention(q, k, v, block_q=32, block_k=32,
                           segment_ids=seg)
    k2 = k.at[:, :32].add(1.0)  # perturb doc 0 keys only
    v2 = v.at[:, :32].add(-1.0)
    out2 = flash_attention(q, k2, v2, block_q=32, block_k=32,
                           segment_ids=seg)
    np.testing.assert_array_equal(
        np.asarray(out1[:, 32:]), np.asarray(out2[:, 32:])
    )
    assert not np.allclose(np.asarray(out1[:, :32]), np.asarray(out2[:, :32]))


def test_flash_ragged_seq_len(rng):
    # T not a multiple of the block: padded keys must not leak into rows.
    q, k, v = _qkv(rng, T=100)
    out = flash_attention(q, k, v, block_q=64, block_k=64)
    ref = attention_reference(q, k, v)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_grads_match_dense(rng, causal):
    """custom_vjp backward kernels == autodiff through the dense oracle."""
    q, k, v = _qkv(rng, B=1, T=96, H=2, Hkv=1, D=32)

    def loss_flash(q, k, v):
        out = flash_attention(q, k, v, causal, 2, 32, 32)
        return jnp.sum(jnp.sin(out))

    def loss_dense(q, k, v):
        out = attention_reference(q, k, v, causal=causal, kv_repeat=2)
        return jnp.sum(jnp.sin(out))

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gd, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-5, rtol=5e-5,
            err_msg=f"d{name}",
        )


def test_flash_grads_ragged(rng):
    """Backward with padding: padded rows/keys contribute zero gradient."""
    q, k, v = _qkv(rng, B=1, T=50, H=2, D=16)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) ** 2)

    gf = jax.grad(loss(lambda q, k, v: flash_attention(q, k, v, True, 1, 32, 32)),
                  argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss(lambda q, k, v: attention_reference(q, k, v)),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=5e-5)


def test_sharded_local_attention_dp_tp(rng):
    """Flash under shard_map on a dp×tp mesh == dense, no seq axis."""
    from ddl_tpu.parallel.mesh import make_mesh
    from ddl_tpu.parallel.ring_attention import sharded_local_attention

    mesh = make_mesh({"dp": 4, "tp": 2})
    q, k, v = _qkv(rng, B=4, T=64, H=4, Hkv=2, D=32)
    out = sharded_local_attention(q, k, v, mesh, kv_repeat=2, use_flash=True)
    ref = attention_reference(q, k, v, kv_repeat=2)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_sharded_local_attention_indivisible_axes(rng):
    """Axes that don't divide B/H stay unsharded rather than erroring."""
    from ddl_tpu.parallel.mesh import make_mesh
    from ddl_tpu.parallel.ring_attention import sharded_local_attention

    mesh = make_mesh({"dp": 8})
    q, k, v = _qkv(rng, B=3, T=32, H=2, D=16)  # B=3 not divisible by dp=8
    out = sharded_local_attention(q, k, v, mesh, use_flash=True)
    ref = attention_reference(q, k, v)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_flash_bf16_and_jit(rng):
    q, k, v = _qkv(rng, T=64, dtype=jnp.bfloat16)
    fn = jax.jit(lambda q, k, v: flash_attention(q, k, v, block_q=32,
                                                 block_k=32))
    out = fn(q, k, v)
    assert out.dtype == jnp.bfloat16
    ref = attention_reference(q, k, v)
    np.testing.assert_allclose(
        out.astype(np.float32), ref.astype(np.float32), atol=3e-2, rtol=3e-2
    )


class TestRingFlash:
    """Ring attention with the Pallas kernel per ring step (interpret)."""

    def _mesh(self):
        from ddl_tpu.parallel.mesh import make_mesh

        return make_mesh({"dp": 2, "sp": 4})

    def test_ring_flash_matches_dense(self, rng):
        from ddl_tpu.parallel.ring_attention import ring_attention

        q, k, v = _qkv(rng, B=2, T=64, H=2, Hkv=1, D=16)
        out = ring_attention(q, k, v, self._mesh(), kv_repeat=2,
                             use_flash=True)
        ref = attention_reference(q, k, v, kv_repeat=2)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_ring_flash_non_causal(self, rng):
        from ddl_tpu.parallel.ring_attention import ring_attention

        q, k, v = _qkv(rng, B=2, T=32, H=2, D=16)
        out = ring_attention(q, k, v, self._mesh(), causal=False,
                             use_flash=True)
        ref = attention_reference(q, k, v, causal=False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_ring_flash_grads_match_dense(self, rng):
        """Grads flow through kernel + lse-combine + ppermute schedule."""
        from ddl_tpu.parallel.ring_attention import ring_attention

        mesh = self._mesh()
        q, k, v = _qkv(rng, B=2, T=32, H=2, D=16)

        def loss(fn):
            return lambda q, k, v: jnp.sum(jnp.sin(fn(q, k, v)))

        gf = jax.grad(
            loss(lambda q, k, v: ring_attention(q, k, v, mesh,
                                                use_flash=True)),
            argnums=(0, 1, 2),
        )(q, k, v)
        gd = jax.grad(
            loss(lambda q, k, v: attention_reference(q, k, v)),
            argnums=(0, 1, 2),
        )(q, k, v)
        for a, b, name in zip(gf, gd, "qkv"):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=5e-5, rtol=5e-5,
                err_msg=f"d{name}",
            )

    def test_lse_variant_and_offsets(self, rng):
        """Offset-based masking == slicing the global computation."""
        from ddl_tpu.ops import flash_attention_with_lse

        q, k, v = _qkv(rng, B=1, T=64, H=2, D=16)
        # Queries are the SECOND half of a 128-token sequence whose keys
        # are `k`: global causal mask via offsets.
        out, lse = flash_attention_with_lse(
            q, k, v, q_offset=64, k_offset=0, block_q=32, block_k=32
        )
        # Every key position (0..63) is <= every query position (64..127),
        # so this equals non-causal attention.
        ref = attention_reference(q, k, v, causal=False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)
        assert lse.shape == (1, 2, 64)
        # Fully-masked case: queries BEFORE all keys under causal.
        out2, lse2 = flash_attention_with_lse(
            q, k, v, q_offset=0, k_offset=64, block_q=32, block_k=32
        )
        assert float(np.abs(np.asarray(out2)).max()) == 0.0
        assert bool(np.all(np.asarray(lse2) < -1e29))


# -- a sliding window in the blockwise kernels -----------------------------------


@pytest.mark.parametrize("gqa", [1, 2, 8], ids=["mha", "gqa", "gqa8"])
@pytest.mark.parametrize("T,window,block_q,block_k", [
    (512, 200, 128, 128),  # the band's edge cuts inside a 128-block
    (512, 256, 128, 128),  # ... lies on a block edge: two blocks exactly
    (512, 128, 128, 128),  # ... one block exactly
    (512, 50, 128, 128),  # ... is narrower than one block
    (512, 1000, 128, 128),  # ... is wider than the row: plain causal
    (400, 150, 128, 128),  # the row is off the block size
    (384, 1, 128, 128),  # every query sees itself alone
    (512, 200, 64, 128),  # query blocks narrower than key blocks
    (512, 200, 128, 64),  # ... and wider
    (400, 150, 64, 128),  # off the block size: 448 queries, 512 keys
    (400, 150, 128, 64),  # ... 512 queries, 448 keys
    (330, 129, 128, 64),  # the window one past a block edge, 46 pad rows
], ids=lambda v: str(v))
def test_windowed_flash_matches_dense_forward_and_gradients(
        rng, T, window, block_q, block_k, gqa):
    H = max(2, gqa)
    q, k, v = _qkv(rng, B=1, T=T, H=H, Hkv=H // gqa, D=32)
    do = jnp.asarray(rng.standard_normal(q.shape), jnp.float32)
    kw = dict(kv_repeat=gqa, window=window)

    def flash(q, k, v):
        return flash_attention(q, k, v, block_q=block_q, block_k=block_k, **kw)

    def dense(q, k, v):
        return attention_reference(q, k, v, **kw)

    np.testing.assert_allclose(flash(q, k, v), dense(q, k, v), atol=2e-5, rtol=2e-5)
    got = jax.grad(lambda *a: jnp.sum(flash(*a) * do), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(dense(*a) * do), argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=5e-5, rtol=5e-5)


def _blocks_with_a_visible_pair(T, window, block_q, block_k):
    """(query blocks, key blocks) bool: brute force over the padded mask."""
    nqb, nkb = -(-T // block_q), -(-T // block_k)
    i, j = np.arange(T)[:, None], np.arange(T)[None, :]
    mask = np.zeros((nqb * block_q, nkb * block_k), bool)
    mask[:T, :T] = (j <= i) & (i - j < window)
    return mask.reshape(nqb, block_q, nkb, block_k).any(axis=(1, 3))


@pytest.mark.parametrize("T,window,block_q,block_k,steps,steps_dkv,live", [
    (8192, 2048, 1024, 1024, 24, 24, 21),  # Trinity-Mini's, at the old
    # blocks: 64 steps a head before the grid followed the band
    (8192, 2048, 512, 512, 80, 80, 70),  # ... at the windowed default
    (8192, 2048, 512, 1024, 48, 48, 42),
    (8192, 2048, 1024, 512, 48, 48, 42),
    (8192, 4096, 1024, 1024, 40, 40, 30),
    (4096, 2048, 512, 512, 40, 40, 30),
    (512, 200, 128, 128, 12, 12, 9),
    (512, 256, 128, 128, 12, 12, 9),  # one key past two blocks: three
    (512, 128, 128, 128, 8, 8, 7),
    (512, 50, 128, 128, 8, 8, 7),
    (384, 1, 128, 128, 3, 3, 3),
    (400, 150, 64, 128, 21, 20, 14),
    (400, 150, 128, 64, 20, 21, 15),
    (330, 129, 128, 64, 12, 12, 10),
    (512, 511, 128, 128, 16, 16, 10),  # all but one pair: the whole triangle
], ids=lambda v: str(v))
def test_the_band_grid_is_a_brute_force_count_of_blocks(
        T, window, block_q, block_k, steps, steps_dkv, live):
    """The static counter: executed steps a head and the live ones, and
    every live block visited exactly once by both grids' index maps."""
    from ddl_tpu.ops.flash_attention import (
        _band_k_block, _band_q_block, band_grid,
    )

    want = _blocks_with_a_visible_pair(T, window, block_q, block_k)
    g = band_grid(T, window, block_q, block_k)
    assert (g.nqb, g.nkb) == want.shape
    assert g.live == want.sum() == live
    assert g.nk == want.sum(axis=1).max() and g.nq == want.sum(axis=0).max()
    assert (g.steps, g.steps_dkv) == (steps, steps_dkv)
    assert max(steps, steps_dkv) <= g.nqb * g.nkb
    for i in range(g.nqb):  # fwd, dq: key blocks of query block i
        js = [int(_band_k_block(i, jj, block_q, block_k, g))
              for jj in range(g.nk)]
        assert js == list(range(js[0], js[0] + g.nk)) and js[-1] < g.nkb
        assert set(np.flatnonzero(want[i])) <= {j for j in js if j >= 0}
    for j in range(g.nkb):  # dkv: query blocks of key block j
        qs = [_band_q_block(j, ii, block_q, block_k) for ii in range(g.nq)]
        assert set(np.flatnonzero(want[:, j])) <= {i for i in qs if i < g.nqb}


@pytest.mark.parametrize("block_q,block_k", [(128, 128), (64, 128), (128, 64)],
                         ids=lambda v: str(v))
@pytest.mark.parametrize("pick", [0, 3, 4])  # the head, inside, the tail
def test_blocks_outside_the_band_are_never_attended(rng, pick, block_q, block_k):
    """NaN in every key/value block that holds no pair visible to query
    block ``pick`` leaves that block's output and dq as they were, and NaN
    in every query block that sees nothing of key block ``pick`` leaves its
    dk and dv as they were: whatever a clamped or a dead step fetches, it
    attends nothing."""
    T, window = 640, 200
    q, k, v = _qkv(rng, B=1, T=T, H=1, D=32)
    do = jnp.asarray(rng.standard_normal(q.shape), jnp.float32)
    live = _blocks_with_a_visible_pair(T, window, block_q, block_k)

    def run(q, k, v, do):
        out, vjp = jax.vjp(
            lambda *a: flash_attention(*a, window=window, block_q=block_q,
                                       block_k=block_k), q, k, v)
        return (out, *vjp(do))

    def poison(x, block, keep):
        dead = np.repeat(~keep, block)[:T]
        assert dead.any()
        return jnp.where(dead[None, :, None, None], jnp.nan, x)

    clean = run(q, k, v, do)
    rows = slice(pick * block_q, (pick + 1) * block_q)
    got = run(q, poison(k, block_k, live[pick]), poison(v, block_k, live[pick]), do)
    for a, b in zip(got[:2], clean[:2]):  # the output, dq
        np.testing.assert_array_equal(np.asarray(a)[:, rows], np.asarray(b)[:, rows])
    cols = slice(pick * block_k, (pick + 1) * block_k)
    got = run(poison(q, block_q, live[:, pick]), k, v,
              poison(do, block_q, live[:, pick]))
    for a, b in zip(got[2:], clean[2:]):  # dk, dv
        np.testing.assert_array_equal(np.asarray(a)[:, cols], np.asarray(b)[:, cols])


@pytest.mark.parametrize("T,window,given,want", [
    (8192, 2048, (None, None), (512, 512)),  # Trinity-Mini's sliding layers
    (8192, 1024, (None, None), (512, 512)),
    (8192, 4096, (None, None), (1024, 1024)),  # a wide band: the row's
    (8192, None, (None, None), (1024, 1024)),  # no window: as before
    (2048, None, (None, None), (512, 1024)),
    (8192, 2048, (1024, None), (1024, 512)),  # what is given is kept
    (8192, 2048, (256, 128), (256, 128)),
], ids=lambda v: str(v))
def test_the_windowed_default_blocks(T, window, given, want):
    from ddl_tpu.ops.flash_attention import _default_blocks

    assert _default_blocks(T, *given, window) == want


def test_the_window_is_a_band_and_not_the_whole_causal_triangle(rng):
    q, k, v = _qkv(rng, B=1, T=256, H=2, D=32)
    banded = attention_reference(q, k, v, window=64)
    assert float(jnp.max(jnp.abs(banded - attention_reference(q, k, v)))) > 0.05
    # A key older than the window does not reach the query.
    k2 = k.at[:, 0].add(100.0)
    v2 = v.at[:, 0].add(100.0)
    np.testing.assert_array_equal(
        np.asarray(flash_attention(q, k2, v2, window=64, block_q=64, block_k=64))[:, 64:],
        np.asarray(flash_attention(q, k, v, window=64, block_q=64, block_k=64))[:, 64:],
    )


def test_a_window_needs_causal_attention_and_refuses_packed_rows(rng):
    q, k, v = _qkv(rng, B=1, T=256, H=2, D=32)
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, causal=False, window=64)
    with pytest.raises(NotImplementedError, match="packed rows"):
        flash_attention(q, k, v, window=64, segment_ids=jnp.zeros((1, 256), jnp.int32))
    with pytest.raises(ValueError, match="self-attention"):  # the band is static
        flash_attention(q, k[:, :128], v[:, :128], window=64)


def test_the_one_block_kernels_refuse_a_window_narrower_than_the_row(rng):
    from ddl_tpu.ops import flash_tile

    q, k, v = _qkv(rng, B=2, T=196, H=12, D=64)
    assert flash_tile.fits(q, k, v, 1, 512, 1024, None)
    assert flash_tile.fits(q, k, v, 1, 512, 1024, None, window=196)
    assert not flash_tile.fits(q, k, v, 1, 512, 1024, None, window=195)


# -- a sequence that fits one block: ops/flash_tile.py --------------------------

#: (B, T, H, D): ViT-B/16's geometry, off every tile (196 rows, 64-deep
#: heads: six 128-lane groups, two an iteration); a tile multiple (four
#: heads in one group); a single token; an odd number of groups; an odd
#: number of 64-lane heads (one static group); 128-lane heads (a group
#: each).
TILE_SHAPES = [(2, 196, 12, 64), (2, 128, 4, 32), (3, 1, 2, 32),
               (1, 24, 6, 64), (1, 24, 3, 64), (1, 16, 2, 128)]


def _kernel_calls(fn, *args):
    """How often ``fn``'s program lowered for the TPU calls each Pallas
    kernel, by name."""
    import collections

    text = jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",)).as_text()
    return dict(collections.Counter(
        re.findall(r'kernel_name = "(ddl_flash_\w+)"', text)))


def _kernels(fn, *args):
    """The Pallas kernels' names in ``fn``'s program lowered for the TPU."""
    return set(_kernel_calls(fn, *args))


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("shape", TILE_SHAPES, ids=lambda s: "x".join(map(str, s)))
def test_tile_matches_dense(rng, shape, causal):
    from ddl_tpu.ops import flash_tile

    B, T, H, D = shape
    q, k, v = _qkv(rng, B=B, T=T, H=H, D=D)
    assert flash_tile.fits(q, k, v, 1, 512, 1024, None)
    out = flash_attention(q, k, v, causal=causal)
    ref = attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("shape", TILE_SHAPES, ids=lambda s: "x".join(map(str, s)))
def test_tile_grads_match_dense(rng, shape, causal):
    """The one backward kernel == autodiff through the dense oracle."""
    B, T, H, D = shape
    q, k, v = _qkv(rng, B=B, T=T, H=H, D=D)

    def loss(fn):
        return lambda q, k, v: jnp.sum(jnp.sin(fn(q, k, v, causal=causal)))

    gf = jax.grad(loss(flash_attention), argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss(attention_reference), argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gd, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-5, rtol=5e-5,
            err_msg=f"d{name}",
        )


@pytest.mark.parametrize("rows", [1, 2, 3, 4, 6], ids=lambda b: f"batch{b}")
def test_tile_one_batch_row_a_grid_step(rng, rows):
    """Every batch size: the grid's first axis is the batch."""
    q, k, v = _qkv(rng, B=rows, T=24, H=2, D=32)
    out = flash_attention(q, k, v, causal=False)
    ref = attention_reference(q, k, v, causal=False)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_tile_head_groups_when_all_heads_exceed_the_budget(rng, monkeypatch):
    """Heads split over the grid's second axis in 128-lane groups."""
    from ddl_tpu.ops import flash_tile

    q, k, v = _qkv(rng, B=2, T=40, H=4, D=64)
    monkeypatch.setattr(flash_tile, "_BLOCK_BUDGET", 14 * 40 * 4 * 64 * 2)
    assert flash_tile._plan(40, 4, 64, q.dtype) == 2
    fn = lambda q, k, v: jnp.sum(flash_attention(q, k, v, causal=False) ** 2)  # noqa: E731
    rf = lambda q, k, v: jnp.sum(attention_reference(q, k, v, causal=False) ** 2)  # noqa: E731
    for a, b in zip(jax.grad(fn, (0, 1, 2))(q, k, v),
                    jax.grad(rf, (0, 1, 2))(q, k, v)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=5e-5)
    # 3 heads x 64 is no multiple of 128 lanes and all 3 are too many:
    # no legal group, the blockwise kernels keep the call.
    q3 = q[:, :, :3]
    monkeypatch.setattr(flash_tile, "_BLOCK_BUDGET", 14 * 40 * 4 * 64)
    assert not flash_tile.fits(q3, q3, q3, 1, 512, 1024, None)


@pytest.mark.parametrize("what", ["out", "grads"])
def test_tile_bf16_and_jit(rng, what):
    q, k, v = _qkv(rng, B=2, T=196, H=2, D=64, dtype=jnp.bfloat16)

    def run(attn):
        if what == "out":
            return (attn(q, k, v, causal=False),)
        return jax.grad(
            lambda q, k, v: jnp.sum(
                attn(q, k, v, causal=False).astype(jnp.float32) ** 2
            ), argnums=(0, 1, 2),
        )(q, k, v)

    got = jax.jit(lambda: run(flash_attention))()
    want = run(attention_reference)
    for a, b in zip(got, want):
        assert a.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            a.astype(np.float32), b.astype(np.float32), atol=3e-2, rtol=3e-2
        )


@pytest.mark.parametrize("what", ["out", "grads"])
def test_tile_under_sharded_local_attention(rng, what):
    """ViT's dp mesh: the one-block kernels inside the shard_map."""
    from ddl_tpu.parallel.mesh import make_mesh
    from ddl_tpu.parallel.ring_attention import sharded_local_attention

    mesh = make_mesh({"dp": 8})
    q, k, v = _qkv(rng, B=16, T=50, H=4, D=32)

    def sharded(q, k, v):
        return sharded_local_attention(q, k, v, mesh, causal=False,
                                       use_flash=True)

    def dense(q, k, v):
        return attention_reference(q, k, v, causal=False)

    if what == "out":
        np.testing.assert_allclose(sharded(q, k, v), dense(q, k, v),
                                   atol=2e-5, rtol=2e-5)
        return
    loss = lambda fn: lambda q, k, v: jnp.sum(jnp.sin(fn(q, k, v)))  # noqa: E731
    for a, b in zip(jax.grad(loss(sharded), (0, 1, 2))(q, k, v),
                    jax.grad(loss(dense), (0, 1, 2))(q, k, v)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=5e-5)


TILE = {"ddl_flash_tile_fwd", "ddl_flash_tile_bwd"}
# Forward once, ONE backward kernel - the dK/dV grid carrying dQ (PR 45) -
# and no ``*_bwd_dq`` family at any shape a cell or a test below runs.
BLOCK = {"ddl_flash_fwd", "ddl_flash_bwd_dkv"}
SWA = {"ddl_flash_swa_fwd", "ddl_flash_swa_bwd_dkv"}


def _grad_of(attn):
    # The value too: the one-block backward needs nothing of the forward,
    # and jit drops a forward kernel whose output nobody reads.
    return jax.value_and_grad(
        lambda q, k, v: jnp.sum(attn(q, k, v).astype(jnp.float32)),
        argnums=(0, 1, 2),
    )


@pytest.mark.parametrize("case,want", [
    ("vit", TILE), ("vit_causal", TILE), ("t512_d128", TILE),
    ("d16", BLOCK), ("d80", BLOCK), ("d96", BLOCK), ("d192", BLOCK),
    ("float16", BLOCK), ("t4096", BLOCK), ("t513", BLOCK), ("segment_ids", BLOCK),
    ("gqa", BLOCK), ("explicit_blocks", BLOCK), ("with_lse", BLOCK),
    ("with_lse_offsets", BLOCK), ("window", SWA), ("window_one_block", SWA),
    ("window_covers_t4096", BLOCK), ("window_covers_one_block", TILE),
])
def test_which_kernels_a_call_lowers_to(case, want):
    """The rule is shapes and arguments: the kernels' names in the program
    lowered for the TPU, forward and backward."""
    from ddl_tpu.ops import flash_attention_with_lse

    def args(B, T, H, D, Hkv=None):
        q = jax.ShapeDtypeStruct((B, T, H, D), jnp.bfloat16)
        kv = jax.ShapeDtypeStruct((B, T, Hkv or H, D), jnp.bfloat16)
        return q, kv, kv

    kw = dict(interpret=False)
    seg = jnp.zeros((2, 196), jnp.int32)
    fn, shapes = {
        "vit": (lambda q, k, v: flash_attention(q, k, v, causal=False, **kw),
                args(128, 196, 12, 64)),
        "vit_causal": (lambda q, k, v: flash_attention(q, k, v, **kw),
                       args(2, 196, 12, 64)),
        "t512_d128": (lambda q, k, v: flash_attention(q, k, v, **kw),
                      args(1, 512, 32, 128)),
        # Heads shallower than 32 lanes (a group would unroll 8), and heads
        # that neither divide a 128-lane group nor fill whole ones (ViT-H/14's
        # 80: no aligned lane slice): the blockwise kernels'.
        "d16": (lambda q, k, v: flash_attention(q, k, v, causal=False, **kw),
                args(8, 196, 16, 16)),
        "d80": (lambda q, k, v: flash_attention(q, k, v, causal=False, **kw),
                args(8, 257, 16, 80)),
        "d96": (lambda q, k, v: flash_attention(q, k, v, causal=False, **kw),
                args(8, 196, 16, 96)),
        "d192": (lambda q, k, v: flash_attention(q, k, v, **kw),
                 args(8, 128, 4, 192)),
        # A dtype no chip run timed and no compile tried on the new path.
        "float16": (lambda q, k, v: flash_attention(q, k, v, **kw),
                    tuple(jax.ShapeDtypeStruct(x.shape, jnp.float16)
                          for x in args(2, 196, 12, 64))),
        "t4096": (lambda q, k, v: flash_attention(q, k, v, **kw),
                  args(1, 4096, 2, 128)),
        "t513": (lambda q, k, v: flash_attention(q, k, v, **kw),
                 args(1, 513, 2, 128)),
        "segment_ids": (
            lambda q, k, v: flash_attention(q, k, v, segment_ids=seg, **kw),
            args(2, 196, 12, 64)),
        "gqa": (lambda q, k, v: flash_attention(q, k, v, kv_repeat=2, **kw),
                args(2, 196, 12, 64, Hkv=6)),
        "explicit_blocks": (
            lambda q, k, v: flash_attention(q, k, v, block_q=128,
                                            block_k=128, **kw),
            args(2, 196, 12, 64)),
        "with_lse": (
            lambda q, k, v: flash_attention_with_lse(q, k, v, **kw)[0],
            args(2, 196, 12, 64)),
        "with_lse_offsets": (
            lambda q, k, v: flash_attention_with_lse(
                q, k, v, q_offset=196, k_offset=0, **kw)[0],
            args(2, 196, 12, 64)),
        # A sliding window narrower than the row: kernels of their own
        # names, in the one-block geometry too (the tile kernels have no
        # band); one that covers the row is plain causal attention.
        "window": (
            lambda q, k, v: flash_attention(q, k, v, window=2048,
                                            kv_repeat=2, **kw),
            args(1, 8192, 2, 128, Hkv=1)),
        "window_one_block": (
            lambda q, k, v: flash_attention(q, k, v, window=64, **kw),
            args(2, 196, 12, 64)),
        "window_covers_t4096": (
            lambda q, k, v: flash_attention(q, k, v, window=4096, **kw),
            args(1, 4096, 2, 128)),
        "window_covers_one_block": (
            lambda q, k, v: flash_attention(q, k, v, window=196, **kw),
            args(2, 196, 12, 64)),
    }[case]
    assert _kernels(_grad_of(fn), *shapes) == want


#: sha256 of the traced train steps below, source locations and function
#: addresses taken out.  ``kanana`` is PR 48's tree (the child of 41492d9),
#: changed on purpose: latent attention cuts its projections' WEIGHTS by
#: head (``deepseek_v3._head_columns``) where it sliced the activations, so
#: the step holds two products for q and two for k / v and no per-head
#: slice, pad or concatenate of a ``(B, T, H, 192 | 256)`` array; the
#: kernels and their calls are the parent's.  The others are PR 45's tree
#: (the child of 90a08e5), which changed
#: five of them on purpose: the backward pass of a blockwise flash call is
#: ONE kernel, the dK/dV grid carrying dQ, where a ``dq`` and a ``dkv``
#: kernel stood (forward once, one backward kernel a layer, no ``*_bwd_dq``
#: family; nothing else of a step moved: the parent's text with each pair's
#: two ``pallas_call``s taken for one).  Before: PR 33's tree (selective
#: remat saves the blockwise cores' residuals), PR 35's for ``olmoe`` (the
#: routed layer's row moves), PR 42's parent for the four later families.
#: ``minicpm_sala`` is that one still: its row takes the block-sparse
#: kernels, which keep their own pair.  (The
#: text LOWERED for the TPU will not do: Mosaic serialises each kernel with
#: the file and line of every operation, so it changes with the checkout's
#: path.)
PARENT_JAX = "0.9.0"
PARENT_STEP_SHA256 = {
    "mistral":
        "8101cf75b6e821e2ebe0206fbd75ff7835aa267cb49e240bdcb73206c1b0b51a",
    "olmoe":
        "b49069002c6ddde1f2397067f7a306940c0d57919c0180ca9f2641309e11ba9a",
    # (until PR 45: PR 42's parent's, recorded before it touched a model)
    "trinity":
        "56bade065d726d9bb3ded3e4722a5fe03b9eaf36e7425f8961e45f50dec8563d",
    "kanana":
        "cb4b4b4c0eb0ce81a7a069cefebc9de5fbb3db2fd7e8d3b9b8796492179a0b9a",
    "olmo_hybrid":
        "2f62853826f3544759a431230e1d0ad7e2f23cfa8d8fdc326f2738c751093719",
    "minicpm_sala":
        "921389e4b214acba64f2eaa4cbdbbb80376b51db6d2f8cbe3b5345ae42d57239",
}


def _decoder_case(model):
    """(module, config, row length) of a decoder shaped like the
    benchmark's cell of that family, at a size a trace takes seconds of:
    128-deep heads, flash kernels, selective remat, bf16 storage; a share of
    4 of 16 experts behind a dense first layer for the two share families,
    both mixer kinds for the two hybrids (a row past ``dense_len`` for
    MiniCPM-SALA's selection)."""
    from ddl_tpu.models import (
        afmoe, deepseek_v3, llama, minicpm_sala, moe, olmo_hybrid)

    common = dict(
        vocab=256, d_model=256, n_heads=2, max_seq=4096, attn_impl="flash",
        remat="selective", param_dtype=jnp.bfloat16,
    )
    if model == "mistral":
        return llama, llama.LlamaConfig(
            n_layers=1, n_kv_heads=1, d_ff=128, **common), 4096
    if model == "olmoe":
        return moe, moe.MoeConfig(
            n_layers=1, d_ff=128, n_kv_heads=2, n_experts=4, topk=2,
            qk_norm=True, norm_topk_prob=False, **common), 4096
    share = dict(d_ff=128, d_expert=64, n_experts=16, topk=4,
                 n_dense_layers=1, held_experts=(4, 4), route_scale=2.5)
    if model == "trinity":
        S, F = afmoe.SLIDING, afmoe.FULL
        return afmoe, afmoe.AfmoeConfig(
            n_kv_heads=1, head_dim=128, layer_types=(S, S, F, S),
            sliding_window=512, **share, **common), 2048
    if model == "kanana":
        return deepseek_v3, deepseek_v3.DeepseekV3Config(
            n_layers=3, qk_nope_dim=128, qk_rope_dim=64, v_head_dim=128,
            kv_lora_rank=128, **share, **common), 2048
    if model == "olmo_hybrid":
        L, F = olmo_hybrid.LINEAR, olmo_hybrid.FULL
        return olmo_hybrid, olmo_hybrid.OlmoHybridConfig(
            d_ff=128, layer_types=(L, L, F, L), n_linear_heads=2,
            linear_key_dim=96, linear_value_dim=192, **common), 1024
    assert model == "minicpm_sala", model
    G, A = minicpm_sala.LIGHTNING, minicpm_sala.SPARSE
    return minicpm_sala, minicpm_sala.MiniCPMSalaConfig(
        n_kv_heads=1, head_dim=128, n_lightning_heads=2,
        lightning_head_dim=128, d_ff=128, mixer_types=(A, G, G, A),
        dense_len=1024, dim_model_base=32, **common), 2048


DECODERS = ["mistral", "olmoe", "trinity", "kanana", "olmo_hybrid",
            "minicpm_sala"]


@pytest.mark.parametrize("model", DECODERS)
def test_decoder_steps_trace_to_what_the_parent_traced(model, monkeypatch):
    """T = 4096 bypasses the one-block path: the train step of a decoder
    shaped like the benchmark's (GQA 2:1 for Mistral; full MHA + QK-norm
    and routed experts for OLMoE; 128-deep heads, selective remat, flash
    kernels and all) holds the forward kernel and the one backward kernel
    (the dK/dV grid carrying dQ: no ``*_bwd_dq`` family) and no other, each
    once a layer and step — the forward too — and is, equation for equation,
    the program the commit above traced.  So are the four later families'
    (``_decoder_case``): traced, never lowered."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mod, cfg, T = _decoder_case(model)
    params = jax.eval_shape(lambda: mod.init_params(cfg, jax.random.key(0)))
    tokens = jax.ShapeDtypeStruct((1, T), jnp.int32)
    text = str(jax.make_jaxpr(jax.value_and_grad(
        lambda p, t: mod.next_token_loss(p, t, cfg)
    ))(params, tokens))
    calls = collections.Counter(re.findall(r"name=(ddl_\w+)", text))
    del calls["ddl_attn_out"]  # the checkpoint name, not a kernel's
    # each kernel once a layer of its kind and step, the forward too
    assert calls == {
        "mistral": dict.fromkeys(BLOCK, 1),
        "olmoe": dict.fromkeys(BLOCK, 1),
        "trinity": {**dict.fromkeys(BLOCK, 1), **dict.fromkeys(SWA, 3)},
        "kanana": dict.fromkeys(("ddl_flash_mla_fwd", "ddl_flash_mla_bwd_dkv"), 3),
        "olmo_hybrid": {**dict.fromkeys(BLOCK, 1), "ddl_gdn_fwd": 3, "ddl_gdn_bwd": 3},
        "minicpm_sala": dict.fromkeys(
            ("ddl_sparse_select", "ddl_flash_sparse_fwd", "ddl_flash_sparse_bwd_dq",
             "ddl_flash_sparse_bwd_dkv", "ddl_lightning_fwd", "ddl_lightning_bwd"), 2),
    }[model], calls
    if model in ("mistral", "olmoe"):
        assert set(re.findall(r"ddl_flash_\w+", text)) == BLOCK
    if jax.__version__ == PARENT_JAX:  # the printed form is this JAX's
        text = re.sub(r" at (0x[0-9a-f]+|\S+:\d+)", "", text)
        assert hashlib.sha256(text.encode()).hexdigest() == PARENT_STEP_SHA256[model]


#: sha256 over ``init_params(cfg, key(0))`` of the six cases above: every
#: leaf's path, dtype, shape, ``PartitionSpec`` and bytes, on PR 42's parent (6d42264).  The
#: order in which a family draws its keys is part of it.
PARENT_INIT_SHA256 = {
    "mistral":
        "7f9322dbab1eeb65a755c2278dc61dda831df81acac34f5125a581279591e6be",
    "olmoe":
        "06b83c19e58cda67cd9652aa957a7e2090a1c2f9ed6331dfaddf62218dcdb761",
    "trinity":
        "c2b648d48ca3f301aefdbdeb1ff1135e0f6a017617e5713d22ad13cde90f051a",
    "kanana":
        "4858a41fb65d790b98f9e84ea138212e2f8c39ec93d94058c84c3ae77814d1d9",
    "olmo_hybrid":
        "fe07a9905af886f1f5cc4ab3613e515e8370a040bd74e31c97f67bd65ec97078",
    "minicpm_sala":
        "af73273354f19c83c7f2564128391f89e0e2ca97738de0ab994c33f0b5997f50",
}


@pytest.mark.parametrize("model", DECODERS)
def test_initial_weights_for_a_key_are_the_parents(model):
    mod, cfg, _ = _decoder_case(model)
    params = mod.init_params(cfg, jax.random.key(0))
    is_spec = lambda s: isinstance(s, jax.sharding.PartitionSpec)
    specs = jax.tree.leaves(mod.param_specs(cfg), is_leaf=is_spec)
    leaves = jax.tree_util.tree_leaves_with_path(params)
    assert jax.tree.structure(params) == jax.tree.structure(
        mod.param_specs(cfg), is_leaf=is_spec)
    digest = hashlib.sha256()
    for (path, leaf), spec in zip(leaves, specs):
        assert len(spec) == leaf.ndim, (path, spec)
        digest.update(
            f"{jax.tree_util.keystr(path)} {leaf.dtype} {leaf.shape} {spec}\n".encode())
        digest.update(np.asarray(leaf).tobytes())
    if hasattr(mod, "param_shapes"):  # the same tree, no weight made
        shapes = jax.tree.leaves(mod.param_shapes(cfg))
        assert [(s.shape, s.dtype) for s in shapes] == [
            (leaf.shape, leaf.dtype) for _, leaf in leaves]
    if jax.__version__ == PARENT_JAX:
        assert digest.hexdigest() == PARENT_INIT_SHA256[model]


#: sha256 of the traced cache paths of the two families that decode — the
#: prefill, which asks for the frontier's logits alone, and a one-token step
#: — on PR 42's parent (6d42264).  The head's order is part of it: the final
#: norm over every position, then the frontier's slice (a slice in front of
#: the norm XLA sinks into the last layer's matmuls, and bf16 logits move).
PARENT_DECODE_SHA256 = {
    "mistral":
        "d5a685a3fc7c16f13833bcf66b5c18b6628c88a11c3311ae04fb7ec73ed34af8",
    "olmoe":
        "9084357c374a22c6ef4c0edd37c31dad5cca7e9d936a56d3a5dab612f2c62803",
}


@pytest.mark.parametrize("model", DECODERS[:2])
def test_decode_programs_trace_to_what_the_parent_traced(model):
    mod, cfg, _ = _decoder_case(model)
    params = jax.eval_shape(lambda: mod.init_params(cfg, jax.random.key(0)))
    cache = jax.eval_shape(lambda: mod.init_cache(cfg, 2, 32))
    text = "".join(
        str(jax.make_jaxpr(
            lambda p, t, c, pos: mod.forward_with_cache(
                p, t, cfg, c, pos, last_only=last_only)
        )(params, jax.ShapeDtypeStruct((2, T), jnp.int32), cache,
          jax.ShapeDtypeStruct((), jnp.int32)))
        for T, last_only in [(16, True), (1, False)]
    )
    if jax.__version__ == PARENT_JAX:
        text = re.sub(r" at (0x[0-9a-f]+|\S+:\d+)", "", text)
        assert hashlib.sha256(text.encode()).hexdigest() == PARENT_DECODE_SHA256[model]


@pytest.mark.parametrize("model", DECODERS[2:])
def test_the_train_loss_is_the_cross_entropy_of_the_familys_forward(model):
    """What ``benchmarks/tests`` (not tier-1) reads of ``next_token_loss`` in
    the families whose loss ``decoder.loss_of`` builds — the check's loss is
    the model's train loss: the text, and that the ``forward`` the loss
    closes over is the family's own."""
    import inspect

    mod, _, _ = _decoder_case(model)
    assert "next_token_cross_entropy(forward(" in inspect.getsource(
        mod.next_token_loss)
    assert inspect.getclosurevars(mod.next_token_loss).nonlocals == {
        "forward": mod.forward}


# -- what remat="selective" keeps of a blockwise call ----------------------------

CORES = {  # name: (the kernels' infix, what the layer passes besides q, k, v)
    "causal_gqa": ("", dict(kv_repeat=2)),
    "packed": ("", dict(segment_ids=True)),
    "window": ("swa_", dict(kv_repeat=2, window=256)),
    "latent": ("mla_", dict(rope=64)),
}


def _attn_layer(core, B, T, H, D, policy, **flash_kw):
    """``(x, w) -> x``: one projection, a blockwise attention call of the
    given core, the output projection — under the remat policy."""
    from ddl_tpu.models import remat

    _, kw = CORES[core]
    kw = dict(kw)
    rep, R = kw.get("kv_repeat", 1), kw.pop("rope", None)
    if kw.pop("segment_ids", False):
        kw["segment_ids"] = jnp.arange(T)[None].repeat(B, 0) // (T // 2)

    def layer(x, w):
        q = (x @ w).reshape(B, T, H, D)
        k = v = q[:, :, ::rep]
        if R:
            kw.update(q_rope=q[..., :R], k_rope=k[:, :, :1, :R])
        out = flash_attention(q, k, v, **kw, **flash_kw)
        return x + out.reshape(B, T, H * D) @ w

    return remat.wrap(layer, policy)


@pytest.mark.parametrize("policy,forward_calls", [
    ("none", 1), ("selective", 1), ("full", 2), ("dots", 2)])
@pytest.mark.parametrize("core", list(CORES))
def test_forward_kernel_calls_a_layer_under_each_remat_policy(
        core, policy, forward_calls):
    """The backward kernel reads the output and the logsumexp, and
    ``selective`` saves both: its backward pass runs no forward kernel,
    as with no remat at all; ``full`` and ``dots`` keep neither and run it
    again.  Forward once (twice), one backward kernel a layer, no
    ``*_bwd_dq`` family: counted in two layers' train step lowered for the
    TPU."""
    B, T, H, D = 1, 1024, 2, 128
    layer = _attn_layer(core, B, T, H, D, policy, interpret=False)
    x = jax.ShapeDtypeStruct((B, T, H * D), jnp.bfloat16)
    w = jax.ShapeDtypeStruct((H * D, H * D), jnp.bfloat16)
    got = _kernel_calls(jax.value_and_grad(
        lambda w, x: jnp.sum(layer(layer(x, w), w).astype(jnp.float32))
    ), w, x)
    name = "ddl_flash_" + CORES[core][0]
    assert got == {name + "fwd": 2 * forward_calls, name + "bwd_dkv": 2}


@pytest.mark.parametrize("T", [1024, 1000], ids=["on_the_block", "off_the_block"])
@pytest.mark.parametrize("core", list(CORES))
def test_selective_saves_one_output_and_a_compact_logsumexp(core, T):
    """A layer's saved values under ``selective``: its input, ONE tensor of
    the attention output's bytes and one float32 (B, H, T) — not the
    kernels' lane-padded (B, H, Tq, 1) row operand, and no second copy of
    the output in the kernels' layout."""
    from jax._src.ad_checkpoint import saved_residuals

    B, H, D = 1, 2, 128
    layer = _attn_layer(core, B, T, H, D, "selective", interpret=False)
    x = jax.ShapeDtypeStruct((B, T, H * D), jnp.bfloat16)
    w = jax.ShapeDtypeStruct((H * D, H * D), jnp.bfloat16)
    saved = [(aval.shape, str(aval.dtype)) for aval, why in saved_residuals(layer, x, w)
             if "from the argument" not in why and "from a constant" not in why]
    assert sorted(saved) == sorted([((B, T, H, D), "bfloat16"),
                                    ((B, H, T), "float32")]), saved


def test_the_logsumexp_cotangent_reaches_q_and_k(rng):
    """``flash_attention_with_lse`` with a non-zero ``dlse`` (the ``sp``
    ring's combine weights make one) against the dense scores, at a T off
    the block: the compact logsumexp the backward now reads is padded back
    to the kernels' rows."""
    from ddl_tpu.ops import flash_attention_with_lse

    q, k, v = _qkv(rng, B=2, T=70, H=4, Hkv=2, D=16)
    mix = jnp.asarray(rng.standard_normal((2, 4, 70)), jnp.float32)

    def dense(q, k, v):
        kk, vv = jnp.repeat(k, 2, axis=2), jnp.repeat(v, 2, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, kk) / 4.0
        s = jnp.where(jnp.tril(jnp.ones((70, 70), bool)), s, -jnp.inf)
        return (jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), vv),
                jax.nn.logsumexp(s, -1))

    def loss(fn):
        def f(q, k, v):
            out, lse = fn(q, k, v)
            return jnp.sum(jnp.sin(out)) + jnp.sum(mix * lse)
        return f

    flash = lambda q, k, v: flash_attention_with_lse(  # noqa: E731
        q, k, v, kv_repeat=2, block_q=32, block_k=32)
    np.testing.assert_allclose(flash(q, k, v)[1], dense(q, k, v)[1],
                               atol=2e-5, rtol=2e-5)
    plain = jax.grad(lambda q, k, v: jnp.sum(jnp.sin(flash(q, k, v)[0])), (0, 1))
    for got, want, bare, name in zip(
            jax.grad(loss(flash), (0, 1, 2))(q, k, v),
            jax.grad(loss(dense), (0, 1, 2))(q, k, v),
            plain(q, k, v) + (None,), ("dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=5e-5, rtol=5e-5, err_msg=name)
        if bare is not None:  # the lse term moved it: dlse was not dropped
            assert float(jnp.max(jnp.abs(got - bare))) > 1e-3, name


# -- the backward pass as ONE kernel: the dK/dV grid carries dQ ------------------

blockwise = importlib.import_module("ddl_tpu.ops.flash_attention")


BACKWARD_CASES = {
    # name: (q/k/v shape arguments, the call's arguments)
    "causal": (dict(T=96), dict()),
    "not_causal": (dict(T=96), dict(causal=False)),
    "band_16": (dict(T=96), dict(window=16)),
    "band_nearly_the_row": (dict(T=96), dict(window=80)),
    "band_wider_than_the_row": (dict(T=96), dict(window=200)),
    "band_blocks_16x32": (dict(T=96), dict(window=40, block_q=16, block_k=32)),
    "band_blocks_32x16": (dict(T=96), dict(window=40, block_q=32, block_k=16)),
    "band_pads": (dict(T=70), dict(window=24)),
    "blocks_16x32": (dict(T=96), dict(block_q=16, block_k=32)),
    "blocks_64x16": (dict(T=128), dict(block_q=64, block_k=16)),
    "gqa_4": (dict(T=96, H=8, Hkv=2), dict(kv_repeat=4)),
    "packed": (dict(T=96), dict(segment_ids=(40, 30, 26))),
    "latent": (dict(T=96), dict(rope=16)),
    "latent_pads": (dict(T=70), dict(rope=16)),
    "dlse": (dict(T=96, H=4, Hkv=2), dict(kv_repeat=2, lse=True)),
    "pads": (dict(T=70), dict()),
    "ring_step_behind": (dict(T=64), dict(lse=True, q_offset=64, k_offset=0)),
    "ring_step_on_the_diagonal": (
        dict(T=64), dict(lse=True, q_offset=64, k_offset=64)),
    "ring_step_ahead": (dict(T=64), dict(lse=True, q_offset=0, k_offset=64)),
    "ring_step_packed": (dict(T=64), dict(
        lse=True, q_offset=32, k_offset=0, segment_ids=(20, 44))),
}


def _backward_case(rng, case, dtype, **flash_kw):
    """(loss, operands) of a case: the weighted sum of the call's output -
    and, through ``flash_attention_with_lse``, of its logsumexp, so that
    ``dlse`` is not zero - over q, k, v (and the rotary pair)."""
    from ddl_tpu.ops import flash_attention_with_lse

    shape, kw = BACKWARD_CASES[case]
    kw = dict(block_q=32, block_k=32) | kw | flash_kw
    q, k, v = _qkv(rng, D=32, dtype=dtype, **shape)
    B, T, H, _ = q.shape
    operands = [q, k, v]
    R, with_lse = kw.pop("rope", None), kw.pop("lse", False)
    if R:
        operands += [jnp.asarray(rng.standard_normal(s), dtype)
                     for s in ((B, T, H, R), (B, T, 1, R))]
    if "segment_ids" in kw:
        ids = np.repeat(np.arange(len(kw["segment_ids"])), kw["segment_ids"])
        kw["segment_ids"] = jnp.asarray(ids, jnp.int32)[None].repeat(B, 0)
    w_out = jnp.asarray(rng.standard_normal(q.shape), jnp.float32)
    w_lse = jnp.asarray(rng.standard_normal((B, H, T)), jnp.float32)

    def loss(q, k, v, *rope):
        if with_lse:
            # offsets as the ring passes them: traced inside its scan
            offs = {n: jnp.asarray(kw[n]) + 0 * q.shape[0] for n in
                    ("q_offset", "k_offset") if n in kw}
            out, lse = flash_attention_with_lse(q, k, v, **(kw | offs))
            lse = jnp.where(lse > -1e29, lse, 0.0)  # an all-masked row's
            return jnp.sum(out * w_out) + jnp.sum(lse * w_lse)
        if rope:
            return jnp.sum(flash_attention(
                q, k, v, q_rope=rope[0], k_rope=rope[1], **kw) * w_out)
        return jnp.sum(flash_attention(q, k, v, **kw) * w_out)

    return loss, operands


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(BACKWARD_CASES))
def test_the_one_kernel_backward_is_the_two_kernels(rng, monkeypatch, case, dtype):
    """The backward pass as one kernel on the dK/dV grid, dQ's float32 rows
    of a whole (batch, head) in VMEM, against the two kernels it replaces
    (kept for rows too long for that: forced here) on the same operands,
    interpreted: every gradient EQUAL, bit for bit - the same products in
    the same order (``j`` ascending for a fixed ``i``), one rounding at the
    end - and the one kernel's program holds no ``*_bwd_dq`` family."""
    loss, operands = _backward_case(rng, case, dtype)
    for_tpu, _ = _backward_case(rng, case, dtype, interpret=False)
    argnums = tuple(range(len(operands)))
    one = jax.grad(loss, argnums)(*operands)
    names = _kernels(jax.grad(for_tpu, argnums), *operands)
    with monkeypatch.context() as m:
        # the two kernels at every shape: no VMEM for dQ's rows; and new
        # function objects, so that nothing traced above is read again
        m.setattr(blockwise, "_BWD_ROW_BYTES", 0)
        two = jax.grad(lambda *a: loss(*a), argnums)(*operands)
        names_pair = _kernels(jax.grad(lambda *a: for_tpu(*a), argnums), *operands)
    for got, want, name in zip(one, two, ("dq", "dk", "dv", "dq_rope", "dk_rope")):
        assert got.dtype == want.dtype == dtype and got.shape == want.shape
        # a ring step wholly in the masked future moves nothing
        assert (float(jnp.abs(want.astype(jnp.float32)).max()) > 0) == (
            case != "ring_step_ahead"), name
        np.testing.assert_array_equal(
            np.asarray(got.astype(jnp.float32)),
            np.asarray(want.astype(jnp.float32)), err_msg=name)
    assert {n.rsplit("_", 1)[1] for n in names} == {"fwd", "dkv"}, names
    assert {n.rsplit("_", 1)[1] for n in names_pair} == {"fwd", "dq", "dkv"}
    assert len(names) == 2 and len(names_pair) == 3


@pytest.mark.parametrize("T,D,R,want", [
    (65536, 128, None, "one"),  # 32 MiB of float32 rows: the bound itself
    (65536 + 1024, 128, None, "pair"),
    (131072, 64, None, "pair"),  # 64 lanes take a tile's 128
    (16384, 128, 64, "one"), (32768 + 1024, 128, 64, "pair"),
])
def test_a_row_past_the_vmem_bound_keeps_the_two_kernels(T, D, R, want):
    """Which side a shape takes is read off the operands alone: dQ's
    float32 rows of one (batch, head) - lane-padded, the latent form's
    rotary rows beside them - within half the VMEM the kernel asks for."""
    rows = blockwise._dq_row_bytes(T, *((D, R) if R else (D,)))
    assert (rows <= blockwise._BWD_ROW_BYTES) == (want == "one")
    assert blockwise._BWD_ROW_BYTES * 2 == blockwise._BWD_VMEM_LIMIT
    shape = lambda h, d: jax.ShapeDtypeStruct((1, T, h, d), jnp.bfloat16)  # noqa: E731
    args = (shape(1, D),) * 3 + ((shape(1, R), shape(1, R)) if R else ())

    def loss(q, k, v, *rope):
        rope = dict(q_rope=rope[0], k_rope=rope[1]) if rope else {}
        return jnp.sum(flash_attention(
            q, k, v, interpret=False, **rope).astype(jnp.float32))

    names = _kernel_calls(jax.grad(loss, tuple(range(len(args)))), *args)
    infix = "mla_" if R else ""
    assert names == {
        f"ddl_flash_{infix}{kernel}": 1 for kernel in
        (("fwd", "bwd_dkv") if want == "one" else ("fwd", "bwd_dq", "bwd_dkv"))}


@pytest.mark.parametrize("T,window,block_q,block_k", [
    (8192, None, 1024, 1024), (8192, 2048, 512, 512), (8192, 4096, 1024, 1024),
    (96, None, 32, 32), (96, None, 16, 32), (96, None, 32, 16),
    (96, 16, 32, 32), (96, 80, 32, 32), (96, 40, 16, 32), (96, 40, 32, 16),
    (70, 24, 32, 32), (100, 33, 16, 48), (100, 7, 48, 16), (512, 1, 64, 64),
    (4096, 1024, 512, 512), (4096, 1024, 256, 512), (4096, 1024, 512, 256),
])
def test_every_dq_block_leaves_vmem_once_after_its_last_addition(
        T, window, block_q, block_k):
    """dQ's output block on the dK/dV grid (``_dq_block_of``), walked as the
    TPU's pipeline walks it: a block is written back to HBM when the next
    step's index differs (and after the head's last step).  Interpret mode
    cannot see this - it stores a block every step - so the walk is done
    here: every Q block of the row is written back exactly once, holding
    what ``_dq_final``'s step put there, and no step that can add to a Q
    block (any step of the grid that reaches it) comes after that step."""
    nqb, nkb = -(-T // block_q), -(-T // block_k)
    band = inner = None
    q_block = lambda j, ii: ii  # noqa: E731
    if window is not None:
        band = blockwise.band_grid(T, window, block_q, block_k)
        q_block = blockwise._q_block_of(block_q, block_k, band)
    inner = nqb if band is None else band.nq
    at = blockwise._dq_block_of(nqb, nkb, block_q, block_k, band, q_block)
    steps = [(j, ii) for j in range(nkb) for ii in range(inner)]
    touched, final_at, holds, written = {}, {}, {}, []
    for n, (j, ii) in enumerate(steps):
        i = ii if band is None else blockwise._band_q_block(
            j, ii, block_q, block_k)
        in_row = i < nqb
        if in_row:
            touched[i] = n
            if bool(blockwise._dq_final(j, i, nkb, block_q, block_k, band)):
                assert i not in final_at
                final_at[i] = n
                assert int(at(j, ii)) == i  # the step writes ITS block
                holds[i] = "final"
        if n + 1 == len(steps) or int(at(*steps[n + 1])) != int(at(j, ii)):
            written.append((int(at(j, ii)), holds.get(int(at(j, ii)))))
    assert sorted(written) == [(i, "final") for i in range(nqb)], written
    assert final_at == touched  # a block's last visit is its final step
