"""Ring attention correctness vs the dense oracle, on the 8-device mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddl_tpu.parallel.mesh import make_mesh
from ddl_tpu.parallel.ring_attention import (
    attention_reference,
    ring_attention,
)


def _qkv(key, B=2, T=32, H=4, D=16, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(key, 3)
    shape = (B, T, H, D)
    return (
        jax.random.normal(kq, shape, dtype),
        jax.random.normal(kk, shape, dtype),
        jax.random.normal(kv, shape, dtype),
    )


class TestRingAttention:
    @pytest.mark.parametrize("causal", [True, False])
    @pytest.mark.parametrize("sp", [2, 4, 8])
    def test_matches_dense_oracle(self, causal, sp):
        mesh = make_mesh({"sp": sp}, jax.devices()[:sp])
        q, k, v = _qkv(jax.random.key(0))
        out = ring_attention(q, k, v, mesh, causal=causal, dp_axis=None)
        ref = attention_reference(q, k, v, causal=causal)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
        )

    @pytest.mark.parametrize("use_flash", [False, True])
    def test_packed_segments_match_dense_oracle(self, use_flash):
        """Packed sequences across ring shards: key-side segment ids ride
        the ring with their K/V blocks; result matches the segment-aware
        dense oracle, including documents that straddle shard cuts."""
        sp = 4
        mesh = make_mesh({"sp": sp}, jax.devices()[:sp])
        q, k, v = _qkv(jax.random.key(2), T=32)
        rng = np.random.default_rng(0)
        ids = np.zeros((2, 32), np.int32)
        for b in range(2):
            cuts = np.sort(rng.choice(np.arange(1, 32), 3, replace=False))
            ids[b] = np.searchsorted(cuts, np.arange(32), side="right")
        seg = jnp.asarray(ids)
        out = ring_attention(q, k, v, mesh, causal=True, dp_axis=None,
                             use_flash=use_flash, segment_ids=seg)
        ref = attention_reference(q, k, v, causal=True, segment_ids=seg)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
        )

    @pytest.mark.parametrize("use_flash", [False, True])
    def test_packed_segments_grads(self, use_flash):
        sp = 4
        mesh = make_mesh({"sp": sp}, jax.devices()[:sp])
        q, k, v = _qkv(jax.random.key(3), T=32)
        seg = jnp.asarray(
            np.repeat(np.arange(4, dtype=np.int32), 8)
        )[None].repeat(2, axis=0)

        def loss_ring(q, k, v):
            return jnp.sum(
                ring_attention(q, k, v, mesh, causal=True, dp_axis=None,
                               use_flash=use_flash, segment_ids=seg) ** 2
            )

        def loss_ref(q, k, v):
            return jnp.sum(
                attention_reference(q, k, v, causal=True,
                                    segment_ids=seg) ** 2
            )

        gr = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
        gd = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gr, gd):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-4
            )

    def test_dp_and_sp_mesh(self):
        mesh = make_mesh({"dp": 2, "sp": 4})
        q, k, v = _qkv(jax.random.key(1), B=4, T=64)
        out = ring_attention(q, k, v, mesh, causal=True)
        ref = attention_reference(q, k, v, causal=True)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
        )

    def test_sp_absent_falls_back_dense(self):
        mesh = make_mesh({"dp": 8})
        q, k, v = _qkv(jax.random.key(2))
        out = ring_attention(q, k, v, mesh, causal=True)
        ref = attention_reference(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-6)

    def test_jit_composes(self):
        mesh = make_mesh({"sp": 4}, jax.devices()[:4])

        @jax.jit
        def f(q, k, v):
            return ring_attention(q, k, v, mesh, causal=True, dp_axis=None)

        q, k, v = _qkv(jax.random.key(3))
        np.testing.assert_allclose(
            np.asarray(f(q, k, v)),
            np.asarray(attention_reference(q, k, v, causal=True)),
            rtol=2e-5, atol=2e-5,
        )


class TestGQACompactRing:
    def test_kv_repeat_matches_expanded(self):
        """Compact-GQA ring (kv rotated unexpanded) == pre-expanded dense."""
        mesh = make_mesh({"sp": 4}, jax.devices()[:4])
        key = jax.random.key(5)
        kq, kk, kv = jax.random.split(key, 3)
        q = jax.random.normal(kq, (2, 32, 8, 16))
        k = jax.random.normal(kk, (2, 32, 2, 16))  # 2 kv heads, rep=4
        v = jax.random.normal(kv, (2, 32, 2, 16))
        out = ring_attention(q, k, v, mesh, causal=True, dp_axis=None,
                             kv_repeat=4)
        k_exp = jnp.repeat(k, 4, axis=2)
        v_exp = jnp.repeat(v, 4, axis=2)
        ref = attention_reference(q, k_exp, v_exp, causal=True)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
        )


class TestMaskedRowNumerics:
    def test_strongly_negative_scores_survive(self):
        """Regression: fully-masked ring blocks must not clamp the running
        max to 0 (exp underflow for strongly negative true scores)."""
        mesh = make_mesh({"sp": 4}, jax.devices()[:4])
        key = jax.random.key(6)
        # Scale q so true scores are ~ -300: exp(s - 0) would underflow.
        q = -20.0 * jnp.abs(jax.random.normal(key, (1, 32, 2, 16)))
        k = 20.0 * jnp.abs(jax.random.normal(key, (1, 32, 2, 16)))
        v = jax.random.normal(jax.random.key(7), (1, 32, 2, 16))
        out = ring_attention(q, k, v, mesh, causal=True, dp_axis=None)
        ref = attention_reference(q, k, v, causal=True)
        assert bool(jnp.all(jnp.isfinite(out)))
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-4
        )


def test_the_sp_ring_refuses_a_sliding_window_by_name():
    """A window on the ring is not the ring with a mask: the dispatcher
    says so rather than ignoring the argument; off the ring it is taken."""
    from ddl_tpu.parallel.ring_attention import attention

    q, k, v = _qkv(jax.random.key(4))
    mesh = make_mesh({"sp": 4}, jax.devices()[:4])
    with pytest.raises(NotImplementedError, match="ring attention.*sliding window"):
        attention(q, k, v, mesh=mesh, impl="dense", window=8)
    want = attention_reference(q, k, v, window=8)
    for on in (None, make_mesh({"dp": 2}, jax.devices()[:2])):
        np.testing.assert_allclose(
            np.asarray(attention(q, k, v, mesh=on, impl="dense", window=8)),
            np.asarray(want), rtol=2e-5, atol=2e-5,
        )
