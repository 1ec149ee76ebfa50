"""The fixed-decay scan (``ops/lightning_attention.py``) against the plain
recurrence, forward and in every gradient, on the CPU (Pallas' interpret
mode)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddl_tpu.ops import lightning_attention as L
import reference_minicpm_sala as reference

NAMES = ("o", "dq", "dk", "dv")


def _operands(B, T, H, d, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    return tuple(
        jnp.asarray(rng.standard_normal((B, T, H, d)), dtype) for _ in range(4)
    )


def _both(q, k, v, w, chunk=None):
    """(system, reference): the output and the three input gradients, the
    system in chunks of ``chunk`` (``None``: the row's own)."""
    def run(f, *a):
        o, pull = jax.vjp(f, *a)
        return (o,) + pull(w.astype(o.dtype))

    with jax.default_matmul_precision("highest"):
        C = chunk or L._chunk_len(q.shape[1])
        got = run(lambda q, k, v: L._in_chunks(q, k, v, None, C), q, k, v)
        f32 = [x.astype(jnp.float32) for x in (q, k, v)]
        want = run(
            lambda q, k, v: reference.lightning(q, k, v, L.slopes(q.shape[2]), 16),
            *f32,
        )
    return dict(zip(NAMES, got)), dict(zip(NAMES, want))


def _rel(a, b):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return float(jnp.sqrt(jnp.sum((a - b) ** 2) / jnp.sum(b**2)))


SHAPES = {
    "whole_chunks": (2, 64, 4, 16, 16),
    "ragged_row": (1, 100, 2, 32, 32),  # 100 is no multiple of 32
    "one_chunk": (1, 40, 4, 16, None),  # the chunk is the row's power of two
    "short_chunks": (1, 72, 8, 8, 8),
    "long_chunk": (1, 150, 2, 16, 128),
}


@pytest.mark.parametrize("name", NAMES)
@pytest.mark.parametrize("shape", list(SHAPES))
def test_the_scan_is_the_recurrence_forward_and_in_every_gradient(shape, name):
    B, T, H, d, C = SHAPES[shape]
    got, want = _both(*_operands(B, T, H, d), chunk=C)
    assert got[name].shape == (B, T, H, d)
    assert _rel(got[name], want[name]) < 5e-6, (shape, name)


@pytest.mark.parametrize("name", NAMES)
def test_bfloat16_operands_stay_on_the_recurrence(name):
    got, want = _both(*_operands(1, 96, 4, 16, dtype=jnp.bfloat16), chunk=32)
    assert got[name].dtype == jnp.bfloat16
    assert _rel(got[name], want[name]) < 2e-2, name


def test_the_decay_is_a_constant_of_the_head():
    s = L.slopes(32)
    assert s[0] == pytest.approx(2.0**-0.25) and s[-1] == pytest.approx(2.0**-8)
    np.testing.assert_allclose(s, reference.slopes(32))
    decay, edge = L._tables(s, 8, 4)
    lam = np.exp(-s)
    np.testing.assert_allclose(decay[:, 5, 2], lam**3, rtol=1e-6)
    assert float(jnp.max(jnp.abs(jnp.triu(decay, 1)))) == 0.0
    np.testing.assert_allclose(edge[:, 7, 0], lam**8, rtol=1e-6)  # the whole chunk
    np.testing.assert_allclose(edge[:, 8, 0], lam**7, rtol=1e-6)  # position 0's worth
    assert float(jnp.max(decay)) <= 1.0 and float(jnp.max(edge)) <= 1.0


def test_without_decay_it_is_causal_linear_attention():
    q, k, v, _ = _operands(1, 48, 2, 8)
    got = L._in_chunks(q, k, v, (0.0, 0.0), 16)
    with jax.default_matmul_precision("highest"):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * 8**-0.5
        s = jnp.where(jnp.tril(jnp.ones((48, 48), bool)), s, 0.0)
        want = jnp.einsum("bhqk,bkhd->bqhd", s, v)
    assert _rel(got, want) < 5e-6


def test_the_public_entry_takes_its_chunk_from_the_row():
    q, k, v, _ = _operands(1, 32, 2, 8)
    whole = L.lightning_attention(q, k, v)  # one chunk of 32
    assert _rel(L._in_chunks(q, k, v, None, 8), whole) < 1e-6


def test_a_state_carried_in_bfloat16_shows_in_float32(monkeypatch):
    q, k, v, w = _operands(1, 256, 2, 16, seed=3)
    sound, want = _both(q, k, v, w, chunk=16)
    monkeypatch.setattr(L, "_STATE_DTYPE", jnp.bfloat16)
    low, _ = _both(q, k, v, w, chunk=16)
    assert _rel(sound["o"], want["o"]) < 5e-6
    assert _rel(low["o"], want["o"]) > 2e-4


@pytest.mark.parametrize("T,want", [(5, 8), (37, 64), (128, 128), (200, 128), (16384, 128)])
def test_the_chunk_comes_from_the_row(T, want):
    assert L._chunk_len(T) == want


def test_the_heads_a_step_come_from_the_shapes():
    # the cell's shape: 32 heads of 128, chunks of 128, bfloat16
    assert L._heads_per_step(32, 128, 128, 2, True) == 8
    assert L._heads_per_step(32, 128, 128, 4, True) == 4  # float32: half as many
    assert L._heads_per_step(6, 128, 128, 2, True) == 6
    # a head that does not fill whole lanes: one block takes them all
    assert L._heads_per_step(4, 16, 16, 4, False) == 4


@pytest.mark.parametrize("remat,fwd", [("none", 1), ("selective", 1), ("full", 2)])
def test_the_backward_pass_reads_the_saved_states(remat, fwd, monkeypatch):
    """Under ``selective`` the chunk states and the output are kept: the
    lowered gradient runs the forward kernel once."""
    from ddl_tpu.models import remat as R

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    q = jax.ShapeDtypeStruct((1, 256, 2, 128), jnp.bfloat16)

    def loss(q, k, v):
        f = R.wrap(lambda q, k, v: L.lightning_attention(q, k, v) * 2, remat)
        return jnp.sum(f(q, k, v).astype(jnp.float32))

    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).trace(q, q, q).lower(
        lowering_platforms=("tpu",)).as_text()
    count = lambda name: text.count(f'kernel_name = "{name}"')
    assert count("ddl_lightning_fwd") == fwd
    assert count("ddl_lightning_bwd") == 1
