"""What the hyper-connected wraps' tests share (``tests/test_xing4.py``,
``tests/test_hc_kernels.py``): a seeded wrap off its near-identity start, one
wrap written out for ``jax.grad``, the switch to XLA's passes at a shape that
takes the ``ddl_hc_*`` kernels, and the comparison.
"""

import contextlib
import re
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np

from ddl_tpu.models import decoder
from ddl_tpu.models import hyper_connections as hc


def close(got, want, tol, what, floor=0.0):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    scale = max(float(np.max(np.abs(want))), floor, 1e-30)
    err = float(np.max(np.abs(got - want))) / scale
    assert err <= tol, f"{what}: {err:.3g} of the largest magnitude, limit {tol}"


def _wrap(n=4, C=24, seed=0, off=0.2):
    keys = iter(jax.random.split(jax.random.key(seed), 32))
    wrap = {}
    for row in hc.wrap_rows("w", n, C):
        name = row.name.split(".")[1]
        if row.fill is not None:
            value = jnp.full(row.shape, row.fill, row.dtype)
        elif row.draw is not None:
            value = row.draw(next(keys), row.shape)
        else:
            value = decoder.dense_init(next(keys), row.shape[-2], row.shape, row.dtype)
        wrap[name] = 30.0 * value if name.startswith("alpha") else (
            value + off * jax.random.normal(next(keys), value.shape))
    return wrap


def plain_wrap(X, wrap, settings, F):
    """One wrap written out for ``jax.grad`` - no ``custom_vjp``, the float32
    matmuls at ``highest``: what the routines are held to."""
    Bx, n, Tx, C = X.shape
    Xf = X.astype(jnp.float32)
    flat = jnp.moveaxis(Xf, 1, 2).reshape(Bx, Tx, n * C)
    xb = flat * jax.lax.rsqrt(
        jnp.mean(flat * flat, axis=-1, keepdims=True) + settings.norm_eps
    ) * wrap["norm"].astype(jnp.float32)
    phi = jnp.concatenate(
        [wrap["phi_pre"], wrap["phi_post"], wrap["phi_res"]], axis=-1)
    z = jnp.einsum("btk,km->bmt", xb, phi.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST)
    pre, post, res = hc.matrices(z, wrap, settings)
    h = jnp.einsum("bit,bitc->btc", pre, Xf).astype(X.dtype)
    y = F(h).astype(jnp.float32)
    out = jnp.einsum("bijt,bjtc->bitc", res, Xf) + post[..., None] * y[:, None]
    return out.astype(X.dtype)


def kernel_names(fn, *args):
    """The ``ddl_hc_*`` kernels in ``fn``'s traced program."""
    return set(re.findall(r"ddl_hc_\w+", str(jax.make_jaxpr(fn)(*args))))


HC_KERNELS = {"ddl_hc_pre_fwd", "ddl_hc_pre_bwd", "ddl_hc_post_fwd", "ddl_hc_post_bwd"}
#: (B, T, C): the tier-1 toy wrap, off the lanes' grid; two token tiles of
#: two lanes' width, which takes the kernels.
TOY, TILED = (2, 11, 24), (2, 256, 256)


@contextlib.contextmanager
def xla_passes():
    """XLA's passes at a shape that takes the kernels: the rule answered no,
    and the passes that are jitted by name traced through the bare functions
    (JAX keeps a jitted function's traces)."""
    with contextlib.ExitStack() as stack:
        stack.enter_context(mock.patch.object(hc, "_takes_kernels", lambda X: False))
        for name in ("_hc_pre_bwd", "_hc_post_fwd", "_hc_post_bwd"):
            stack.enter_context(
                mock.patch.object(hc, name, getattr(hc, name).__wrapped__))
        yield
