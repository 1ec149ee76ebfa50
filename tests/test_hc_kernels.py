"""The hyper-connected wraps' ``ddl_hc_*`` kernels (``ops/hyper_connections.py``)
in Pallas' interpret mode on the CPU, pass by pass against XLA's passes of
``models/hyper_connections.py`` - the plain form they are held to - and the
shape rule that decides between the two (``tests/test_xing4.py`` holds the
whole wrap, either way, to ``jax.grad`` of the equations written out).
"""

import jax
import jax.numpy as jnp
import pytest

from ddl_tpu.models import hyper_connections as hc
from hcsupport import (
    HC_KERNELS, TILED, _wrap, close, kernel_names, plain_wrap, xla_passes)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
def test_each_kernel_is_its_xla_pass(dtype):
    """Pass by pass at a shape that takes the kernels: every output of
    ``ddl_hc_pre_fwd`` / ``_pre_bwd`` / ``_post_fwd`` / ``_post_bwd``
    (interpret mode) against XLA's pass on the same operands - the stream's
    rows to a rounding of their dtype, the float32 sums to float32's."""
    (Bn, Tn, C), n = TILED, 4
    wrap, settings = _wrap(n, C), hc.HyperConnections()
    keys = jax.random.split(jax.random.key(7), 6)
    X = jax.random.normal(keys[0], (Bn, n, Tn, C)).astype(dtype)
    y, dh = (jax.random.normal(k, (Bn, Tn, C)).astype(dtype) for k in keys[1:3])
    dXn = jax.random.normal(keys[3], X.shape).astype(dtype)
    row = 2e-2 if dtype == jnp.bfloat16 else 2e-5

    def passes():
        (h, post, res, _), (_, _, pre, p, ss) = hc._pre(X, wrap, settings, lambda v: v)
        dpost, dres = (jax.random.normal(k, v.shape) for k, v in zip(keys[4:], (post, res)))
        dX, dwrap = hc._hc_pre_bwd(X, wrap, pre, p, ss, dh, dpost, dres, dXn, settings)
        return {
            "h": (h, row), "p": (p, 2e-5), "ss": (ss, 2e-5), "Hpre": (pre, 2e-5),
            "X'": (hc._hc_post_fwd(X, y, post, res), row),
            "pre_bwd dX": (dX, row),
            **{"pre_bwd d" + k: (v, 5e-5) for k, v in dwrap.items()},
            **{"post_bwd " + k: (v, row if v.dtype == dtype else 2e-5) for k, v in zip(
                ("dX", "dy", "dHpost", "dHres"), hc._hc_post_bwd(X, y, post, res, dXn))},
        }

    got = passes()
    with xla_passes():
        want = passes()
    for name, (value, tol) in got.items():
        close(value, want[name][0], tol, name)


@pytest.mark.parametrize("shape,kernels", [
    ((1, 4, 128, 200), False), ((1, 4, 96, 128), False),
    ((1, 3, 128, 128), False), ((2, 2, 256, 384), True)],
    ids=["width-200", "length-96", "three-streams", "two-streams"])
def test_the_shape_decides_between_kernels_and_xlas_passes(shape, kernels):
    """The rule's two sides, read off the traced program: ``C`` a multiple of
    128, ``T`` of the token tile and ``2 n + n^2`` of the sublanes' 8 take the
    four kernels; a width, a length or a stream count off the grid takes XLA's
    passes - and either way the wrap is the plain form's (three rounds: the
    rule is not about them)."""
    Bn, n, Tn, C = shape
    wrap, settings = _wrap(n, C), hc.HyperConnections(n=n, iters=3)
    X = jax.random.normal(jax.random.key(9), shape)
    F = jnp.tanh

    def system(X, wrap):
        h, post, res, X = hc.hc_pre(X, wrap, settings)
        return hc.hc_post(X, F(h), post, res)

    value = lambda f: lambda *a: jnp.sum(f(*a) ** 2)
    assert hc._takes_kernels(X) == kernels
    assert kernel_names(jax.grad(value(system)), X, wrap) == (
        HC_KERNELS if kernels else set())
    plain = lambda X, wrap: plain_wrap(X, wrap, settings, F)
    close(system(X, wrap), plain(X, wrap), 2e-5, "X'")
    got = jax.grad(value(system), argnums=(0, 1))(X, wrap)
    want = jax.grad(value(plain), argnums=(0, 1))(X, wrap)
    for (path_, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                             jax.tree.leaves(want)):
        close(g, w, 5e-5, "d" + jax.tree_util.keystr(path_))
