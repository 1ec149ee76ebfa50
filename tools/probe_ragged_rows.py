#!/usr/bin/env python3
"""What XLA's ``jax.lax.ragged_dot`` does on the chip with the rows past
the last group: how long it takes with every row, an eighth of the rows and
no row in a group, whether the other rows of the result (and of the
transpose with respect to the rows) are zero, stale or NaN, and what the
N*k-row gather beside it costs.  It is what ``models/moe.py:ragged_experts``'
``held=`` masking rests on (PERF.md section 6, PR 30).

Then (PR 35) the transposes of ``ragged_experts``' two row moves at the
expert cells' shapes, N*k x 2048 bf16 for (N, k) = (16384, 8) and (16384,
6), with ``order`` the stable sort of seeded expert ids and ``inv`` its
inverse: the scatter-add JAX's autodiff emits for each, the same scatter
told ``unique_indices=True`` (the permutation only: the other collides k
times a row), and the gathers that are their equals — ``g[order]`` for the
un-permute, ``g[inv]`` summed over k in float32 for the copies — and the
backward combine from the (N, D) cotangent against the materialised (N, k,
D) one.  It is what ``moe._take_copies`` / ``moe._combine_copies`` rest on
(PERF.md section 6, PR 35).

Then (PR 40) a share's routed layer over a static bound of held rows
(``moe.held_row_bound``): at (N, k) = (16384, 8) with F = 1024 and (16384,
6) with F = 768, 16 of 128 experts held, a seeded router — the grouped
matmul at bounds around 2 x the balanced share (which row tile it likes),
the copies, the elementwise pass, the combine and both backward rules over
the first B sorted rows (``moe._head_copies`` / ``moe._head_combine``: the
B rows summed by token in a grouped matmul) against all N*k, the N*k slots
gathered out of a (B, D) source against ``rows[inv]``, and the whole layer, value and gradients: at full
width (``held=(0, 16)``, the parent's program), bounded (``held=(0, 16,
128)``: one ``lax.cond`` a pass, the bounded branch taken) and the same
program with every choice on a held expert (the fallback branch taken).

    chiprun -- python3 tools/probe_ragged_rows.py [--only-bounded]

Needs a TPU (``REHEARSE=1`` runs the control flow at a tiny size on the
CPU and proves nothing).
"""
import json, os, sys, time
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from ddl_tpu.bringup import bring_up
REH = bool(os.environ.get("REHEARSE"))
bring_up("cpu" if REH else None)
import jax, jax.numpy as jnp, numpy as np


def ms(fn, *args):
    """Mean host ms of 10 calls after one that compiles."""
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(10):
        y = fn(*args)
    jax.block_until_ready(y)
    return (time.perf_counter() - t0) / 10 * 1e3


def bounded_rows(out):
    """PR 40: the row passes over the first B sorted rows."""
    from ddl_tpu.models import moe

    N, E, G, D = (512, 128, 16, 64) if REH else (16384, 128, 16, 2048)
    for k, F in ((8, 32), (6, 24)) if REH else ((8, 1024), (6, 768)):
        M = N * k
        B = moe.held_row_bound(M, G, E)
        scores = jax.random.uniform(jax.random.key(40 + k), (N, E))
        top_w, top_e = jax.lax.top_k(scores, k)
        x = jax.random.normal(jax.random.key(k), (N, D), jnp.bfloat16)
        experts = {
            name: jax.random.normal(jax.random.key(i), shape, jnp.bfloat16) / 45.0
            for i, (name, shape) in enumerate(
                (("w_gate", (G, D, F)), ("w_up", (G, D, F)), ("w_down", (G, F, D))))
        }
        is_held = (top_e < G).reshape(-1)
        flat_e = jnp.where(is_held, top_e.reshape(-1), G)
        order = jnp.argsort(flat_e)
        inv = jnp.argsort(order)
        sizes = jnp.bincount(flat_e, length=G + 1).astype(jnp.int32)[:G]
        r = {"rows": M, "bound": B, "held_rows": int(jnp.sum(sizes)),
             "held_share": float(jnp.mean(is_held))}
        # Which bound the grouped matmul likes: the same groups, other B.
        dot = jax.jit(lambda xs, w, gs: jax.lax.ragged_dot(xs, w, gs))
        for extra in (0, 8, 128, 256, 512):
            xs = jnp.zeros((B + extra, D), jnp.bfloat16)
            r[f"ragged_dot_{B}+{extra}_rows_ms"] = ms(dot, xs, experts["w_gate"], sizes)
        r["ragged_dot_all_rows_ms"] = ms(
            dot, jnp.zeros((M, D), jnp.bfloat16), experts["w_gate"], sizes)
        g_rows = jax.random.normal(jax.random.key(9), (M, D), jnp.bfloat16)
        g_out = g_rows[:N]

        def both(name, fwd, primal, g):
            """Forward and cotangent of ``fwd`` at ``primal``, each in ms."""
            run = jax.jit(lambda p: jax.vjp(fwd, p))
            r[name + "_ms"] = ms(lambda p: run(p)[0], primal)
            r[name + "_T_ms"] = ms(jax.jit(lambda p, g: run(p)[1](g)[0]), primal, g)

        both("copies_all", lambda x: moe._take_copies(x, order, inv, k), x, g_rows)
        both("combine_all", lambda rows: moe._combine_copies(
            rows, top_w, order, inv, is_held), g_rows, g_out)
        head, live = order[:B], jnp.arange(B) < jnp.sum(sizes)
        by_token = jax.jit(lambda head, live: moe._by_token(head // k, live, N))
        r["by_token_sort_ms"] = ms(by_token, head, live)
        by_token = by_token(head, live)
        both("copies_bound", lambda x: moe._head_copies(
            x, head // k, live, by_token, N), x, g_rows[:B])
        both("combine_bound", lambda rows: moe._head_combine(
            rows, top_w, head, live, by_token), g_rows[:B], g_out)
        # The summing matmul alone: tokens a group, and what it writes.
        for lanes in (128, 256, 512):
            moe.TOKEN_LANES = lanes
            perm, lane, block_sizes = moe._by_token(head // k, live, N)
            hot = jnp.where(lane[:, None] == jnp.arange(lanes), 1, 0).astype(jnp.bfloat16)
            for out_dtype in (jnp.float32, jnp.bfloat16):
                r[f"sum_by_token_{lanes}_lanes_{jnp.dtype(out_dtype).name}_ms"] = ms(
                    jax.jit(lambda hot, rows, sizes: jax.lax.ragged_dot_general(
                        hot, rows, sizes, moe._SUM_BY_GROUP,
                        preferred_element_type=out_dtype)),
                    hot, g_rows[:B], block_sizes)
        moe.TOKEN_LANES = 128
        r["gather_bound_rows_ms"] = ms(
            jax.jit(lambda rows, perm: jnp.take(rows, perm, axis=0)), g_rows[:B], perm)
        r["slots_all_ms"] = ms(jax.jit(lambda rows: jnp.take(rows, inv, axis=0)), g_rows)
        r["slots_out_of_bound_rows_ms"] = ms(jax.jit(lambda rows: jnp.take(
            rows, jnp.minimum(inv, B - 1), axis=0)), g_rows[:B])
        for name, n_rows in (("all", M), ("bound", B)):
            r[f"silu_mul_{name}_ms"] = ms(
                jax.jit(lambda a, b: jax.nn.silu(a) * b),
                g_rows[:n_rows, :F], g_rows[:n_rows, F:2 * F])

        def layer(held):
            def loss(x, experts, top_e):
                out = moe.ragged_experts(x, experts, top_w, top_e, held=held)
                return jnp.sum(out.astype(jnp.float32) ** 2), out
            return jax.jit(jax.value_and_grad(loss, argnums=(0, 1), has_aux=True))

        every_choice_held = top_e % G
        for name, held, picks in (
                ("full_width", (0, G), top_e), ("bounded", (0, G, E), top_e),
                ("full_width_every_choice_held", (0, G), every_choice_held),
                ("fallback_every_choice_held", (0, G, E), every_choice_held)):
            r[f"layer_{name}_ms"] = ms(layer(held), x, experts, picks)
        same = [bool(jnp.all(a == b)) for a, b in zip(
            jax.tree.leaves(layer((0, G))(x, experts, top_e)),
            jax.tree.leaves(layer((0, G, E))(x, experts, top_e)))]
        r["bounded_bit_equal_to_full_width"] = same  # loss, out, d_x, 3 stacks
        out[f"bounded_{N}x{k}_F{F}"] = r


if "--only-bounded" in sys.argv:
    out = {}
    bounded_rows(out)
    print(json.dumps(out, indent=1))
    sys.exit(0)

M, D, F, G = (1024, 64, 32, 16) if REH else (131072, 2048, 1024, 16)
key = jax.random.key(0)
x = jax.random.normal(key, (M, D), jnp.bfloat16)
w = jax.random.normal(jax.random.key(1), (G, D, F), jnp.bfloat16) / 45.0
out = {}
f = jax.jit(lambda x, w, gs: jax.lax.ragged_dot(x, w, gs))
for name, per in (("all_rows", M // G), ("eighth", M // G // 8), ("none", 0)):
    gs = jnp.full((G,), per, jnp.int32)
    y = f(x, w, gs); y.block_until_ready()
    t0 = time.perf_counter()
    for _ in range(10):
        y = f(x, w, gs)
    y.block_until_ready()
    dt = (time.perf_counter() - t0) / 10
    tail = np.asarray(y[per * G:].astype(jnp.float32)) if per * G < M else np.zeros((1, 1))
    out[name] = {"ms": dt * 1e3, "rows_in_groups": per * G,
                 "tflops_on_grouped_rows": 2 * per * G * D * F / dt / 1e12 if per else 0,
                 "tail_all_zero": bool((tail == 0).all()), "tail_has_nan": bool(np.isnan(tail).any()),
                 "tail_abs_max": float(np.nanmax(np.abs(tail)))}
# same with NaN-poisoned fresh output buffers: fill memory with NaN first
big = jnp.full((M, F), jnp.nan, jnp.bfloat16); big.block_until_ready(); del big
gs = jnp.full((G,), M // G // 8, jnp.int32)
y = f(x, w, gs); tail = np.asarray(y[M // 8:].astype(jnp.float32))
out["eighth_after_nan_fill"] = {"tail_all_zero": bool((tail == 0).all()), "tail_has_nan": bool(np.isnan(tail).any())}
# transpose wrt lhs and rhs
g = jax.jit(jax.grad(lambda x, w, gs: jnp.sum(jax.lax.ragged_dot(x, w, gs).astype(jnp.float32)[: M // 8]), argnums=(0, 1)))
dx, dw = g(x, w, gs)
tail = np.asarray(dx[M // 8:].astype(jnp.float32))
out["grad_lhs_tail"] = {"all_zero": bool((tail == 0).all()), "has_nan": bool(np.isnan(tail).any())}
# gather timing: N*k rows
idx = jnp.asarray(np.random.default_rng(0).integers(0, M // 8, M), jnp.int32)
h = jax.jit(lambda x, idx: jnp.take(x, idx, axis=0))
y = h(x[: M // 8], idx); y.block_until_ready()
t0 = time.perf_counter()
for _ in range(10):
    y = h(x[: M // 8], idx)
y.block_until_ready()
out["gather_131072_rows_ms"] = (time.perf_counter() - t0) / 10 * 1e3


N, E = (128, 8) if REH else (16384, 64)
for k in (8, 6):
    rng = np.random.default_rng(k)
    order = jnp.argsort(jnp.asarray(rng.integers(0, E, N * k), jnp.int32))
    inv = jnp.argsort(order)
    g = jax.random.normal(jax.random.key(k), (N * k, D), jnp.bfloat16)
    d_out = g[:N]
    w = jax.random.uniform(jax.random.key(k + 1), (N, k), jnp.float32)
    r = {}
    # The un-permute per_slot = rows[inv]: d_rows[inv[j]] += g[j].
    as_jax = jax.jit(lambda g, inv: jax.vjp(lambda r: jnp.take(r, inv, axis=0), g)[1](g)[0])
    hinted = jax.jit(lambda g, inv: jnp.zeros_like(g).at[inv].add(g, unique_indices=True))
    gathered = jax.jit(lambda g, order: jnp.take(g, order, axis=0))
    want = np.asarray(as_jax(g, inv).astype(jnp.float32))
    for name, fn, idx in (("scatter_add", as_jax, inv), ("scatter_add_unique_indices", hinted, inv),
                          ("gather", gathered, order)):
        r["unpermute_T_" + name + "_ms"] = ms(fn, g, idx)
        r["unpermute_T_" + name + "_bit_equal"] = bool((np.asarray(fn(g, idx).astype(jnp.float32)) == want).all())
    # The copies xs = x[order // k]: d_x[order[i] // k] += g[i].
    as_jax = jax.jit(lambda g, order: jax.vjp(lambda x: jnp.take(x, order // k, axis=0), d_out)[1](g)[0])
    summed = jax.jit(lambda g, inv: jnp.sum(
        jnp.take(g, inv, axis=0).reshape(N, k, D), axis=1, dtype=jnp.float32).astype(g.dtype))
    exact = np.asarray(g.astype(jnp.float32))[np.asarray(inv)].reshape(N, k, D).sum(1)
    for name, fn, idx in (("scatter_add", as_jax, order), ("gather_sum_f32", summed, inv)):
        r["copies_T_" + name + "_ms"] = ms(fn, g, idx)
        r["copies_T_" + name + "_max_err_vs_f32"] = float(np.abs(np.asarray(fn(g, idx).astype(jnp.float32)) - exact).max())
    # The backward combine: d_rows[i] = w_flat[order[i]] * d_out[order[i] // k].
    materialised = jax.jit(lambda d, w, order: jnp.take(
        (w.astype(d.dtype)[:, :, None] * d[:, None, :]).reshape(N * k, D), order, axis=0))
    from_nd = jax.jit(lambda d, w, order: jnp.take(w.reshape(-1), order).astype(d.dtype)[:, None]
                      * jnp.take(d, order // k, axis=0))
    r["combine_T_materialised_then_gather_ms"] = ms(materialised, d_out, w, order)
    r["combine_T_gather_from_nd_ms"] = ms(from_nd, d_out, w, order)
    r["combine_T_bit_equal"] = bool((np.asarray(materialised(d_out, w, order).astype(jnp.float32))
                                     == np.asarray(from_nd(d_out, w, order).astype(jnp.float32))).all())
    r["forward_take_copies_ms"] = ms(jax.jit(lambda x, order: jnp.take(x, order // k, axis=0)), d_out, order)
    r["forward_unpermute_ms"] = ms(gathered, g, inv)
    r["MB_of_rows"] = N * k * D * 2 / 1e6
    out[f"transposes_{N * k}x{D}"] = r
bounded_rows(out)
print(json.dumps(out, indent=1))
