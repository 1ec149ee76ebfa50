"""The block selection and the sparse flash kernels
(``ops/sparse_attention.py``) against plain forms, on the CPU (Pallas'
interpret mode)."""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddl_tpu.ops import sparse_attention as S
import reference_minicpm_sala as reference

B, T, H, G, D = 1, 200, 4, 2, 16
SC = S.SparseConfig(
    block=16, kernel=8, stride=4, topk=4, init_blocks=1, local_blocks=2,
)
NB = -(-T // SC.block)


@pytest.fixture(scope="module", autouse=True)
def tiles_of_32():
    """Several tiles in a row of 200 positions: the module's tiles are
    constants of the kernels, set for the chip."""
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(S, "_TILE_Q", 32)
        patch.setattr(S, "_TILE_K", 32)
        yield


def _config(**kw):
    return reference.Config(
        n_heads=H, n_kv_heads=G, head_dim=D, lightning_heads=H,
        lightning_head_dim=D, sparse_layers=(True,), block=SC.block,
        kernel=SC.kernel, stride=SC.stride, topk=SC.topk,
        init_blocks=SC.init_blocks, local_blocks=SC.local_blocks, dense_len=64,
        query_block=64, **kw,
    )


@pytest.fixture(scope="module")
def operands():
    rng = np.random.default_rng(0)
    normal = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)
    return normal(B, T, H, D), normal(B, T, G, D), normal(B, T, G, D), normal(B, T, H, D)


@pytest.fixture(scope="module")
def scores(operands):
    q, k = operands[:2]
    with jax.default_matmul_precision("highest"):
        return S.block_scores(q, k, SC), reference.block_scores(q, k, _config())


@pytest.fixture(scope="module")
def selection(scores):
    return S.make_selection(S.visible_blocks(scores[0], SC), SC, jnp.float32)


def _seen(selection):
    return np.asarray(selection.visible[:, :, :T, :NB]) > 0.5  # (B, G, T, blocks)


def test_the_block_scores_are_the_references(scores):
    got, want = scores
    assert got.shape == (B, G, T, NB)
    np.testing.assert_allclose(got, jnp.moveaxis(want, 1, 2), atol=2e-6)
    # a sum of a group's softmaxes: a query's best block holds at least the
    # group's share of one compressed key among those in its past
    assert float(jnp.max(got)) <= H // G + 1e-5 and float(jnp.min(got)) >= 0.0


def test_the_kernel_never_looks_ahead(operands):
    """A block's score for a query does not move with keys at or behind the
    query: every compressed key it reads lies wholly in the past."""
    q, k = operands[:2]
    cut = 120
    later = k.at[:, cut:].set(7.0)
    a, b = S.block_scores(q, k, SC), S.block_scores(q, later, SC)
    np.testing.assert_array_equal(a[:, :, :cut], b[:, :, :cut])


def test_the_picks_are_the_references(scores):
    got = S.visible_blocks(scores[0], SC)
    want = reference.visible_blocks(jnp.moveaxis(scores[0], 1, 2), _config())
    np.testing.assert_array_equal(got, jnp.moveaxis(want, 1, 2))


def test_what_a_query_always_sees_and_never_sees(selection):
    seen = _seen(selection)
    own = np.arange(T) // SC.block
    b = np.arange(NB)
    # no block of the future, ever
    assert not seen[..., b[None, :] > own[:, None]].any()
    # the initial block and the local blocks, always
    assert seen[:, :, :, 0].all()
    local = (b[None, :] <= own[:, None]) & (own[:, None] - b[None, :] < SC.local_blocks)
    assert seen[..., local].all()
    # and topk - init_blocks more where the past holds as many
    counts = seen.sum(-1)
    want = np.minimum(own + 1, SC.topk + SC.local_blocks)
    np.testing.assert_array_equal(counts, np.broadcast_to(want, counts.shape))


def test_a_groups_heads_share_one_list(selection):
    # one row of ``visible`` a key-value group: the kernels' query tile is
    # the group's heads, and there is no per-head list to differ
    assert selection.visible.shape[:2] == (B, G)
    assert not np.array_equal(_seen(selection)[:, 0], _seen(selection)[:, 1])


def test_ties_go_to_the_lower_block():
    flat = jnp.ones((1, 1, T, NB), jnp.float32)
    seen = np.asarray(S.visible_blocks(flat, SC))[0, 0]
    last = seen[T - 1]
    first_open = SC.init_blocks
    picks = SC.topk - SC.init_blocks
    assert last[first_open : first_open + picks].all()
    assert not last[first_open + picks : NB - SC.local_blocks].any()


def _lists(selection):
    """{(group, tile): the blocks its list names}, {(group, key tile): the
    query tiles its list names}, from the packed words."""
    tq, tk, Tp = S._tiles(T, B * G, SC)
    nq, nk, nbp = Tp // tq, Tp // tk, Tp // SC.block
    steps = -(-nbp // 2)
    words = np.asarray(selection.lists).reshape(B * G, nq, steps)
    counts = np.asarray(selection.counts).reshape(B * G, nq)
    blocks = np.stack([words & 0xFFFF, words >> 16], -1).reshape(B * G, nq, -1)
    fwd = {(g, i): blocks[g, i, : counts[g, i]] for g in range(B * G) for i in range(nq)}
    lists_t = np.asarray(selection.lists_t).reshape(B * G, nk, nq)
    counts_t = np.asarray(selection.counts_t).reshape(B * G, nk)
    bwd = {(g, c): lists_t[g, c, : counts_t[g, c]] for g in range(B * G) for c in range(nk)}
    return fwd, bwd, blocks, lists_t, (tq, tk, nq, nk, steps)


def test_a_tiles_list_is_the_union_of_its_positions_picks(selection):
    fwd, bwd, blocks, lists_t, (tq, tk, nq, nk, steps) = _lists(selection)
    padded = np.asarray(selection.visible) > 0.5
    for (g, i), listed in fwd.items():
        union = np.flatnonzero(padded[0, g, i * tq : (i + 1) * tq].any(0))
        np.testing.assert_array_equal(listed, union)
        # behind the list's end every step repeats its last entry: the
        # index map moves nowhere, so no block is fetched
        assert (blocks[g, i, len(listed):] == listed[-1]).all()
    per = tk // SC.block
    for (g, c), tiles in bwd.items():
        want = [i for i in range(nq)
                if padded[0, g, i * tq : (i + 1) * tq, c * per : (c + 1) * per].any()]
        np.testing.assert_array_equal(tiles, want)
        assert (lists_t[g, c, len(tiles):] == (tiles[-1] if len(tiles) else 0)).all()


def test_the_forward_and_dq_index_maps_read_only_listed_blocks(selection, operands):
    """The key blocks the forward / dq grids fetch, read off the block
    specs' index maps over the whole grid, are the tiles' lists and nothing
    else."""
    fwd, _, _, _, (tq, tk, nq, nk, steps) = _lists(selection)
    nbp = S._tiles(T, B * G, SC)[2] // SC.block
    _, listed, _, _ = S._list_specs(G, H // G, D, tq, nq, nbp, SC.block, steps)
    lists, counts = np.asarray(selection.lists), np.asarray(selection.counts)
    for g in range(G):
        for i in range(nq):
            fetched = set()
            for j in range(steps):
                for spec in listed[:2]:
                    fetched.add(int(spec.index_map(0, g, i, j, lists, counts)[2]))
            assert fetched == set(fwd[(g, i)].tolist()), (g, i)
    # and a tile that lists fewer blocks than the row holds leaves steps idle
    assert min(len(v) for v in fwd.values()) < nbp


@pytest.mark.parametrize("groups,tile_q", [(2, 128), (8, 128), (16, 256), (32, 512)])
def test_the_query_tile_grows_until_the_lists_fit_scalar_memory(groups, tile_q, monkeypatch):
    """The cell's shape, and a selection a head (32 lists a row): words of
    lists a call prefetches stay under the budget."""
    monkeypatch.setattr(S, "_TILE_Q", 128)
    monkeypatch.setattr(S, "_TILE_K", 128)
    sc = S.SparseConfig()
    tq, tk, Tp = S._tiles(16384, groups, sc)
    assert (tq, tk, Tp) == (tile_q, 128, 16384)
    words = groups * (Tp // tq) * max(Tp // sc.block // 2, Tp // tk)
    assert 4 * words <= S._SMEM_BUDGET
    # a short row is one tile of whole blocks
    assert S._tiles(100, groups, sc) == (128, 128, 128)


NAMES = ("o", "dq", "dk", "dv")


@pytest.fixture(scope="module")
def both(operands, selection):
    q, k, v, w = operands
    seen = jnp.moveaxis(jnp.asarray(_seen(selection)), 1, 2)

    def run(f):
        o, pull = jax.vjp(f, q, k, v)
        return dict(zip(NAMES, (o,) + pull(w)))

    with jax.default_matmul_precision("highest"):
        return (
            run(lambda q, k, v: S.sparse_attention(q, k, v, selection)),
            run(lambda q, k, v: S.attention_dense(q, k, v, selection)),
            run(lambda q, k, v: reference.sparse_attention(q, k, v, seen, SC.block, 64)),
        )


@pytest.mark.parametrize("name", NAMES)
def test_the_kernels_are_the_masked_softmax(both, name):
    kernels, dense, plain = both
    np.testing.assert_allclose(kernels[name], plain[name], atol=5e-6)
    np.testing.assert_allclose(dense[name], plain[name], atol=5e-6)


@pytest.mark.parametrize("tile_q,tile_k", [(16, 16), (64, 32), (32, 64)])
def test_the_tiles_change_nothing(operands, scores, both, tile_q, tile_k, monkeypatch):
    q, k, v, w = operands
    monkeypatch.setattr(S, "_TILE_Q", tile_q)
    monkeypatch.setattr(S, "_TILE_K", tile_k)
    assert S._tiles(T, B * G, SC)[:2] == (tile_q, tile_k)
    sel = S.make_selection(S.visible_blocks(scores[0], SC), SC, jnp.float32)
    with jax.default_matmul_precision("highest"):
        o, pull = jax.vjp(lambda q, k, v: S.sparse_attention(q, k, v, sel), q, k, v)
        got = dict(zip(NAMES, (o,) + pull(w)))
    for name in NAMES:
        np.testing.assert_allclose(got[name], both[2][name], atol=5e-6)


def test_bfloat16_operands_stay_on_the_masked_softmax(operands, selection, both):
    q, k, v, _ = (x.astype(jnp.bfloat16) for x in operands)
    sel = dataclasses.replace(selection, visible=selection.visible.astype(jnp.bfloat16))
    got = S.sparse_attention(q, k, v, sel)
    assert got.dtype == jnp.bfloat16
    err = jnp.sqrt(jnp.mean((got.astype(jnp.float32) - both[2]["o"]) ** 2))
    assert float(err / jnp.sqrt(jnp.mean(both[2]["o"] ** 2))) < 2e-2


def test_no_gradient_flows_through_the_selection(operands):
    q, k, v, w = operands

    def loss(q, k, v):
        sel = S.select_blocks(q, k, SC)
        return jnp.sum(S.attention_dense(q, k, v, sel) * w)

    def given(q, k, v, sel):
        return jnp.sum(S.attention_dense(q, k, v, sel) * w)

    sel = S.select_blocks(q, k, SC)
    got = jax.grad(loss, argnums=(0, 1))(q, k, v)
    want = jax.grad(given, argnums=(0, 1))(q, k, v, sel)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)


def test_the_selection_never_writes_per_head_scores(monkeypatch):
    """The lowered forward at a long row holds no array of T x T/stride x
    heads elements, nor of T x T/stride x groups: the scores of the
    compressed keys stay in the kernel."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    T_, H_, G_, D_ = 4096, 8, 2, 128
    sc = S.SparseConfig()
    q = jax.ShapeDtypeStruct((1, T_, H_, D_), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, T_, G_, D_), jnp.bfloat16)

    def forward(q, k, v):
        return S.sparse_attention(q, k, v, S.select_blocks(q, k, sc))

    text = jax.jit(forward).trace(q, kv, kv).lower(lowering_platforms=("tpu",)).as_text()
    M = (T_ - sc.kernel) // sc.stride + 1
    sizes = set()
    for shape in re.findall(r"tensor<([0-9x]+)x[a-z]+[0-9]+>", text):
        sizes.add(int(np.prod([int(n) for n in shape.split("x")])))
    # nothing as large as the heads' scores, and nothing of the scores' or
    # their group sums' size (q itself, T x H x D, is the largest array)
    assert sizes and max(sizes) < T_ * M * H_, max(sizes)
    for per_key in (M, T_ // sc.stride):
        assert not {T_ * per_key * G_, T_ * per_key * H_} & sizes
    assert T_ * 128 * G_ in sizes  # the block scores' lane-padded rows
    names = set(re.findall(r'kernel_name = "(ddl_\w+)"', text))
    assert names == {"ddl_sparse_select", "ddl_flash_sparse_fwd"}, names


@pytest.mark.parametrize("remat,fwd", [("none", 1), ("selective", 1), ("full", 2)])
def test_the_backward_pass_reads_the_saved_lists(remat, fwd, monkeypatch):
    """Under ``selective`` the output, the logsumexp and the lists are kept:
    the lowered gradient selects once and runs the forward kernel once."""
    import collections

    from ddl_tpu.models import remat as R

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    sc = S.SparseConfig()
    q = jax.ShapeDtypeStruct((1, 1024, 8, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 1024, 2, 128), jnp.bfloat16)

    def loss(q, k, v):
        f = R.wrap(
            lambda q, k, v: S.sparse_attention(q, k, v, S.select_blocks(q, k, sc)) * 2,
            remat,
        )
        return jnp.sum(f(q, k, v).astype(jnp.float32))

    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).trace(q, kv, kv).lower(
        lowering_platforms=("tpu",)).as_text()
    got = collections.Counter(re.findall(r'kernel_name = "(ddl_\w+)"', text))
    assert got["ddl_sparse_select"] == fwd and got["ddl_flash_sparse_fwd"] == fwd, got
    assert got["ddl_flash_sparse_bwd_dq"] == 1 and got["ddl_flash_sparse_bwd_dkv"] == 1, got
