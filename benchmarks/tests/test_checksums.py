"""Checksum regeneration catches a swapped, duplicated or altered row,
and the device's sum is the host's."""

import numpy as np
import pytest

from benchmarks.lib import producers as P

MIX = {"rows": "image_pool", "pool_rows": 16, "window_rows": 8, "name": "t"}
SIZES = {"row_values": 49, "n_classes": 10}
TOKENS = {"rows": "tokens", "window_rows": 4, "seq": 32, "name": "t"}
TSIZES = {"seq": 32, "vocab": 256}


def test_windows_are_functions_of_seed_producer_iteration():
    a = P.host_window(TOKENS, TSIZES, 7, 1, 3)
    assert np.array_equal(a, P.host_window(TOKENS, TSIZES, 7, 1, 3))
    assert not np.array_equal(a, P.host_window(TOKENS, TSIZES, 8, 1, 3))
    assert not np.array_equal(a, P.host_window(TOKENS, TSIZES, 7, 2, 3))
    assert not np.array_equal(a, P.host_window(TOKENS, TSIZES, 7, 1, 4))


def test_each_pool_row_is_served_once_per_pass():
    for p in range(3):
        served = np.concatenate([
            P.image_window_rows(5, 1, 2 * p + k, 16, 8) for k in range(2)
        ])
        assert sorted(served) == list(range(16))
    assert not np.array_equal(
        P.image_window_rows(5, 1, 0, 16, 8), P.image_window_rows(5, 1, 2, 16, 8)
    )


@pytest.mark.parametrize("defect", ["swapped", "duplicated", "altered", "permuted_in_row"])
def test_a_defective_window_does_not_check_out(defect):
    win = P.host_window(MIX, SIZES, 3, 1, 0)
    want = P.row_checksums(win)
    bad = win.copy()
    if defect == "swapped":
        bad[[0, 1]] = bad[[1, 0]]
    elif defect == "duplicated":
        bad[1] = bad[0]
    elif defect == "altered":
        bad[2, 5] = np.nextafter(bad[2, 5], np.float32(2))
    else:
        bad[3, [0, 1]] = bad[3, [1, 0]]
    assert np.array_equal(P.row_checksums(win), want)
    assert not np.array_equal(P.row_checksums(bad), want)


def test_producer_pool_checksums_are_the_windows(tmp_path):
    prod = P.make_producer(MIX, SIZES, 3, str(tmp_path))
    prod.on_init(producer_idx=1)
    want = P.expected_checksums(MIX, SIZES, 3, 1, 4, str(tmp_path))
    buf = np.empty((8, 49), np.float32)
    for it in range(4):
        prod.execute_function(buf, iteration=it)
        assert np.array_equal(P.row_checksums(buf), want[it])
        assert np.array_equal(buf, P.host_window(MIX, SIZES, 3, 1, it))
        # The label rides as the last float32 of the row.
        assert set(buf[:, -1]) <= set(np.arange(10, dtype=np.float32))


def test_the_device_sum_is_the_host_sum():
    from benchmarks.run import WindowHook
    from ddl_tpu.observability import Metrics

    hook = WindowHook(Metrics())
    for mix, sizes in ((MIX, SIZES), (TOKENS, TSIZES)):
        win = P.host_window(mix, sizes, 1, 1, 0)
        steps = win.reshape(2, win.shape[0] // 2, -1)
        import jax.numpy as jnp

        out = hook(jnp.asarray(steps))
        assert out.shape == steps.shape
        assert np.array_equal(np.asarray(hook.sums[-1]), P.row_checksums(win))


def test_an_unknown_row_kind_is_refused():
    with pytest.raises(ValueError, match="the generator knows"):
        P.make_producer({"rows": "video", "name": "x"}, {}, 0, "")
