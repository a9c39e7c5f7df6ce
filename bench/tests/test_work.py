"""The work counts equal their formulas at order 7, and the peak table
refuses a device it does not hold."""

import pytest

import work


def test_volume_and_flux_counts_at_order_7():
    M, V, A, F, W = 8, 512, 64, 9, 4
    assert work.kernel_work("dg_volume", 7) == (
        3 * F * V * 6 + 3 * F * V * 2 * M + F * V * 2, 2 * F * V * W + 3 * W)
    assert work.kernel_work("dg_volume", 7) == (313344.0, 36876.0)
    assert work.kernel_work("dg_flux", 7) == (3 * A * 170, 3 * A * 2 * F * W * 2)
    assert work.kernel_work("dg_flux", 7) == (32640.0, 27648.0)


def test_least_time_is_bound_by_bytes_on_a_v5e():
    # one rhs of 8192 order-7 elements: 36876 B x 8192 / 819e9 B/s
    t, bound = work.least_seconds("dg_volume", 7, 8192, 1, "TPU v5 lite")
    assert bound == "bytes" and t == pytest.approx(36876 * 8192 / 819e9)
    t, bound = work.least_seconds("dg_flux", 7, 8192, 5, "TPU v5 lite")
    assert bound == "bytes" and t == pytest.approx(27648 * 8192 * 5 / 819e9)


def test_unknown_device_and_kernel_are_errors():
    with pytest.raises(KeyError):
        work.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        work.kernel_work("dg_lift", 7)
