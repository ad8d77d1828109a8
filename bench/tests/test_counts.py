import math

import pytest

from bench import flops, openloop, peaks


def test_peaks_table_is_keyed_by_device_kind_and_unknown_is_an_error():
    p = peaks.chip_peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9 and p["bf16_flops_per_s"] == 197e12
    for kind in ("cpu", "TPU v4", ""):
        with pytest.raises(KeyError):
            peaks.chip_peaks(kind)


def test_flops_and_least_bytes_count_the_input_not_the_format():
    assert flops.spmv_flops(29_791_000) == 59_582_000
    assert flops.spmv_flops(10, nvec=8) == 160
    n = 1_124_864
    assert flops.spmv_least_bytes(29_791_000, n, n, 4) == \
        29_791_000 * 4 + 2 * n * 4
    assert flops.spmv_least_bytes(100, 10, 20, 2, nvec=3) == 200 + 30 * 4 * 3


def test_least_seconds_is_the_larger_bound():
    p = peaks.chip_peaks("TPU v5 lite")
    t = flops.spmv_least_seconds(29_791_000, 1_124_864, 1_124_864, 4, 1, p)
    assert t == pytest.approx(128_162_912 / 819e9)
    dense = {"hbm_bytes_per_s": 1e15, "bf16_flops_per_s": 1e9}
    assert flops.spmv_least_seconds(1000, 10, 10, 4, 1, dense) == \
        pytest.approx(2000 / 1e9)


@pytest.mark.parametrize("rate,seconds", [(10.0, 40.0), (2.5, 3.0),
                                          (0.01, 1.0)])
def test_arrivals_fixed_count_inside_the_window(rate, seconds):
    due = openloop.arrivals(rate, seconds, seed=2 ** 31 + 7)
    assert due.shape[0] == max(1, round(rate * seconds))
    assert due[0] > 0 and due[-1] < seconds
    assert all(b > a for a, b in zip(due, due[1:]))


def test_arrivals_same_gaps_in_another_order_per_seed():
    a = openloop.arrivals(10.0, 40.0, seed=1)
    b = openloop.arrivals(10.0, 40.0, seed=2 ** 40 + 3)
    ga, gb = [a[0], *(a[1:] - a[:-1])], [b[0], *(b[1:] - b[:-1])]
    assert sorted(ga) == pytest.approx(sorted(gb))
    assert list(a) != list(b)
    assert list(a) == list(openloop.arrivals(10.0, 40.0, seed=1))
    # the gaps are exponential quantiles: mean 1/rate, heavy-ish tail
    assert sum(ga) / len(ga) == pytest.approx(0.1, rel=0.01)
    assert max(ga) > 5 * 0.1


def test_percentile_is_the_exact_nearest_rank():
    xs = list(range(1, 101))
    assert openloop.percentile(xs, 95) == 95
    assert openloop.percentile(xs, 50) == 50
    assert openloop.percentile([3.0], 95) == 3.0
    assert openloop.percentile([5, 1, 4, 2, 3], 50) == 3
    assert openloop.percentile([1, 2, math.inf], 95) == math.inf
    with pytest.raises(ValueError):
        openloop.percentile([], 50)


def test_lateness_summary_in_ms():
    s = openloop.lateness_summary([0.001] * 19 + [0.5])
    assert s["p50_ms"] == pytest.approx(1.0)
    assert s["p95_ms"] == pytest.approx(1.0)
    assert s["max_ms"] == pytest.approx(500.0)
