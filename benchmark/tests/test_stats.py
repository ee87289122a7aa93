import numpy as np
import pytest

from benchmark.lib import stats


@pytest.mark.parametrize("q", [0, 25, 50, 90, 95, 100])
@pytest.mark.parametrize("n", [1, 2, 7, 100])
def test_percentile_is_numpys_linear_interpolation(q, n):
    xs = list(np.random.default_rng(n).normal(size=n))
    assert stats.percentile(xs, q) == pytest.approx(np.percentile(xs, q))


def test_percentile_of_nothing_and_bad_q():
    assert stats.percentile([], 90) is None
    with pytest.raises(ValueError):
        stats.percentile([1.0], 101)


def test_window_is_half_open():
    assert stats.in_window([0.9, 1.0, 1.5, 2.0], 1.0, 2.0) == [1.0, 1.5]


def test_gap_belongs_to_its_later_token():
    stamps = [0.5, 1.1, 1.4, 2.2]
    # 0.5→1.1 ends inside [1, 2); 1.4→2.2 ends outside
    assert stats.gaps_in_window(stamps, 1.0, 2.0) == pytest.approx(
        [0.6, 0.3])


def test_iqr_share_is_the_contracts():
    import statistics
    xs = [10.0, 10.2, 9.9, 10.1, 10.4, 9.8]
    q1, _, q3 = statistics.quantiles(xs, n=4)
    assert stats.iqr_share(xs) == pytest.approx(
        (q3 - q1) / statistics.median(xs))
