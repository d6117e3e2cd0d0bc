"""Rate and percentile arithmetic over what the window served."""
import _paths  # noqa: F401
import numpy as np
import pytest

from stats import Served, inter_token_gaps, iqr_share, percentile, rate, tokens_in


def _served():
    a = Served(0, token_times=[0.5, 0.6, 0.8])
    b = Served(1, token_times=[2.0, 2.5])
    c = Served(2)                                             # no token yet
    d = Served(3, token_times=[3.5], failed=True)
    return [a, b, c, d]


def test_rate_counts_tokens_inside_the_window():
    s = _served()
    assert tokens_in(s, 0.55, 2.5) == 4           # 0.6, 0.8, 2.0, 2.5
    assert rate(s, 0.5, 2.5) == pytest.approx(4 / 2.0)


def test_percentile_is_linear_interpolation():
    v = [1.0, 2.0, 3.0, 4.0, 10.0]
    assert percentile(v, 90) == pytest.approx(float(np.percentile(v, 90)))
    assert percentile(v, 90) == pytest.approx(4.0 + 0.6 * 6.0)
    assert percentile(v, 50) == pytest.approx(3.0)
    assert percentile([], 50) is None


def test_inter_token_gaps_of_tokens_in_window():
    assert inter_token_gaps(_served(), 0.0, 2.6) == pytest.approx([0.1, 0.2, 0.5])
    assert inter_token_gaps(_served(), 0.7, 2.6) == pytest.approx([0.2, 0.5])


def test_iqr_share_uses_statistics_quantiles():
    assert iqr_share([1.0, 2.0, 3.0, 4.0, 5.0]) == pytest.approx((4.5 - 1.5) / 3.0)
