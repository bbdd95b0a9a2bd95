"""The scan's bytes from shapes, against a count by hand."""

import pytest

from chipbench.roofline import roofline_pct, scan_bytes


def test_bytes_by_hand_at_a_tiny_shape():
    # N=2 members, R=3 rows, T=5 ticks, T60=2 grid points, S=1 slot
    read = (2 * 3 * 2 * 8          # occupancy
            + 2 * 5 * 3 * 8        # alive mask + budget scale
            + 5 * (8 + 8 + 4 + 4)  # tick time, weight, grid index, tick
            + 3 * 8 + 22 * 8)      # row budgets, scalars
    write = 2 * 3 * 4 + 2 * 2 * 8 + 2 * 1 * 3 * 2 * 8
    kw = dict(N=2, R=3, T=5, T60=2, S=1)
    assert scan_bytes(**kw, keep_series=False, keep_fire=False) \
        == read + write
    assert scan_bytes(**kw, keep_series=False, keep_fire=True) \
        == read + write + 2 * 5 * 3
    assert scan_bytes(**kw, keep_series=True, keep_fire=True) \
        == read + write + 2 * 5 * 3 + 2 * 5 * 4 * 8


def test_roofline_share():
    assert roofline_pct(819e9, 2.0, 819e9) == pytest.approx(50.0)
