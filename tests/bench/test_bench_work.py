"""The fixed work count of a pass: the roofline's numerator."""
from __future__ import annotations

import pytest

from bench import work


@pytest.mark.parametrize("scale,columns,want", [
    (22, 1, 318_767_104),      # 2**22 ids, 16 edges each, one column
    (20, 8, 138_412_032),      # 2**20 ids, eight slot columns
])
def test_pass_bytes_worked_values(scale, columns, want):
    n = 1 << scale
    assert work.pass_bytes(n, 16 * n, columns) == want


def test_pass_bytes_of_the_graphalytics_dataset():
    """graph500-22 as Graphalytics publishes it: 2,396,657 vertices,
    each of its 64,155,735 undirected edges followed both ways."""
    assert work.pass_bytes(2_396_657, 2 * 64_155_735) == 542_005_764


def test_pass_bytes_depends_only_on_n_m_and_columns():
    assert work.pass_bytes(10, 100, 3) == 4 * 100 + 4 * 10 * 7


def test_roofline_pct_at_the_least_time_is_100():
    n, m, cols, bw = 1 << 22, 16 << 22, 1, 819e9
    least = work.pass_bytes(n, m, cols) / bw
    assert work.roofline_pct(n, m, cols, least, bw) == pytest.approx(100.0)
    assert work.roofline_pct(n, m, cols, 2 * least, bw) == pytest.approx(
        50.0)
