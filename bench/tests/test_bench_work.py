"""The work counts: MBR degrees and the bytes the filter needs."""
import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from harness import work  # noqa: E402


def test_mbr_degrees_match_a_loop():
    rng = np.random.default_rng(3)
    lo = rng.uniform(0, 1, (40, 2))
    r = np.concatenate([lo, lo + rng.uniform(0, 0.2, (40, 2))], axis=1)
    lo = rng.uniform(0, 1, (70, 2))
    s = np.concatenate([lo, lo + rng.uniform(0, 0.2, (70, 2))], axis=1)
    deg_r, deg_s = work.mbr_degrees(r, s)
    hit = np.array([[a[0] <= b[2] and b[0] <= a[2] and a[1] <= b[3]
                     and b[1] <= a[3] for b in s] for a in r])
    assert np.array_equal(deg_r, hit.sum(1))
    assert np.array_equal(deg_s, hit.sum(0))


def test_filter_bytes_count_candidate_lists_only():
    # R0 meets S0 and S1; R1 meets nothing; S2 meets nothing
    a_r, f_r = np.array([3, 100]), np.array([1, 50])
    a_s, f_s = np.array([2, 4, 1000]), np.array([0, 2, 500])
    deg_r, deg_s = np.array([2, 0]), np.array([1, 1, 0])
    got = work.filter_bytes((a_r, f_r), (a_s, f_s), deg_r, deg_s)
    # pair (0,0): 3+1+2+0, pair (0,1): 3+1+4+2 intervals, 8 bytes each
    assert got == 8 * ((3 + 1 + 2 + 0) + (3 + 1 + 4 + 2))


def test_padding_counts_nothing():
    # the same lists padded to any width need the same bytes: the count
    # reads list lengths, never a padded shape
    a, f = np.array([3]), np.array([1])
    one = work.filter_bytes((a, f), (a, f), np.array([1]), np.array([1]))
    assert one == 8 * 8


def test_filter_comparisons_read_each_list_per_merge():
    a_r, f_r = np.array([3]), np.array([1])
    a_s, f_s = np.array([2]), np.array([5])
    got = work.filter_comparisons((a_r, f_r), (a_s, f_s), np.array([1]),
                                  np.array([1]))
    # AA: 3+2, AF: 3+5, FA: 1+2
    assert got == 5 + 8 + 3
