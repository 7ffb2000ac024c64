"""The work a join needs, counted from its inputs: MBR candidate degrees,
and the bytes and comparisons of the APRIL intermediate filter.

The filter needs, for each candidate pair, the A and F interval lists of
both objects, and nothing else: an interval is two int32 endpoints, 8
bytes. Pairs that are not candidates and the padding of any
implementation count for nothing, so the count reads the same whatever
implements the filter.
"""
from __future__ import annotations

import numpy as np

#: bytes of one interval: start and inclusive last, int32 each
INTERVAL_BYTES = 8
#: bound on the [rows, |S|] MBR test of one block
_BLOCK_ELEMS = 1 << 24


def mbr_degrees(mbrs_r: np.ndarray, mbrs_s: np.ndarray):
    """(deg_r [|R|], deg_s [|S|]): how many closed-MBR candidates each
    object has, by brute force in blocks of R rows."""
    deg_r = np.zeros(len(mbrs_r), np.int64)
    deg_s = np.zeros(len(mbrs_s), np.int64)
    step = max(1, _BLOCK_ELEMS // max(1, len(mbrs_s)))
    for i in range(0, len(mbrs_r), step):
        r = mbrs_r[i:i + step]
        hit = ((mbrs_s[None, :, 0] <= r[:, None, 2])
               & (mbrs_s[None, :, 2] >= r[:, None, 0])
               & (mbrs_s[None, :, 1] <= r[:, None, 3])
               & (mbrs_s[None, :, 3] >= r[:, None, 1]))
        deg_r[i:i + step] = hit.sum(axis=1)
        deg_s += hit.sum(axis=0)
    return deg_r, deg_s


def filter_bytes(lists_r, lists_s, deg_r, deg_s) -> int:
    """Bytes the filter needs over all candidates. ``lists_*`` are the
    per-object interval counts (A, F) of each side."""
    per_r = sum(np.asarray(c, np.int64) for c in lists_r)
    per_s = sum(np.asarray(c, np.int64) for c in lists_s)
    return INTERVAL_BYTES * int(per_r @ deg_r + per_s @ deg_s)


def filter_comparisons(lists_r, lists_s, deg_r, deg_s) -> int:
    """Endpoint comparisons of the three linear merges (AA, AF, FA) over
    all candidates: each merge reads both lists once."""
    (a_r, f_r), (a_s, f_s) = lists_r, lists_s
    per_r = 2 * np.asarray(a_r, np.int64) + np.asarray(f_r, np.int64)
    per_s = 2 * np.asarray(a_s, np.int64) + np.asarray(f_s, np.int64)
    return int(per_r @ deg_r + per_s @ deg_s)
