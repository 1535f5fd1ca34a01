"""Reference equi-join: every (S row, T row) pair with equal keys.

The answer is a set of row-id pairs; a join is exact when it holds each
reference pair once and nothing else.
"""
from __future__ import annotations

import numpy as np


def join_reference(s_keys, t_keys) -> np.ndarray:
    """All matching (s_row, t_row) pairs, encoded s_row << 32 | t_row,
    sorted."""
    s_keys = np.asarray(s_keys).reshape(-1)
    t_keys = np.asarray(t_keys).reshape(-1)
    order = np.argsort(t_keys, kind="stable")
    t_sorted = t_keys[order]
    lo = np.searchsorted(t_sorted, s_keys, side="left")
    hi = np.searchsorted(t_sorted, s_keys, side="right")
    counts = hi - lo
    s_rows = np.repeat(np.arange(s_keys.size, dtype=np.int64), counts)
    starts = np.repeat(lo - np.concatenate(([0], np.cumsum(counts)[:-1])),
                       counts)
    t_rows = order[np.arange(s_rows.size) + starts].astype(np.int64)
    return np.sort((s_rows << 32) | t_rows)


def compare_join(ref_pairs: np.ndarray, s_rows, t_rows) -> dict:
    """``pairs_missing``: reference pairs absent from the answer;
    ``pairs_wrong``: answer pairs that are not reference pairs, or
    repeat one."""
    got = ((np.asarray(s_rows).reshape(-1).astype(np.int64) << 32)
           | (np.asarray(t_rows).reshape(-1).astype(np.int64) & 0xFFFFFFFF))
    uniq = np.unique(got)
    hit = int(np.count_nonzero(np.isin(uniq, ref_pairs, assume_unique=True)))
    return {"pairs_missing": int(ref_pairs.size - hit),
            "pairs_wrong": int(got.size - hit)}


def control_join(s_keys, t_keys):
    """The reference one precision down (int16 keys), as the control that
    the comparison must refuse.  Returns (s_rows, t_rows)."""
    low = lambda k: np.asarray(k).reshape(-1).astype(np.int16)
    pairs = join_reference(low(s_keys), low(t_keys))
    return pairs >> 32, pairs & 0xFFFFFFFF
