"""Reference sort: ``np.sort`` of the float32 keys.

A sorted answer is exact when its keys equal the reference bit for bit
and its payload is a permutation of the record ids that carries each
record's own key.  Ties leave the payload order free, so the payload is
checked by consistency, not against one permutation.
"""
from __future__ import annotations

import numpy as np


def sort_reference(keys_in: np.ndarray) -> np.ndarray:
    return np.sort(np.asarray(keys_in, np.float32).reshape(-1))


def _bits(a: np.ndarray) -> np.ndarray:
    return np.ascontiguousarray(a, np.float32).view(np.uint32)


def compare_sort(keys_in, out_keys, out_vals) -> dict:
    """Counts of wrong output positions: ``keys_wrong`` against the
    reference, ``payload_wrong`` for ids that are out of range, repeated,
    missing, or carry another key (0 when the query has no payload)."""
    keys_in = np.asarray(keys_in, np.float32).reshape(-1)
    ref = sort_reference(keys_in)
    n = ref.size
    out_keys = np.asarray(out_keys, np.float32).reshape(-1)
    common = min(n, out_keys.size)
    keys_wrong = abs(n - out_keys.size) + int(np.count_nonzero(
        _bits(out_keys[:common]) != _bits(ref[:common])))
    payload_wrong = 0
    if out_vals is not None:
        vals = np.asarray(out_vals).reshape(-1).astype(np.int64)
        common = min(n, vals.size)
        vals, keys = vals[:common], out_keys[:common]
        in_range = (vals >= 0) & (vals < n)
        ids = np.where(in_range, vals, 0)
        carries = in_range & (_bits(keys_in[ids]) == _bits(keys))
        seen = np.zeros(n, bool)
        seen[vals[in_range]] = True
        distinct = int(np.count_nonzero(seen))
        payload_wrong = (abs(n - np.asarray(out_vals).size)
                         + int(np.count_nonzero(~carries))
                         + (n - distinct))
    return {"keys_wrong": int(keys_wrong), "payload_wrong": int(payload_wrong)}


def control_sort(keys_in, vals_in):
    """The reference one precision down (bfloat16 keys), as the control
    that the comparison must refuse: a stable sort of the rounded keys,
    returned with their record ids."""
    import ml_dtypes
    keys = np.asarray(keys_in, np.float32).reshape(-1)
    low = keys.astype(ml_dtypes.bfloat16)
    order = np.argsort(low, kind="stable")
    out_vals = None if vals_in is None else \
        np.asarray(vals_in).reshape(-1)[order]
    return low[order].astype(np.float32), out_vals
