"""Static-capacity local equi-join — the per-device "reducer" cross product.

Given local fragments of S and T (integer join keys + payload row-ids),
emit every matching (s_row, t_row) pair into a fixed-capacity output
buffer.  TPU-native: sort T by key, then for each S tuple binary-search
its match range; output slot j is decoded back to (s index, offset) with a
searchsorted over the cumulative match counts — three sorts/searches and
two gathers, no data-dependent shapes anywhere.

All three hot loops route through the kernel-dispatch layer
(repro.kernels.ops): the T-side sort and the binary searches.

Masked tuples use key == MASKED_KEY (int sentinel) and never match.
"""
from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp

from repro.kernels import ops

__all__ = ["MASKED_KEY", "JoinOutput", "local_equijoin", "join_size"]

MASKED_KEY = jnp.iinfo(jnp.int32).max  # sentinel; real keys must be < this


class JoinOutput(NamedTuple):
    s_rows: jnp.ndarray   # (capacity,) payload of the S side (row ids)
    t_rows: jnp.ndarray   # (capacity,) payload of the T side
    valid: jnp.ndarray    # (capacity,) bool
    count: jnp.ndarray    # scalar: true number of result tuples
    dropped: jnp.ndarray  # scalar: results beyond capacity (0 == success)


def join_size(s_keys: jnp.ndarray, t_keys: jnp.ndarray) -> jnp.ndarray:
    """Exact |S >< T| for the local fragments (for capacity planning)."""
    tk = ops.sort(jnp.where(t_keys == MASKED_KEY, MASKED_KEY, t_keys))
    lo = ops.searchsorted(tk, s_keys, side="left")
    hi = ops.searchsorted(tk, s_keys, side="right")
    cnt = jnp.where(s_keys == MASKED_KEY, 0, hi - lo)
    return jnp.sum(cnt)


def local_equijoin(s_keys: jnp.ndarray, s_rows: jnp.ndarray,
                   t_keys: jnp.ndarray, t_rows: jnp.ndarray,
                   capacity: int) -> JoinOutput:
    """Cross-product of equal keys, statically shaped.

    s_keys/t_keys: (ns,)/(nt,) int32 join keys (MASKED_KEY = absent).
    s_rows/t_rows: payloads (row identifiers) aligned with the keys.
    """
    ns = s_keys.shape[0]

    # Sort T by key; masked tuples (sentinel = int max) sort to the end and
    # are excluded because searchsorted for any real key stops before them.
    tk, tv = ops.sort_kv(t_keys, t_rows)

    lo = ops.searchsorted(tk, s_keys, side="left")     # (ns,)
    hi = ops.searchsorted(tk, s_keys, side="right")
    cnt = jnp.where(s_keys == MASKED_KEY, 0, hi - lo)  # matches per S tuple

    cum = jnp.cumsum(cnt)                              # inclusive
    total = cum[-1] if ns > 0 else jnp.zeros((), jnp.int32)
    excl = cum - cnt                                   # exclusive offsets

    out_j = jnp.arange(capacity)
    # slot j belongs to the S tuple whose [excl, cum) window contains j
    src_s = ops.searchsorted(cum, out_j, side="right")
    src_s = jnp.clip(src_s, 0, ns - 1)
    within = out_j - excl[src_s]
    t_idx = jnp.clip(lo[src_s] + within, 0, tk.shape[0] - 1)
    valid = out_j < total
    out = JoinOutput(
        s_rows=jnp.where(valid, s_rows[src_s], 0),
        t_rows=jnp.where(valid, tv[t_idx], 0),
        valid=valid,
        count=total.astype(jnp.int32),
        dropped=jnp.maximum(total - capacity, 0).astype(jnp.int32),
    )
    return out