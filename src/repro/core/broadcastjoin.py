"""Broadcast (fragment-replicate) equi-join — the small-table fast path.

Beyond the paper's three joins: when min(|S|, |T|) fits per-machine
memory, replicating the small table everywhere beats any repartition —
the big table never crosses the network, there is no hash skew (a hot
key's big-side tuples stay where they were dealt), and the whole join
is **one** synchronized round: alpha = 1, one ``all_gather``.

The big side is dealt **round-robin** (machine i gets rows i, i+t,
i+2t, ...), so a run of hot-key tuples that sits contiguously in the
input spreads evenly instead of landing on one machine — that is what
keeps the output workload near W/t without any planning.  Per-machine
output is not theorem-bounded (a single big-side machine could still
hold disproportionately many matching rows), so the front door pairs
the default Theorem-6-style capacity with the shared
``run_with_capacity`` retry loop.

The planner (repro.planner) selects this path when the sketched small
side fits ``BROADCAST_MEM_BUDGET``; it is also directly reachable via
``cluster.join(..., algorithm="broadcast")``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax.numpy as jnp
import numpy as np

from repro.cluster.collectives import CollectiveTape
from repro.cluster.substrate import Substrate, default_pool

from .localjoin import MASKED_KEY, local_equijoin

__all__ = ["broadcast_join"]


def _broadcast_body(bk, br, sk, sr, *, tape: CollectiveTape, axis,
                    small_side, out_capacity):
    """Per-device body (module-level for stable compiled-program keys)."""
    with tape.phase("broadcast+join"):
        cnt = jnp.sum(sk != MASKED_KEY)
        gk = tape.all_gather(sk, axis, count=cnt).reshape(-1)
        gr = tape.all_gather(sr, axis, track=False).reshape(-1)
        if small_side == "s":
            return local_equijoin(gk, gr, bk, br, out_capacity)
        return local_equijoin(bk, br, gk, gr, out_capacity)


def _deal_round_robin(keys: np.ndarray, rows: np.ndarray, t: int):
    """(n,) -> (t, ceil(n/t)): machine i holds rows i, i+t, i+2t, ..."""
    n = len(keys)
    pad = (-n) % t
    k = np.concatenate([np.asarray(keys, np.int32),
                        np.full(pad, MASKED_KEY, np.int32)])
    r = np.concatenate([np.asarray(rows, np.int32),
                        np.zeros(pad, np.int32)])
    return (jnp.asarray(k.reshape(-1, t).T.copy()),
            jnp.asarray(r.reshape(-1, t).T.copy()))


def broadcast_join(s_keys: np.ndarray, s_rows: np.ndarray,
                   t_keys: np.ndarray, t_rows: np.ndarray,
                   t_machines: int, out_capacity: int,
                   substrate: Optional[Substrate] = None,
                   small_side: Optional[str] = None):
    """All-gather the small table, join locally.  Returns (JoinOutput, report).

    small_side: "s" or "t" forces which table is replicated; default is
    the shorter one (ties go to S).  Output pairs keep the (s_row,
    t_row) orientation regardless of which side was broadcast.
    """
    t = t_machines
    s_keys = np.asarray(s_keys, np.int32)
    t_keys = np.asarray(t_keys, np.int32)
    if small_side is None:
        small_side = "s" if len(s_keys) <= len(t_keys) else "t"
    if small_side not in ("s", "t"):
        raise ValueError(f"small_side must be 's' or 't', got {small_side!r}")
    if substrate is None:
        substrate = default_pool()(t)
    assert substrate.t == t, (substrate, t)
    axis = substrate.axis_name

    if small_side == "s":
        small_k, small_r = _deal_round_robin(s_keys, np.asarray(s_rows), t)
        big_k, big_r = _deal_round_robin(t_keys, np.asarray(t_rows), t)
    else:
        small_k, small_r = _deal_round_robin(t_keys, np.asarray(t_rows), t)
        big_k, big_r = _deal_round_robin(s_keys, np.asarray(s_rows), t)

    body = functools.partial(_broadcast_body, axis=axis,
                             small_side=small_side,
                             out_capacity=out_capacity)
    out, tape = substrate.run(body, big_k, big_r, small_k, small_r)

    counts = np.asarray(out.count).reshape(-1)
    n_in = len(s_keys) + len(t_keys)
    report = tape.report(algorithm=f"BroadcastJoin(small={small_side.upper()})",
                         t=t, n_in=n_in, n_out=int(counts.sum()),
                         workload=counts)
    return out, report
