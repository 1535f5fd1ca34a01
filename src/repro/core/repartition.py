"""Standard Repartition Join — Hadoop's stock equi-join (paper §4 intro).

All tuples of a join key land on the machine ``hash(key) % t``; that
machine cross-products the two sides.  This is the skew-vulnerable
baseline the paper improves on (a single hot key pins its entire result
to one machine), implemented so benchmarks can reproduce the imbalance
the paper motivates with.  Runs on a repro.cluster substrate like the
real algorithms; its one shuffle phase is recorded on the tape with the
received count measured in-program.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax.numpy as jnp
import numpy as np

from repro.cluster.substrate import Substrate, default_pool

from .localjoin import MASKED_KEY, local_equijoin

__all__ = ["repartition_join"]


def _repartition_body(a, b, c, d, *, tape, out_capacity):
    """Per-device body (module-level for stable compiled-program keys)."""
    with tape.phase("shuffle"):
        received = jnp.sum(a != MASKED_KEY) + jnp.sum(c != MASKED_KEY)
        tape.record(sent=received, received=received)
        return local_equijoin(a, b, c, d, out_capacity)


def repartition_join(s_keys: np.ndarray, s_rows: np.ndarray,
                     t_keys: np.ndarray, t_rows: np.ndarray,
                     t_machines: int, out_capacity: int,
                     substrate: Optional[Substrate] = None,
                     donate: Optional[bool] = None):
    """Hash-partition both tables by key; join per machine.

    ``donate=None`` (default) donates the four partitioned fragment
    tensors: the out_capacity here is caller-fixed (single attempt, no
    retry loop) and the fragments are built fresh in this call.
    ``donate=False`` keeps them alive.
    """
    t = t_machines
    s_keys = np.asarray(s_keys, np.int64)
    t_keys = np.asarray(t_keys, np.int64)
    if substrate is None:
        substrate = default_pool()(t)
    assert substrate.t == t, (substrate, t)

    def shard(keys, rows):
        dest = (keys * 2654435761 % 2**31) % t  # Knuth multiplicative hash
        cap = max(1, int(np.max(np.bincount(dest, minlength=t))))
        k = np.full((t, cap), MASKED_KEY, np.int32)
        v = np.zeros((t, cap), np.int32)
        fill = np.zeros(t, np.int64)
        for i, d in enumerate(dest):
            k[d, fill[d]] = keys[i]
            v[d, fill[d]] = rows[i]
            fill[d] += 1
        return jnp.asarray(k), jnp.asarray(v), fill

    sk, sr, ns = shard(s_keys, np.asarray(s_rows))
    tk, tr, nt = shard(t_keys, np.asarray(t_rows))

    body = functools.partial(_repartition_body, out_capacity=out_capacity)
    donate_argnums = (0, 1, 2, 3) if donate is not False else ()
    out, tape = substrate.run(body, sk, sr, tk, tr,
                              donate_argnums=donate_argnums)
    counts = np.asarray(out.count).reshape(-1)
    n_in = len(s_keys) + len(t_keys)
    report = tape.report(algorithm="RepartitionJoin", t=t, n_in=n_in,
                        n_out=int(counts.sum()), workload=counts)
    return out, report
