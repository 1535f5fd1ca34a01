"""RandJoin (paper §4.2) — randomized skew equi-join on an a x b machine matrix.

The t devices form an a x b **machine matrix** A (a*b = t, chosen to
minimize a|T| + b|S|).  Every S tuple draws a uniform row i in [0, a) and
must reach the b devices A[i, *]; every T tuple draws a column j and must
reach the a devices A[*, j].  Device A[i, j] cross-products what it holds,
so the (i, j) fragment pair is joined exactly once.

TPU mapping: the machine matrix IS a 2D mesh ('a', 'b').  "Send tuple to
all machines in row i" = one static all_to_all over axis 'a' (route to the
right row, same column) followed by one all_gather over axis 'b'
(replicate across the row) — RandJoin is fragment-replicate join, and on
TPU both hops are single collectives.  All four hops are recorded on the
CollectiveTape under ONE phase: RandJoin is (1, .)-minimal — a single
synchronized round.

Guarantee (Cor 3 / Thm 5): per-device output < 2 * MN/t per key w.p.
>= 1 - 1.2e-9 when M/a, N/b >= 300; the static output capacity uses that
bound.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.cluster.collectives import CollectiveTape
from repro.cluster.substrate import Substrate, default_pool
from repro.kernels import ops

from .exchange import PAD, build_send_buffer, static_exchange
from .localjoin import MASKED_KEY, JoinOutput, local_equijoin

__all__ = ["choose_ab", "randjoin_shard", "randjoin", "route_to_interval"]


def choose_ab(t: int, size_s: int, size_t: int) -> Tuple[int, int]:
    """Pick (a, b) with a*b = t minimizing a|T| + b|S| (paper §4.2.1)."""
    best = None
    for a in range(1, t + 1):
        if t % a:
            continue
        b = t // a
        cost = a * size_t + b * size_s
        if best is None or cost < best[0]:
            best = (cost, a, b)
    return best[1], best[2]


def route_to_interval(keys: jnp.ndarray, rows: jnp.ndarray,
                      assign: jnp.ndarray, n_dst: int, axis_name: str,
                      cap_pair: int, tape: Optional[CollectiveTape] = None):
    """all_to_all tuples to their assigned interval along ``axis_name``.

    Returns (join_keys, payload_rows, dropped, valid_count); masked slots
    have join_key == MASKED_KEY.

    The destination sort and the interval boundary search run as ONE
    fused ``ops.sort_partition_kv`` dispatch.  Integer boundaries
    1..n_dst-1 with side='left' give the same cuts as the historical
    float (k - 0.5) midpoints: for integer assignments, a < k iff
    a < k - 0.5.
    """
    pairs = jnp.stack([keys, rows], axis=-1)                   # (m, 2) int32
    interior = jnp.arange(1, n_dst, dtype=assign.dtype)
    assign_sorted, payload, starts, lens = ops.sort_partition_kv(
        assign, pairs, interior)
    a_sorted = assign_sorted.astype(jnp.float32)
    kbuf, vbuf, dropped = build_send_buffer(a_sorted, starts, lens, cap_pair,
                                            values=payload)
    me = lax.axis_index(axis_name)
    rk, rv = static_exchange(kbuf, axis_name, vbuf, tape=tape,
                             sent=keys.shape[0] - lens[me])
    rk = rk.reshape(-1)
    rv = rv.reshape(-1, 2)
    valid = rk < jnp.asarray(PAD, rk.dtype)
    jkeys = jnp.where(valid, rv[:, 0], MASKED_KEY)
    jrows = jnp.where(valid, rv[:, 1], 0)
    return jkeys, jrows, dropped, jnp.sum(valid)


def randjoin_shard(s_keys, s_rows, t_keys, t_rows, rng, *,
                   axis_a: str, axis_b: str, a: int, b: int,
                   out_capacity: int, in_cap_factor: float = 2.0,
                   tape: Optional[CollectiveTape] = None) -> JoinOutput:
    """Per-device RandJoin body.  Local fragments: (ms,), (mt,) int32."""
    ms, mt = s_keys.shape[0], t_keys.shape[0]
    rng_s, rng_t = jax.random.split(rng)
    if tape is None:
        tape = CollectiveTape()

    with tape.phase("map: route+replicate"):
        # ---- map phase: random tuple-to-interval assignment ----------------
        i_assign = jax.random.randint(rng_s, (ms,), 0, a)
        j_assign = jax.random.randint(rng_t, (mt,), 0, b)

        # ---- route S to its row (all_to_all over 'a'), replicate over 'b' --
        cap_s = max(1, math.ceil(in_cap_factor * ms / a))
        sk, sr, sdrop, s_count = route_to_interval(
            s_keys, s_rows, i_assign, a, axis_a, cap_s, tape=tape)
        sk = tape.all_gather(sk, axis_b, count=s_count).reshape(-1)
        sr = tape.all_gather(sr, axis_b, track=False).reshape(-1)

        # ---- route T to its column (all_to_all over 'b'), replicate over 'a'
        cap_t = max(1, math.ceil(in_cap_factor * mt / b))
        tk, tr, tdrop, t_count = route_to_interval(
            t_keys, t_rows, j_assign, b, axis_b, cap_t, tape=tape)
        tk = tape.all_gather(tk, axis_a, count=t_count).reshape(-1)
        tr = tape.all_gather(tr, axis_a, track=False).reshape(-1)

        # ---- reduce phase: local cross product (same round — no barrier) ---
        out = local_equijoin(sk, sr, tk, tr, out_capacity)
        dropped = out.dropped + tape.psum(sdrop + tdrop,
                                          axis_a if a > 1 else axis_b)
    return out._replace(dropped=dropped.astype(jnp.int32))


def randjoin(s_keys: np.ndarray, s_rows: np.ndarray,
             t_keys: np.ndarray, t_rows: np.ndarray,
             t_machines: int, out_capacity: int,
             seed: int = 0, in_cap_factor: float = 2.0,
             ab: Optional[Tuple[int, int]] = None,
             substrate: Optional[Substrate] = None):
    """Host wrapper: the a x b machine matrix on a 2-axis substrate.

    Tables are flat host arrays; they are dealt round-robin to the t
    devices (the paper's 'evenly distributed initially' assumption).
    """
    a, b = ab if ab is not None else choose_ab(
        t_machines, s_keys.shape[0], t_keys.shape[0])
    t = a * b
    if substrate is None:
        substrate = default_pool()(("a", a), ("b", b))
    assert substrate.shape == (a, b), (substrate, a, b)
    axis_a, axis_b = substrate.axis_names

    def deal(keys, rows):
        n = keys.shape[0]
        pad = (-n) % t
        k = np.concatenate([keys, np.full(pad, MASKED_KEY, np.int32)])
        r = np.concatenate([rows, np.zeros(pad, np.int32)])
        return (jnp.asarray(k.reshape(t, -1).reshape(a, b, -1)),
                jnp.asarray(r.reshape(t, -1).reshape(a, b, -1)))

    sk, sr = deal(np.asarray(s_keys, np.int32), np.asarray(s_rows, np.int32))
    tk, tr = deal(np.asarray(t_keys, np.int32), np.asarray(t_rows, np.int32))
    rngs = jax.random.split(jax.random.key(seed), t).reshape(a, b)

    # functools.partial of the module-level body: the substrate keys its
    # compiled-program cache on (func, kwargs), so repeated joins with the
    # same parameters reuse one compiled program.
    body = functools.partial(randjoin_shard, axis_a=axis_a, axis_b=axis_b,
                             a=a, b=b, out_capacity=int(out_capacity),
                             in_cap_factor=float(in_cap_factor))
    out, tape = substrate.run(body, sk, sr, tk, tr, rngs)

    counts = np.asarray(out.count).reshape(-1)
    n_in = s_keys.shape[0] + t_keys.shape[0]
    n_out = int(counts.sum())
    report = tape.report(algorithm=f"RandJoin(a={a},b={b})", t=t,
                         n_in=n_in, n_out=n_out, workload=counts)
    return out, report
