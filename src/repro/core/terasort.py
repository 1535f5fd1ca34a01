"""Terasort with Algorithm S (paper §3.2) — the randomized baseline.

Round 1  each machine draws exactly q = ceil(ln(n t)) unbiased samples
         (Algorithm S) and they are gathered.
Round 2  boundaries = every ceil(s/t)-th smallest sample (s = t*q total);
         computed redundantly on every device after an all_gather (same
         SPMD adaptation as SMMS — no M1 bottleneck).
Round 3  bucketed shuffle; receiver sorts.

Guarantee (Thm 3/4): |S_i| <= 5m + 1 w.p. >= 1 - 1/n, so the static
receive capacity uses cap_factor ~ 5 (vs SMMS's ~< 2) — the weaker bound
costs real buffer memory on TPU, which the benchmarks make visible.  The
bound can *fail* (probability <= 1/n); the CapacityPolicy retry loop in
repro.cluster is the recovery path.

Note: the shuffle machinery requires contiguous per-destination segments,
so we locally pre-sort before partitioning (the receiver still merges, and
the workload/network accounting is unchanged — local sorting is exactly
the computation Terasort's Round 3 does anyway, just moved one phase
earlier; recorded as a hardware adaptation in DESIGN.md).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.cluster.capacity import CapacityPolicy, run_with_capacity
from repro.cluster.collectives import CollectiveTape
from repro.cluster.substrate import Substrate, default_pool

from .exchange import exchange_sorted_segments
from .sampling import algorithm_s, terasort_sample_count
from .smms import SortResult, resolve_exchange_topology
from .alpha_k import terasort_workload_bound

__all__ = ["terasort_shard", "terasort_sort"]


def terasort_shard(x_local: jnp.ndarray, rng: jax.Array, *, axis_name,
                   t: int, q: int, cap_factor: float = 5.5,
                   values: Optional[jnp.ndarray] = None,
                   backend: str = "static",
                   staged_shape: Optional[tuple] = None,
                   overlap_chunks: int = 2,
                   tape: Optional[CollectiveTape] = None) -> SortResult:
    """Per-device Terasort body.  x_local: (m,), rng: per-device PRNG key.

    ``staged_shape=(t1, t2)`` runs Round 3 as the two-level staged
    exchange over the ``axis_name`` sub-axis pair (alpha 3 -> 4, output
    bitwise unchanged) — same contract as
    :func:`repro.core.smms.smms_shard`.
    """
    m = x_local.shape[0]
    if tape is None:
        tape = CollectiveTape()

    # -- Round 1: Algorithm-S sampling --------------------------------------
    # (The staged gather relays samples hop-by-hop; the global SORT of
    # the pooled samples makes boundary selection order-independent, so
    # boundaries match the flat path bitwise.)
    with tape.phase("round1->2 samples"):
        samples = algorithm_s(rng, x_local, q)            # (q,)
        if staged_shape is not None:
            gathered = tape.all_gather_multi(samples, axis_name)
        else:
            gathered = tape.all_gather(samples, axis_name)
        all_samples = jnp.sort(gathered.reshape(-1))

    # -- Round 2: every ceil(s/t)-th sample as boundary (replicated) --------
    with tape.phase("round2 boundaries"):
        s_tot = all_samples.shape[0]                      # t * q
        i = jnp.arange(1, t)
        idx = jnp.ceil(i * s_tot / t).astype(jnp.int32) - 1
        interior = all_samples[idx]                       # b_1 .. b_{t-1}

    # -- Round 3: shuffle + sort --------------------------------------------
    # sort_input=True fuses the local sort with the boundary partition
    # into ONE kernel dispatch (ops.sort_partition[_kv]) — unlike SMMS,
    # Terasort's sort and partition are adjacent (no sample gather in
    # between), so the whole pre-shuffle pipeline is a single pass.
    if staged_shape is not None:
        # staged path: the exchange declares its own "round3 shuffle
        # s1"/"s2" phases — no outer phase, or alpha double-counts.
        ex = exchange_sorted_segments(x_local, interior, axis_name=axis_name,
                                      t=t, cap_factor=cap_factor,
                                      values=values, backend=backend,
                                      merge=True, sort_input=True,
                                      tape=tape, staged_shape=staged_shape,
                                      overlap_chunks=overlap_chunks,
                                      phase_prefix="round3 shuffle")
    else:
        with tape.phase("round3 shuffle"):
            ex = exchange_sorted_segments(
                x_local, interior, axis_name=axis_name, t=t,
                cap_factor=cap_factor, values=values, backend=backend,
                merge=True, sort_input=True, tape=tape)
    b = jnp.concatenate([all_samples[:1], interior, all_samples[-1:]])
    return SortResult(ex.keys, ex.values, ex.count, ex.sent, ex.dropped, b)


def _terasort_shard_kv(x_local, rng, values, **kw):
    """Module-level (x, rng, values) adapter: a functools.partial of this
    keys the substrate's compiled-program cache on content, so repeated
    sorts share one compiled program instead of recompiling per call."""
    return terasort_shard(x_local, rng, values=values, **kw)


def terasort_sort(x: jnp.ndarray, seed: int = 0,
                  cap_factor: Optional[float] = None,
                  backend: str = "static",
                  substrate: Optional[Substrate] = None,
                  policy: Optional[CapacityPolicy] = None,
                  values: Optional[jnp.ndarray] = None,
                  exchange: str = "flat",
                  overlap_chunks: int = 2,
                  donate: Optional[bool] = None):
    """Host wrapper over t machines on a substrate.  x: (t, m).

    ``values`` (same leading (t, m) shape) ride along through the
    fused Round-3 ``ops.sort_partition_kv`` pair sort and the exchange,
    exactly as in SMMS.  Returns ``((keys, values), report)`` when
    values are given, ``(keys, report)`` otherwise (the historical
    signature).  ``substrate=None`` uses the process-wide jit pool —
    the sampling scan, boundary selection and shuffle compile into ONE
    cached program, so repeated sorts skip the (expensive) Algorithm-S
    trace entirely.  ``donate`` as in :func:`repro.core.smms.smms_sort`
    (``None`` = donate automatically when the capacity schedule is
    single-shot).
    """
    t, m = x.shape
    n = t * m
    q = terasort_sample_count(n, t)
    rngs = jax.random.split(jax.random.key(seed), t)
    substrate, staged_shape = resolve_exchange_topology(substrate, t,
                                                        exchange)
    assert substrate.t == t, (substrate, t)
    if policy is None:
        policy = (CapacityPolicy.fixed(cap_factor) if cap_factor is not None
                  else CapacityPolicy.terasort(n, t, slack=1.1))
    if donate is None:
        donate = policy.max_retries == 0
    donate_argnums = ()
    if donate and policy.max_retries == 0:
        donate_argnums = (0,) if values is None else (0, 2)
    if staged_shape is not None:
        xr = x.reshape(staged_shape + (m,))
        rr = rngs.reshape(staged_shape + rngs.shape[1:])
        vr = (values.reshape(staged_shape + values.shape[1:])
              if values is not None else None)
        axis_arg = substrate.axis_names
    else:
        xr, rr, vr, axis_arg = x, rngs, values, substrate.axis_name

    def attempt(factor):
        static = dict(axis_name=axis_arg, t=t, q=q,
                      cap_factor=float(factor), backend=backend)
        if staged_shape is not None:
            static.update(staged_shape=staged_shape,
                          overlap_chunks=int(overlap_chunks))
        if values is not None:
            res, tape = substrate.run(
                functools.partial(_terasort_shard_kv, **static),
                xr, rr, vr, donate_argnums=donate_argnums)
        else:
            res, tape = substrate.run(
                functools.partial(terasort_shard, **static), xr, rr,
                donate_argnums=donate_argnums)
        return (res, tape), int(np.asarray(res.dropped).reshape(-1)[0])

    (res, tape), factor, attempts = run_with_capacity(attempt, policy)

    karr = np.asarray(res.keys).reshape(t, -1)
    counts = np.asarray(res.count).reshape(-1)
    flat = np.concatenate([karr[i, :counts[i]] for i in range(t)])
    vals = None
    if res.values is not None:
        v = np.asarray(res.values)
        if staged_shape is not None:      # (t1, t2, C, ...) -> (t, C, ...)
            v = v.reshape((t,) + v.shape[2:])
        vals = np.concatenate([v[i, :counts[i]] for i in range(t)])

    report = tape.report(algorithm="Terasort+AlgS", t=t, n_in=n, n_out=n,
                         workload=counts)
    report.exchange_topology = ("staged" if staged_shape is not None
                                else "flat")
    report.theoretical_workload_bound = terasort_workload_bound(n, t)
    report.total_dropped = 0
    report.cap_factor = factor
    report.capacity_attempts = attempts
    if values is not None:
        return (flat, vals), report
    return flat, report
