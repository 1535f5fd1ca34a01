"""SMMS — Sort-Map-Merge Sort (paper §3.1), TPU-native.

Three logical rounds (= collective phases in ONE jitted SPMD program):

  Round 1   local sort; pick s+1 = r*t+1 equi-depth samples.
  Round 2   all_gather the t*(s+1) samples (tiny); EVERY device runs the
            vectorized Algorithm 1 redundantly (replicated compute beats
            the paper's gather-at-M1-then-broadcast on an SPMD machine —
            no single-device bottleneck, same network bound).
  Round 3   bucketed shuffle with a static capacity derived from
            Theorem 1 (workload <= (1 + 2/r + t^2/n) m), then local merge.

The per-device body is written against an ``axis_name`` plus a
CollectiveTape, so the same code runs on any repro.cluster substrate
(shard_map production mesh or vmap virtual machines) and its (alpha, k)
report is assembled from counters recorded inside the jitted program.

Guarantee (Thm 2): (3, 1 + 2/r + r t^3/n)-minimal for t^3 <= n.
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
from repro.kernels import ops
from repro.obs import trace as obs_trace

from .boundaries import boundaries_jax, equidepth_samples
from .exchange import ExchangeResult, exchange_sorted_segments
from .alpha_k import smms_workload_bound

__all__ = ["smms_shard", "smms_sort", "SortResult", "default_cap_factor",
           "resolve_exchange_topology"]


def resolve_exchange_topology(substrate: Optional[Substrate], t: int,
                              exchange: str = "flat"):
    """Resolve (substrate, staged_shape) for a t-machine sort.

    The one place the host wrappers decide flat-vs-staged:

    * a 2-axis substrate always runs staged over its own (t1, t2) shape
      (there is no single axis to run the flat exchange over);
    * ``exchange="staged"`` with no substrate resolves a pooled 2-axis
      substrate over the balanced factorization of t — non-factorable t
      warns and degrades to flat (the staged path is an optimization,
      never a requirement);
    * ``exchange="staged"`` with an explicit 1-axis substrate warns and
      stays flat (the caller pinned the topology by picking the mesh).

    ``staged_shape=None`` in the result means the flat exchange.
    """
    import warnings

    from repro.launch.mesh import STAGED_AXIS_NAMES, factor_shards

    if exchange not in ("flat", "staged"):
        raise ValueError(f"unknown exchange topology {exchange!r}; "
                         "expected 'flat' or 'staged'")
    if substrate is not None and not callable(substrate) \
            and len(substrate.axes) == 2:
        t1, t2 = substrate.shape
        if min(t1, t2) < 2:
            raise ValueError(f"2-axis substrate {substrate.shape} cannot "
                             "stage the exchange: both sub-axes must be "
                             ">= 2")
        return substrate, (t1, t2)
    pool = substrate if callable(substrate) and not isinstance(
        substrate, Substrate) else None
    if exchange == "staged":
        if substrate is not None and pool is None:
            warnings.warn(
                "explicit single-axis substrate cannot run the staged "
                "exchange; falling back to the flat topology",
                stacklevel=2)
            return substrate, None
        fs = factor_shards(t, warn=True)
        provider = pool if pool is not None else default_pool()
        if fs is None:
            return provider(t), None
        return provider((STAGED_AXIS_NAMES[0], fs[0]),
                        (STAGED_AXIS_NAMES[1], fs[1])), fs
    if substrate is None:
        return default_pool()(t), None
    if pool is not None:
        return pool(t), None
    return substrate, None


class SortResult(NamedTuple):
    keys: jnp.ndarray              # (C,) per device; ascending, PAD-filled tail
    values: Optional[jnp.ndarray]  # payload permuted with keys (optional)
    count: jnp.ndarray             # valid keys on this device
    sent: jnp.ndarray              # keys shipped out in Round 3
    dropped: jnp.ndarray           # global overflow count (0 == success)
    boundaries: jnp.ndarray        # (t+1,) the Algorithm-1 boundaries


def default_cap_factor(n: int, t: int, r: int, slack: float = 1.05) -> float:
    """Static receive capacity from Theorem 1, with a small safety slack."""
    return CapacityPolicy.smms(n, t, r, slack=slack).first_factor


def smms_shard(x_local: jnp.ndarray, *, axis_name, t: int, r: int = 2,
               cap_factor: Optional[float] = None,
               values: Optional[jnp.ndarray] = None,
               backend: str = "static",
               local_sort=None,
               kernel_backend: Optional[str] = None,
               staged_shape: Optional[tuple] = None,
               overlap_chunks: int = 2,
               tape: Optional[CollectiveTape] = None) -> SortResult:
    """Per-device SMMS body.  x_local: (m,) this machine's objects.

    kernel_backend picks the implementation of every sort/partition/merge
    hot loop ("pallas" = the Pallas kernels via repro.kernels.ops,
    "reference" = jnp, None = ops.DEFAULT_BACKEND); results are bitwise
    identical either way.  An explicit ``local_sort`` callable overrides
    the Round-1 keys-only sort (test hook).

    ``staged_shape=(t1, t2)`` runs Round 3 as the two-level staged
    exchange: ``axis_name`` must then be the (sub-axis-1, sub-axis-2)
    name pair of a t1 x t2 substrate.  The shuffle splits into two tape
    phases ("round3 shuffle s1"/"s2"), so alpha rises from 3 to 4 while
    the sorted output stays bitwise equal to the flat path.
    """
    m = x_local.shape[0]
    n = m * t
    s = r * t
    if cap_factor is None:
        cap_factor = default_cap_factor(n, t, r)
    if tape is None:
        tape = CollectiveTape()

    # -- Round 1: local sort + equi-depth samples ---------------------------
    # Amortized padding: the round pads its operands ONCE (ops.pad_pow2)
    # and chains the prepadded sort + clamped partition over the padded
    # buffer — instead of every op padding and unpadding its own copy.
    with tape.phase("round1->2 samples"):
        valid_len: Optional[int] = m
        if values is not None:
            xs, values = ops.sort_kv(ops.pad_pow2(x_local),
                                     ops.pad_pow2(values, fill=0),
                                     backend=kernel_backend, prepadded=True)
        elif local_sort is not None:
            xs = local_sort(x_local)
            valid_len = None           # test hook: unpadded contract
        else:
            xs = ops.sort(ops.pad_pow2(x_local), backend=kernel_backend,
                          prepadded=True)
        lam = equidepth_samples(xs[:m], s)                # (s+1,)
        if staged_shape is not None:
            lam_all = tape.all_gather_multi(lam, axis_name)   # (t1, t2, s+1)
            lam_all = lam_all.reshape(t, s + 1)
        else:
            lam_all = tape.all_gather(lam, axis_name)     # (t, s+1)

    # -- Round 2: replicated Algorithm 1 (no traffic, still a round) --------
    with tape.phase("round2 boundaries"):
        b = boundaries_jax(lam_all, m, s)                 # (t+1,)

    # -- Round 3: bucketed shuffle + merge ----------------------------------
    if staged_shape is not None:
        # The staged exchange declares its own per-stage phases
        # ("round3 shuffle s1"/"s2"); wrapping it in an outer phase here
        # would add an empty round and inflate alpha.
        ex: ExchangeResult = exchange_sorted_segments(
            xs, b[1:-1], axis_name=axis_name, t=t, cap_factor=cap_factor,
            values=values, backend=backend, merge=True,
            kernel_backend=kernel_backend, valid_len=valid_len, tape=tape,
            staged_shape=staged_shape, overlap_chunks=overlap_chunks,
            phase_prefix="round3 shuffle")
    else:
        with tape.phase("round3 shuffle"):
            ex = exchange_sorted_segments(
                xs, b[1:-1], axis_name=axis_name, t=t,
                cap_factor=cap_factor, values=values, backend=backend,
                merge=True, kernel_backend=kernel_backend,
                valid_len=valid_len, tape=tape)
    return SortResult(ex.keys, ex.values, ex.count, ex.sent, ex.dropped, b)


# ---------------------------------------------------------------------------
# Host-level wrapper: run the body on a substrate, with capacity retry.
# ---------------------------------------------------------------------------

def _smms_shard_kv(x_local, values, **kw):
    """Module-level (x, values) adapter so the substrate's compiled-program
    cache can key the body on content (functools.partial of a stable
    function) instead of a per-call closure."""
    return smms_shard(x_local, values=values, **kw)


def smms_sort(x: jnp.ndarray, r: int = 2,
              cap_factor: Optional[float] = None,
              values: Optional[jnp.ndarray] = None,
              backend: str = "static",
              kernel_backend: Optional[str] = None,
              substrate: Optional[Substrate] = None,
              policy: Optional[CapacityPolicy] = None,
              exchange: str = "flat",
              overlap_chunks: int = 2,
              donate: Optional[bool] = None):
    """Sort x of shape (t, m) across t machines on the given substrate.

    Returns ((sorted_global, values_or_None), report: AlphaKReport).
    ``substrate=None`` uses the process-wide jit-compiling pool: the
    whole three-round body runs as ONE compiled program, cached across
    calls.  ``donate=True`` lets that program consume the input buffers
    (honored only when the capacity schedule is single-shot — a retry
    must re-read the operands — and on platforms with donation support).
    ``donate=None`` (the default) donates automatically exactly when
    the resolved capacity schedule is single-shot (``max_retries == 0``:
    an explicit ``cap_factor`` or any ``CapacityPolicy.fixed``), so
    capacity-stable callers get the copy-free path without opting in;
    pass ``donate=False`` to keep the inputs alive.

    ``exchange="staged"`` routes Round 3 through the two-level staged
    exchange over a (t1, t2)-factored substrate (see
    :func:`resolve_exchange_topology` for the fallback rules); the
    sorted output is bitwise equal to the flat path and
    ``report.exchange_topology`` records which topology actually ran.
    """
    t, m = x.shape
    n = t * m
    substrate, staged_shape = resolve_exchange_topology(substrate, t,
                                                        exchange)
    assert substrate.t == t, (substrate, t)
    if policy is None:
        policy = (CapacityPolicy.fixed(cap_factor) if cap_factor is not None
                  else CapacityPolicy.smms(n, t, r))
    if donate is None:
        donate = policy.max_retries == 0
    donate_argnums = ()
    if donate and policy.max_retries == 0:
        donate_argnums = (0,) if values is None else (0, 1)
    if staged_shape is not None:
        xr = x.reshape(staged_shape + (m,))
        vr = (values.reshape(staged_shape + values.shape[1:])
              if values is not None else None)
        axis_arg = substrate.axis_names
    else:
        xr, vr, axis_arg = x, values, substrate.axis_name

    def attempt(factor):
        static = dict(axis_name=axis_arg, t=t, r=r,
                      cap_factor=float(factor), backend=backend,
                      kernel_backend=kernel_backend)
        if staged_shape is not None:
            static.update(staged_shape=staged_shape,
                          overlap_chunks=int(overlap_chunks))
        if values is not None:
            res, tape = substrate.run(
                functools.partial(_smms_shard_kv, **static), xr, vr,
                donate_argnums=donate_argnums)
        else:
            res, tape = substrate.run(
                functools.partial(smms_shard, **static), xr,
                donate_argnums=donate_argnums)
        return (res, tape), int(np.asarray(res.dropped).reshape(-1)[0])

    (res, tape), factor, attempts = run_with_capacity(attempt, policy)

    with obs_trace.span("smms.fetch"):       # device to host
        keys = np.asarray(res.keys).reshape(t, -1)
        counts = np.asarray(res.count).reshape(-1)
        v = None if res.values is None else np.asarray(res.values)
    if v is not None and staged_shape is not None:
        v = v.reshape((t,) + v.shape[2:])  # (t1, t2, C, ...) -> (t, C, ...)
    with obs_trace.span("smms.compact"):     # each machine's filled prefix
        flat = np.concatenate([keys[i, :counts[i]] for i in range(t)])
        vals = (None if v is None
                else np.concatenate([v[i, :counts[i]] for i in range(t)]))

    report = tape.report(algorithm=f"SMMS(r={r})", t=t, n_in=n, n_out=n,
                         workload=counts)
    report.exchange_topology = ("staged" if staged_shape is not None
                                else "flat")
    report.theoretical_workload_bound = smms_workload_bound(n, t, r)
    report.total_dropped = 0
    report.cap_factor = factor
    report.capacity_attempts = attempts
    return (flat, vals), report
