"""Execution substrates: one ``run(shard_fn, *args)`` API, two executors.

A per-device body written against named-axis collectives runs unchanged
under two interchangeable executors:

* :class:`VmapSubstrate`     — t *virtual* machines on one device via
  ``jax.vmap`` with axis names (the unit-test / laptop path).
* :class:`ShardMapSubstrate` — a real device mesh via ``shard_map``
  (the production path; also exercised on forced host devices in CI).

Both thread a :class:`~repro.cluster.collectives.CollectiveTape` through
the body (keyword argument ``tape``) and return ``(outputs, tape)`` with
the tape bound to concrete per-device traffic counters, so the caller
can assemble an AlphaKReport without knowing which executor ran.

Axes are declared as ``(name, size)`` pairs; multi-axis substrates (the
RandJoin a x b machine matrix) nest vmaps / open a 2D mesh.  Input
arrays carry one leading dim per axis (``(t, m)`` or ``(a, b, m)``);
outputs come back with the same leading dims.

Substrates are **re-entrant**: ``run()`` may be called from any number
of threads.  A per-substrate lock serializes execution (the compiled
program's tape metadata is populated at trace time and must not be
mutated concurrently), and every call returns a private bound-snapshot
tape, so a report assembled after ``run()`` can never observe a later
run's counters.  Compiled-program caches key on a *stable* function
identity — ``functools.partial`` objects hash by (func, args, kwargs) —
so repeated queries through the cluster front door reuse the compiled
program instead of recompiling per call; ``Substrate.stats`` counts the
compiles and cache hits (the serving engine's recompile metric).
"""
from __future__ import annotations

import collections
import functools
import math
import threading
from typing import Callable, Dict, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from .collectives import CollectiveTape
from ..obs import trace as obs_trace
from ..obs.metrics import REGISTRY

__all__ = ["Substrate", "VmapSubstrate", "ShardMapSubstrate",
           "SubstratePool", "default_substrate", "default_pool",
           "reset_default_pool", "recommend_pool_size",
           "DONATION_PLATFORMS"]

AxisSpec = Union[int, Tuple[str, int]]

_DEFAULT_NAMES = ("i", "j", "k")

# Platforms where jax buffer donation is implemented; elsewhere (CPU)
# requesting donation would only emit a warning per compile, so the
# substrates drop it and count the drop in stats["donation_dropped"].
DONATION_PLATFORMS = ("gpu", "tpu")


def _donation_supported() -> bool:
    return jax.default_backend() in DONATION_PLATFORMS


def _normalize_axes(axes: Sequence[AxisSpec]) -> Tuple[Tuple[str, int], ...]:
    out = []
    for pos, ax in enumerate(axes):
        if isinstance(ax, int):
            out.append((_DEFAULT_NAMES[pos], ax))
        else:
            name, size = ax
            out.append((str(name), int(size)))
    return tuple(out)


def _stable_fn_key(fn: Callable):
    """A hashable identity for a shard body that survives re-construction.

    The cluster wrappers rebuild their per-device bodies on every call;
    raw function identity would miss the compiled-program cache each
    time.  ``functools.partial`` of a module-level function over
    hashable keywords keys on *content* instead, so two calls with the
    same body and parameters share one compiled program.
    """
    if isinstance(fn, functools.partial):
        try:
            kw = tuple(sorted(fn.keywords.items()))
            hash((fn.func, fn.args, kw))
            return (_stable_fn_key(fn.func), fn.args, kw)
        except TypeError:      # unhashable partial payload: identity key
            return fn
    return fn


def _fn_label(fn: Callable) -> str:
    """Human-readable body name for per-algorithm compile accounting."""
    base = fn
    while isinstance(base, functools.partial):
        base = base.func
    return getattr(base, "__name__", type(base).__name__).lstrip("_")


class Substrate:
    """Common surface: axis metadata + ``run(shard_fn, *args)``."""

    def __init__(self, *axes: AxisSpec):
        if not axes:
            raise ValueError("substrate needs at least one axis")
        self.axes = _normalize_axes(axes)
        # Re-entrancy: serializes trace+execute+bind; RLock so a body that
        # (indirectly) re-enters the same substrate cannot self-deadlock.
        self._lock = threading.RLock()
        # "compiles" / "program_cache_hits" / "runs" — the serving layer's
        # recompile accounting reads these (via stats_snapshot()).
        self.stats: collections.Counter = collections.Counter()

    def stats_snapshot(self) -> Dict[str, int]:
        """Copy of the run/compile counters, taken under the run lock
        (reading the live Counter while run() inserts a first-time key
        would race the dict iteration)."""
        with self._lock:
            return dict(self.stats)

    @property
    def axis_names(self) -> Tuple[str, ...]:
        return tuple(n for n, _ in self.axes)

    @property
    def axis_name(self) -> str:
        """The sole axis name (1D substrates)."""
        if len(self.axes) != 1:
            raise ValueError(f"substrate has {len(self.axes)} axes; "
                             "use .axis_names")
        return self.axes[0][0]

    @property
    def shape(self) -> Tuple[int, ...]:
        return tuple(s for _, s in self.axes)

    @property
    def t(self) -> int:
        return int(np.prod(self.shape))

    def _donation(self, donate_argnums) -> Tuple[int, ...]:
        """Normalize a donation request (call under the run lock).

        Donated positions are reused by XLA for the program's outputs —
        the fused exchange buffers overwrite their inputs instead of
        copying.  On platforms without donation support (CPU) the
        request is dropped (counted in ``stats['donation_dropped']``)
        rather than emitting a per-compile warning.
        """
        if not donate_argnums:
            return ()
        if not _donation_supported():
            self.stats["donation_dropped"] += 1
            REGISTRY.counter("donation_dropped_total",
                             platform=jax.default_backend()).inc()
            obs_trace.event("donation_dropped",
                            platform=jax.default_backend())
            return ()
        return tuple(sorted({int(i) for i in donate_argnums}))

    def _attach_phases(self, sp: Optional["obs_trace.Span"],
                       snap: CollectiveTape) -> None:
        """Attach the bound tape's phases as leaf spans under ``sp``.

        Phases execute inside ONE compiled program, so per-phase host
        time is not observable; each phase becomes an instant child
        carrying the same bound ``sent``/``received`` arrays the
        AlphaKReport's PhaseStats are built from — span bytes therefore
        reconcile bitwise with the report by construction.
        """
        if sp is None:
            return
        for ph in snap.phases(self.t):
            sp.add_child(f"phase:{ph.name}", sent=ph.sent,
                         received=ph.received)

    @staticmethod
    def _call(fn: Callable, args, *, built: bool, body: str):
        """Call a program; the first call of one just built traces and
        compiles it (or loads it from the persistent cache), inside a
        ``substrate.compile`` span."""
        if not built:
            return fn(*args)
        with obs_trace.span("substrate.compile", body=body):
            return fn(*args)

    def run(self, shard_fn: Callable, *args, donate_argnums=()):
        """Execute ``shard_fn(*local_args, tape=tape)`` on every machine.

        Returns ``(outputs, tape)``: outputs with the substrate's leading
        axes restored, tape bound to concrete per-device counters.
        ``donate_argnums`` marks positional inputs whose buffers the
        compiled program may consume (jit-compiling substrates only;
        see :meth:`_donation` for the platform gate).  The caller must
        not reuse a donated array after the call.
        """
        raise NotImplementedError

    def __repr__(self) -> str:
        axes = ",".join(f"{n}={s}" for n, s in self.axes)
        return f"{type(self).__name__}({axes})"


class VmapSubstrate(Substrate):
    """t virtual machines on one device — nested vmap with axis names.

    ``jit=True`` compiles the vmapped program and caches it per
    (shard_fn, arg signature), exactly like ShardMapSubstrate — worth
    it for bodies of many small ops (the planner's sketch pass) where
    eager per-op dispatch dominates.  The cache key is *content-stable*
    for ``functools.partial`` bodies — (function, args, keywords), see
    ``_stable_fn_key`` — and falls back to function identity otherwise,
    so pass a partial of a module-level function (the core wrappers all
    do) or a stable function object to hit it.
    """

    def __init__(self, *axes: AxisSpec, jit: bool = False):
        super().__init__(*axes)
        self._jit = jit
        self._compiled = {}

    def _build(self, shard_fn: Callable):
        tape = CollectiveTape()

        def wrapper(*local):
            tape.reset()
            out = shard_fn(*local, tape=tape)
            return out, tape.traced()

        fn = wrapper
        for name, _ in reversed(self.axes):
            fn = jax.vmap(fn, axis_name=name)
        return fn, tape

    def run(self, shard_fn: Callable, *args, donate_argnums=()):
        with self._lock, obs_trace.span(
                "substrate.run", body=_fn_label(shard_fn),
                substrate=type(self).__name__, t=self.t) as sp:
            self.stats["runs"] += 1
            donate = self._donation(donate_argnums)
            built = False
            if not self._jit:
                fn, tape = self._build(shard_fn)   # eager: donation is moot
            else:
                key = (_stable_fn_key(shard_fn), donate,
                       tuple((jnp.shape(a), str(getattr(a, "dtype", type(a))))
                             for a in args))
                cached = self._compiled.get(key)
                built = cached is None
                if built:
                    fn, tape = self._build(shard_fn)
                    cached = self._compiled[key] = (
                        jax.jit(fn, donate_argnums=donate), tape)
                    self.stats["compiles"] += 1
                    self.stats[f"compiles[{_fn_label(shard_fn)}]"] += 1
                    if sp is not None:
                        sp.add_event("compile", body=_fn_label(shard_fn))
                else:
                    self.stats["program_cache_hits"] += 1
                    if sp is not None:
                        sp.add_event("program_cache_hit")
                fn, tape = cached
                if donate:
                    self.stats["donated_runs"] += 1
            out, frames = self._call(fn, args, built=built,
                                     body=_fn_label(shard_fn))
            snap = tape.bound_snapshot(jax.tree.map(np.asarray, frames))
            self._attach_phases(sp, snap)
            return out, snap


class ShardMapSubstrate(Substrate):
    """A real mesh via shard_map — one device per (virtual) machine.

    The per-device block keeps its leading mesh axes as size-1 dims;
    the wrapper strips them on the way in and restores them on the way
    out, so the body sees exactly what it sees under vmap.
    """

    def __init__(self, *axes: AxisSpec, mesh=None, jit: bool = True):
        super().__init__(*axes)
        if mesh is None:
            from repro.launch.mesh import make_mesh
            mesh = make_mesh(self.shape, self.axis_names)
        self.mesh = mesh
        self._jit = jit
        # (shard_fn, arg signature) -> (jitted fn, tape).  jax.jit's own
        # cache keys on function identity, so a fresh wrapper closure per
        # run() would recompile every call; reusing the wrapper (and its
        # tape, whose static phase metadata the trace populated) restores
        # compile caching for repeated runs of the same body.
        self._compiled = {}

    def _signature(self, shard_fn: Callable, args) -> tuple:
        return (_stable_fn_key(shard_fn),
                tuple((jnp.shape(a), str(getattr(a, "dtype", type(a))))
                      for a in args))

    def run(self, shard_fn: Callable, *args, donate_argnums=()):
        with self._lock, obs_trace.span(
                "substrate.run", body=_fn_label(shard_fn),
                substrate=type(self).__name__, t=self.t) as sp:
            self.stats["runs"] += 1
            donate = self._donation(donate_argnums) if self._jit else ()
            key = self._signature(shard_fn, args) + (donate,)
            cached = self._compiled.get(key)
            built = cached is None
            if built:
                tape = CollectiveTape()
                k = len(self.axes)
                lead = (0,) * k

                def wrapper(*local):
                    tape.reset()
                    stripped = [x[lead] for x in local]
                    out = shard_fn(*stripped, tape=tape)
                    restore = lambda y: jnp.reshape(jnp.asarray(y),
                                                    (1,) * k + jnp.shape(y))
                    return jax.tree.map(restore, (out, tape.traced()))

                spec = P(*self.axis_names)
                fn = jax.shard_map(wrapper, mesh=self.mesh,
                                   in_specs=tuple(spec for _ in args),
                                   out_specs=spec, check_vma=False)
                if self._jit:
                    fn = jax.jit(fn, donate_argnums=donate)
                cached = (fn, tape)
                self._compiled[key] = cached
                self.stats["compiles"] += 1
                self.stats[f"compiles[{_fn_label(shard_fn)}]"] += 1
                if sp is not None:
                    sp.add_event("compile", body=_fn_label(shard_fn))
            else:
                self.stats["program_cache_hits"] += 1
                if sp is not None:
                    sp.add_event("program_cache_hit")
            fn, tape = cached
            if donate:
                self.stats["donated_runs"] += 1
            out, frames = self._call(fn, args, built=built,
                                     body=_fn_label(shard_fn))
            snap = tape.bound_snapshot(jax.tree.map(np.asarray, frames))
            self._attach_phases(sp, snap)
            return out, snap


class SubstratePool:
    """Thread-safe cache of substrates keyed by their (normalized) axes.

    The serving layer's cache-sharing backbone: anywhere the cluster
    front door accepts ``substrate=``, a pool may be passed instead —
    :mod:`repro.cluster.api` detects the callable and resolves it with
    the axis spec each algorithm actually needs (``(t,)`` for the sorts
    and 1D joins, ``(("a", a), ("b", b))`` for RandJoin's machine
    matrix).  All queries that agree on the axes then share ONE
    substrate — and with it the compiled-program cache, its lock, and
    its compile counters.

    ``make`` overrides substrate construction (e.g. 1-device
    ``ShardMapSubstrate`` in the stress tests); the default is a
    jit-compiling :class:`VmapSubstrate`, the fast repeated-traffic
    executor on a single host.
    """

    def __init__(self, make: Optional[Callable[..., Substrate]] = None):
        self._make = make if make is not None \
            else (lambda *axes: VmapSubstrate(*axes, jit=True))
        self._lock = threading.Lock()
        self._subs: dict = {}

    def __call__(self, *axes: AxisSpec) -> Substrate:
        key = _normalize_axes(axes)
        with self._lock:
            sub = self._subs.get(key)
            if sub is None:
                sub = self._subs[key] = self._make(*key)
            return sub

    def substrates(self) -> Tuple[Substrate, ...]:
        with self._lock:
            return tuple(self._subs.values())

    def stats(self) -> collections.Counter:
        """Aggregate run/compile/program-cache counters across the pool."""
        total: collections.Counter = collections.Counter()
        for sub in self.substrates():
            total.update(sub.stats_snapshot())
        return total


# ---------------------------------------------------------------------------
# The process-wide default pool: fused execution behind the front door.
# ---------------------------------------------------------------------------
# Passing substrate=None to cluster.sort/join used to build a fresh
# *eager* VmapSubstrate per call: every query re-traced its whole
# multi-round body op by op — the per-round dispatch tax that made the
# kernel path slower end-to-end than the reference path even though
# every individual kernel won.  The default is now this shared pool of
# jit-compiling substrates: each algorithm's full multi-round body
# (tape counters, capacity checks and report fields are already
# in-program) compiles ONCE per (body, shape, params) into a single
# program and is reused across calls, exactly like the serving engine's
# pool.  Reset it (tests do, via conftest) to measure cold behavior.
_DEFAULT_POOL: Optional[SubstratePool] = None
_DEFAULT_POOL_LOCK = threading.Lock()


def default_pool() -> SubstratePool:
    """The shared jit-compiling SubstratePool behind ``substrate=None``."""
    global _DEFAULT_POOL
    with _DEFAULT_POOL_LOCK:
        if _DEFAULT_POOL is None:
            _DEFAULT_POOL = SubstratePool()
        return _DEFAULT_POOL


def reset_default_pool() -> None:
    """Drop the shared pool (and with it every cached compiled program)."""
    global _DEFAULT_POOL
    with _DEFAULT_POOL_LOCK:
        _DEFAULT_POOL = None


def recommend_pool_size(qps: float, service_time_s: float, *,
                        target_utilization: float = 0.7,
                        max_replicas: int = 64) -> int:
    """Replica count for an observed load, by Little's law.

    A replica serving one request at a time sustains
    ``1 / service_time_s`` QPS at full utilization; running fleets at
    ``target_utilization`` (default 0.7) leaves headroom so queueing
    delay stays bounded under arrival bursts.  So:

        replicas = ceil(qps * service_time_s / target_utilization)

    clamped to ``[1, max_replicas]``.  This is the QPS-derived sizing
    hook behind ``EngineReplicas.suggest_replicas()`` — feed it the
    measured arrival rate and mean execution time from ``ServeStats``.
    Non-positive qps or service time mean "no observed load": returns 1.
    """
    if not 0.0 < target_utilization <= 1.0:
        raise ValueError(
            f"target_utilization must be in (0, 1], got {target_utilization}")
    if max_replicas < 1:
        raise ValueError(f"max_replicas must be >= 1, got {max_replicas}")
    if qps <= 0 or service_time_s <= 0:
        return 1
    # round at 9 digits before ceil: 100*0.07/0.7 is 10.000000000000002
    # in binary and must size as 10 replicas, not 11
    need = math.ceil(round(qps * service_time_s / target_utilization, 9))
    return max(1, min(int(max_replicas), int(need)))


def default_substrate(*axes: AxisSpec,
                      prefer_mesh: bool = False) -> Substrate:
    """Pick an executor for the requested machine count.

    shard_map needs one device per machine; when the process doesn't
    have them (the common single-CPU test environment) fall back to
    virtual machines under vmap.
    """
    sub = VmapSubstrate(*axes)
    if prefer_mesh and len(jax.devices()) >= sub.t:
        return ShardMapSubstrate(*axes)
    return sub
