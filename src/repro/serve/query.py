"""The query-serving engine — sort/join traffic through one front door.

``QueryEngine`` turns the one-shot ``cluster.sort``/``cluster.join``
entry points into a service.  Callers build :func:`sort_query` /
:func:`join_query` specs — optionally with a **priority class** and a
**deadline** — and ``submit()`` them (or ``run()`` a whole trace); a
dispatcher thread admits them through a bounded **per-class priority
queue**, forms micro-batches by **continuous batching** (compatible
requests join in-flight buckets the moment they arrive — no fixed
batch-window boundary), and executes them over a shared
:class:`~repro.cluster.SubstratePool`.

SLO-aware admission, in one paragraph: classes are served strictly
best-first (``PRIORITY_HIGH`` before ``PRIORITY_NORMAL`` before
``PRIORITY_LOW``).  When the admission queue is **full**, a submit of
class c evicts the newest queued request of the *worst strictly-lower*
class — that request is shed with a typed :class:`ShedError` — and
only blocks (or raises :class:`AdmissionError`) when nothing worse is
queued.  So overload sheds by class instead of blocking everyone, and
a high-priority request can never be displaced by a lower one.
Requests carrying ``deadline_s`` that expire before execution are shed
with :class:`DeadlineExceededError` instead of being run late.  Every
shed is surfaced: ``ServeStats.shed``/``expired``/``shed_by_class``,
plus ``serve_shed_total{class,reason}`` counters and per-class
``serve_request_latency_seconds{class}`` histograms in both the
engine's registry and the process-global ``repro.obs`` registry.

What the engine shares across requests — the reason it beats a loop of
one-shot calls on sustained traffic:

* **Compiled programs.**  Every query of the same (kind, algorithm,
  shape, dtype, parameters) resolves to the same pooled substrate and
  the same stable body partial, so it reuses one compiled program; the
  one-shot path re-executes an eager vmap per call.  ``ServeStats``
  reports the compile count so recompiles are visible, not silent.
* **Plans.**  All requests share the planner's blake2b
  content-fingerprint LRU (thread-safe), so a repeated
  ``algorithm="auto"`` query skips its sketch pass.
* **Results of identical queries.**  Continuous batching groups
  compatible requests — same (kind, algorithm, parameter) bucket,
  sizes clustered by the SMMS length-bucketing scheduler — and
  **coalesces** duplicates: one execution serves every identical
  request in flight.  A bounded content-addressed **result LRU**
  (:class:`ResultCache`, shareable across engines) extends the same
  idea across time: the algorithms are pure and explicitly seeded, so
  an equal fingerprint provably means an equal result.  Either way
  each request receives its own :class:`QueryResult` (report copied —
  no cross-request state).

Scaling past one engine: :class:`EngineReplicas` puts N engines behind
one front door, sharing the SubstratePool and the ResultCache — the
4-layer cache contract (DESIGN.md §9) makes the sharing exact, so
replica-mode results are bitwise-identical to a single engine's.

Per-request results carry the full ``AlphaKReport`` (the paper's
(alpha, k) guarantee, surfaced per query), the plan when the planner
chose the algorithm, and the capacity-retry count; :meth:`QueryEngine
.stats` aggregates them into :class:`ServeStats` (QPS, p50/p99 latency
overall and per class, shed/expired counts, plan-cache hit rate,
recompiles, capacity retries).

Every query is executed by the same ``repro.cluster`` code path a
direct call uses — results are bitwise-identical to sequential one-shot
execution, which ``tests/test_serve.py`` asserts under concurrency.
"""
from __future__ import annotations

import collections
import copy
import dataclasses
import itertools
import queue
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.cluster.substrate import SubstratePool, recommend_pool_size
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.obs.metrics import MetricsRegistry

from .batching import ContinuousBatcher, LengthBucketScheduler

__all__ = [
    "AdmissionError", "EngineClosedError", "ShedError",
    "DeadlineExceededError", "ResultTimeout",
    "QuerySpec", "QueryResult", "ServeStats", "QueryEngine",
    "EngineReplicas", "ResultCache",
    "sort_query", "join_query", "run_spec",
    "PRIORITY_HIGH", "PRIORITY_NORMAL", "PRIORITY_LOW",
    "SERVE_COUNTERS", "reset_serve_counters",
]

# Priority classes: smaller = more important.  Any non-negative int is
# accepted (classes beyond LOW simply sort later); these three are the
# named tiers the metrics label by name.
PRIORITY_HIGH = 0
PRIORITY_NORMAL = 1
PRIORITY_LOW = 2
PRIORITY_NAMES = {PRIORITY_HIGH: "high", PRIORITY_NORMAL: "normal",
                  PRIORITY_LOW: "low"}


def _class_name(priority: int) -> str:
    return PRIORITY_NAMES.get(priority, str(priority))


# Module-level serving counters (submitted/admitted/rejected/served/
# failed/shed/expired/coalesced/executed/batches) — the serve twin of
# ops.DISPATCH_COUNTS, reset by the autouse conftest fixture so no test
# sees another test's traffic.
SERVE_COUNTERS: collections.Counter = collections.Counter()
_COUNTERS_LOCK = threading.Lock()


def _tick(name: str, n: int = 1) -> None:
    with _COUNTERS_LOCK:
        SERVE_COUNTERS[name] += n


def reset_serve_counters() -> None:
    with _COUNTERS_LOCK:
        SERVE_COUNTERS.clear()


class AdmissionError(RuntimeError):
    """The admission queue is full (non-blocking submit) or timed out."""


class ShedError(AdmissionError):
    """Shed under overload: a higher class took this request's slot."""


class DeadlineExceededError(RuntimeError):
    """The request's deadline passed before it could execute."""


class EngineClosedError(RuntimeError):
    """submit() after close()."""


class ResultTimeout(TimeoutError):
    """``ticket.result(timeout)`` expired; carries the ticket's status
    ("queued" / "batched" / "executing" / ...) so a deadline-aware
    caller can decide whether re-submitting is safe (still queued) or
    would duplicate work (already executing)."""

    def __init__(self, query_id: int, timeout: Optional[float],
                 status: str):
        self.query_id = query_id
        self.status = status
        super().__init__(
            f"query {query_id} not served within {timeout}s "
            f"(status: {status})")


# ---------------------------------------------------------------------------
# Query specs
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class QuerySpec:
    """One sort/join request: arrays + the cluster front-door parameters.

    ``arrays`` are the positional array operands (sort: ``(x,)`` or
    ``(x, values)``; join: ``(s_keys, s_rows, t_keys, t_rows)``);
    ``params`` everything that forwards to ``cluster.sort``/``cluster
    .join``.  Specs are content-fingerprinted (same blake2b scheme as
    the plan cache) for coalescing: equal fingerprint == equal query.

    ``priority`` and ``deadline_s`` are *serving* attributes — they
    shape admission and shedding but not the computation, so they are
    deliberately excluded from the fingerprint and the compatibility
    bucket: a high- and a low-priority copy of the same query coalesce
    to one execution.
    """
    kind: str                         # "sort" | "join"
    arrays: Tuple[Any, ...]
    params: Tuple[Tuple[str, Any], ...]   # sorted, hashable
    tag: str = ""                     # caller label, not part of identity
    priority: int = PRIORITY_NORMAL   # class: smaller = more important
    deadline_s: Optional[float] = None  # relative to submit; None = no SLO

    @property
    def kwargs(self) -> Dict[str, Any]:
        return dict(self.params)

    @property
    def size(self) -> int:
        """Total objects across operands — the micro-batcher's length.

        Metadata only (no device-to-host copy on the dispatcher path).
        """
        return int(sum(int(np.prod(np.shape(a))) for a in self.arrays))

    def fingerprint(self) -> str:
        from repro.planner.plan import fingerprint_arrays
        return fingerprint_arrays(
            *self.arrays,
            extra=f"serve|{self.kind}|n={len(self.arrays)}|{self.params!r}")

    def bucket_key(self) -> tuple:
        """Compatibility bucket: requests that may share a micro-batch.

        Reads shape/dtype metadata only — materializing operands to
        host here would copy megabytes per query inside the batching
        window (the content copy happens once, in ``fingerprint``).
        """
        shapes = tuple((np.shape(a),
                        str(getattr(a, "dtype", type(a).__name__)))
                       for a in self.arrays)
        return (self.kind, self.params, shapes)


def _spec(kind: str, arrays, params: Dict[str, Any], tag: str,
          priority: int, deadline_s: Optional[float]) -> QuerySpec:
    items = tuple(sorted(params.items()))
    try:
        hash(items)
    except TypeError as exc:
        raise TypeError(f"query parameters must be hashable, got {params!r}"
                        ) from exc
    if int(priority) < 0:
        raise ValueError(f"priority must be >= 0, got {priority}")
    if deadline_s is not None and float(deadline_s) < 0:
        raise ValueError(f"deadline_s must be >= 0, got {deadline_s}")
    return QuerySpec(kind=kind, arrays=tuple(arrays), params=items, tag=tag,
                     priority=int(priority),
                     deadline_s=None if deadline_s is None
                     else float(deadline_s))


def sort_query(x, *, algorithm: str = "auto", values=None, tag: str = "",
               priority: int = PRIORITY_NORMAL,
               deadline_s: Optional[float] = None, **params) -> QuerySpec:
    """A ``cluster.sort`` request; params forward to the front door."""
    arrays = (x,) if values is None else (x, values)
    params = dict(params, algorithm=algorithm, has_values=values is not None)
    return _spec("sort", arrays, params, tag, priority, deadline_s)


def join_query(s_keys, s_rows, t_keys, t_rows, *, t_machines: int,
               algorithm: str = "auto", tag: str = "",
               priority: int = PRIORITY_NORMAL,
               deadline_s: Optional[float] = None, **params) -> QuerySpec:
    """A ``cluster.join`` request; params forward to the front door."""
    params = dict(params, algorithm=algorithm, t_machines=int(t_machines))
    return _spec("join", (s_keys, s_rows, t_keys, t_rows), params, tag,
                 priority, deadline_s)


def run_spec(spec: QuerySpec, *, substrate=None):
    """Execute one spec through the cluster front door.

    The single spec-unpacking path: the engine calls it with its shared
    pool, tests and benchmarks call it bare for the sequential one-shot
    baseline.  Returns ``(value, report)`` exactly like ``cluster.*``.
    """
    from repro import cluster
    kw = spec.kwargs
    if spec.kind == "sort":
        kw.pop("has_values", None)
        values = spec.arrays[1] if len(spec.arrays) > 1 else None
        return cluster.sort(spec.arrays[0], values=values,
                            substrate=substrate, **kw)
    if spec.kind == "join":
        return cluster.join(*spec.arrays, substrate=substrate, **kw)
    raise ValueError(f"unknown query kind {spec.kind!r}")


def _copy_report(report):
    """A per-request report copy: shallow + fresh top-level lists.

    Requesters own their report and may decorate or edit it; copying
    the object and its list-valued fields (``phases``,
    ``sketch_phases``) keeps one request's edits invisible to its
    coalesced twins and to the result LRU.  Leaf entries (PhaseStats,
    arrays, the QueryPlan) are frozen/read-only by convention and stay
    shared.
    """
    if report is None:
        return None
    dup = copy.copy(report)
    for name, value in list(vars(dup).items()):
        if isinstance(value, list):
            setattr(dup, name, list(value))
    return dup


# ---------------------------------------------------------------------------
# Results + tickets
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class QueryResult:
    """Outcome of one request; ``report`` is the per-query AlphaKReport."""
    query_id: int
    spec: QuerySpec
    ok: bool
    value: Any = None                 # ((keys, values), ...) / JoinOutput
    report: Any = None                # AlphaKReport (None on failure)
    error: Optional[str] = None
    batch_id: int = -1
    coalesced: bool = False           # served by an identical in-flight twin
    cached: bool = False              # served from the result LRU
    latency_s: float = 0.0            # submit -> done (queueing included)
    exec_s: float = 0.0               # the cluster call alone
    # Per-request timeline, when the engine's tracer is enabled: the
    # root Span of this request's trace (planner / substrate / phase
    # children below it — see repro.obs.trace).  Coalesced twins share
    # the leader's trace; result-LRU hits carry none (nothing executed).
    trace_id: Optional[str] = None
    trace: Any = None

    @property
    def algorithm(self) -> Optional[str]:
        return getattr(self.report, "algorithm", None)

    @property
    def plan_cached(self) -> Optional[bool]:
        plan = getattr(self.report, "query_plan", None)
        return None if plan is None else bool(plan.cached)

    @property
    def capacity_retries(self) -> int:
        return max(0, int(getattr(self.report, "capacity_attempts", 1)) - 1)


class _Ticket:
    """Internal pending-request handle: submit() returns one.

    Lifecycle (``status()``): "queued" (in the admission queue) ->
    "batched" (on the continuous-batching board) -> "executing" ->
    one of "done" / "failed" / "shed" / "expired".
    """

    def __init__(self, query_id: int, spec: QuerySpec, submitted_at: float):
        self.query_id = query_id
        self.spec = spec
        self.submitted_at = submitted_at
        self.priority = max(0, int(getattr(spec, "priority",
                                           PRIORITY_NORMAL)))
        dl = getattr(spec, "deadline_s", None)
        self.deadline_at = None if dl is None else submitted_at + float(dl)
        self._done = threading.Event()
        self._result: Optional[QueryResult] = None
        self._exc: Optional[BaseException] = None
        self._status = "queued"
        self._claimed = False
        self._claim_lock = threading.Lock()

    def expired(self, now: float) -> bool:
        return self.deadline_at is not None and now > self.deadline_at

    def claim(self) -> bool:
        """Exactly-once finalization guard (first claimer delivers)."""
        with self._claim_lock:
            if self._claimed:
                return False
            self._claimed = True
            return True

    def done(self) -> bool:
        return self._done.is_set()

    def status(self) -> str:
        """Where the request is in its lifecycle (racy by nature: a
        'queued' answer may be 'executing' a microsecond later, but a
        terminal answer — done/failed/shed/expired — is final)."""
        return self._status

    def result(self, timeout: Optional[float] = None) -> QueryResult:
        if not self._done.wait(timeout):
            raise ResultTimeout(self.query_id, timeout, self._status)
        if self._exc is not None:
            raise self._exc
        return self._result


# ---------------------------------------------------------------------------
# Priority admission: the bounded, class-aware front door queue
# ---------------------------------------------------------------------------

class _AdmissionClosed(Exception):
    """Internal: the admission queue was closed (engine close())."""


class _PriorityAdmission:
    """Bounded multi-class queue: FIFO within a class, strict priority
    across classes, shed-by-class under overload.

    One capacity bound spans all classes.  ``get()`` always serves the
    best (lowest-numbered) nonempty class.  A ``put()`` into a full
    queue evicts the **newest** queued ticket of the **worst strictly
    lower** class and returns it to the caller (who sheds it with a
    typed error); if nothing strictly worse is queued, the put blocks /
    raises ``queue.Full`` — so a class can never displace itself or a
    better class, which is the no-priority-inversion invariant the
    property tests pin.

    ``close()`` wakes every blocked producer and consumer: producers
    see :class:`_AdmissionClosed` immediately (their tickets never
    entered, so nothing hangs), consumers drain what remains and then
    see :class:`_AdmissionClosed`.  Closing never blocks — this is the
    structural fix for the close()/submit() deadlock: no engine lock is
    ever held across a blocking queue operation.
    """

    def __init__(self, maxsize: int):
        if maxsize < 1:
            raise ValueError("maxsize must be >= 1")
        self.maxsize = int(maxsize)
        self._lock = threading.Lock()
        self._nonempty = threading.Condition(self._lock)
        self._notfull = threading.Condition(self._lock)
        self._classes: Dict[int, collections.deque] = {}
        self._size = 0
        self._closed = False
        self.peak = 0                 # high-water mark of queued tickets

    # ---- state --------------------------------------------------------
    def qsize(self) -> int:
        with self._lock:
            return self._size

    def depths(self) -> Dict[int, int]:
        with self._lock:
            return {c: len(d) for c, d in self._classes.items() if d}

    # ---- producer side ------------------------------------------------
    def _append_locked(self, ticket: _Ticket) -> None:
        self._classes.setdefault(ticket.priority,
                                 collections.deque()).append(ticket)
        self._size += 1
        self.peak = max(self.peak, self._size)
        self._nonempty.notify()

    def _pop_worse_locked(self, priority: int) -> Optional[_Ticket]:
        """Evict the newest ticket of the worst class > ``priority``.

        Newest-of-worst minimizes wasted wait: the evicted request has
        spent the least time queued, and older same-class requests keep
        their FIFO position.
        """
        worst = None
        for cls, dq in self._classes.items():
            if dq and cls > priority and (worst is None or cls > worst):
                worst = cls
        if worst is None:
            return None
        ticket = self._classes[worst].pop()
        self._size -= 1
        return ticket

    def put(self, ticket: _Ticket, block: bool = True,
            timeout: Optional[float] = None) -> Optional[_Ticket]:
        """Admit ``ticket``; returns the shed lower-class ticket if the
        admission evicted one, else None.  Raises ``queue.Full`` when
        full with nothing worse queued (after the block/timeout), and
        :class:`_AdmissionClosed` once closed."""
        with self._lock:
            if self._closed:
                raise _AdmissionClosed
            deadline = (None if timeout is None
                        else time.monotonic() + timeout)
            while self._size >= self.maxsize:
                shed = self._pop_worse_locked(ticket.priority)
                if shed is not None:
                    self._append_locked(ticket)
                    return shed
                if not block:
                    raise queue.Full
                remaining = (None if deadline is None
                             else deadline - time.monotonic())
                if remaining is not None and remaining <= 0:
                    raise queue.Full
                if not self._notfull.wait(remaining):
                    raise queue.Full
                if self._closed:
                    raise _AdmissionClosed
            self._append_locked(ticket)
            return None

    # ---- consumer side ------------------------------------------------
    def get(self, timeout: Optional[float] = None) -> Optional[_Ticket]:
        """Best class first, FIFO within it.  None on timeout; raises
        :class:`_AdmissionClosed` once closed AND drained (everything
        admitted before close is still delivered)."""
        with self._lock:
            deadline = (None if timeout is None
                        else time.monotonic() + timeout)
            while self._size == 0:
                if self._closed:
                    raise _AdmissionClosed
                remaining = (None if deadline is None
                             else deadline - time.monotonic())
                if remaining is not None and remaining <= 0:
                    return None
                if not self._nonempty.wait(remaining):
                    return None
            for cls in sorted(self._classes):
                dq = self._classes[cls]
                if dq:
                    ticket = dq.popleft()
                    self._size -= 1
                    self._notfull.notify()
                    return ticket
            raise AssertionError("size > 0 with all deques empty")

    def drain(self) -> List[_Ticket]:
        """Remove and return everything queued (close-path cleanup)."""
        with self._lock:
            out = [t for cls in sorted(self._classes)
                   for t in self._classes[cls]]
            self._classes.clear()
            self._size = 0
            self._notfull.notify_all()
            return out

    def close(self) -> None:
        with self._lock:
            self._closed = True
            self._nonempty.notify_all()
            self._notfull.notify_all()


# ---------------------------------------------------------------------------
# Shared result cache
# ---------------------------------------------------------------------------

class ResultCache:
    """Bounded content-addressed result LRU, shareable across engines.

    Pure + explicitly-seeded algorithms make an equal fingerprint
    provably imply an equal result, so serving from the cache is exact;
    mutated inputs hash elsewhere, so staleness is impossible by
    construction.  ``EngineReplicas`` passes one instance to every
    replica — that sharing is what keeps replica mode bitwise-identical
    to a single engine.
    """

    def __init__(self, size: int = 64):
        self.size = int(size)
        self._lock = threading.Lock()
        self._entries: "collections.OrderedDict[str, QueryResult]" = \
            collections.OrderedDict()

    def get(self, fp: str) -> Optional[QueryResult]:
        if self.size <= 0:
            return None
        with self._lock:
            hit = self._entries.get(fp)
            if hit is not None:
                self._entries.move_to_end(fp)
            return hit

    def put(self, fp: str, entry: QueryResult) -> None:
        if self.size <= 0:
            return
        with self._lock:
            self._entries[fp] = entry
            while len(self._entries) > self.size:
                self._entries.popitem(last=False)

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)


# ---------------------------------------------------------------------------
# Engine stats
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ServeStats:
    """Aggregate serving metrics for one engine (since construction)."""
    served: int = 0                   # results delivered (incl. coalesced)
    executed: int = 0                 # cluster.* calls actually run
    failed: int = 0
    rejected: int = 0                 # backpressure refusals
    shed: int = 0                     # overload evictions (ShedError)
    expired: int = 0                  # deadline sheds (DeadlineExceeded)
    coalesced: int = 0
    result_cache_hits: int = 0
    batches: int = 0
    wall_s: float = 0.0               # first submit -> last completion
    qps: float = 0.0
    p50_latency_s: float = 0.0
    p99_latency_s: float = 0.0
    peak_pending: int = 0             # admission-queue high-water mark
    # Per-class SLO views: {"high": ...} keyed by class name.
    shed_by_class: Dict[str, int] = dataclasses.field(default_factory=dict)
    served_by_class: Dict[str, int] = dataclasses.field(default_factory=dict)
    latency_by_class: Dict[str, Dict[str, float]] = dataclasses.field(
        default_factory=dict)         # class -> {p50, p99, p999}
    plan_cache_hits: int = 0
    plan_cache_misses: int = 0
    sketch_runs: int = 0
    plan_cache_hit_rate: float = 0.0
    compiles: int = 0                 # substrate recompile count
    program_cache_hits: int = 0
    capacity_retries: int = 0
    # Buffer donations requested by single-shot queries but dropped by
    # the substrate (platform without donation support, retrying
    # schedule, eager execution).  Nonzero on CPU is expected; nonzero
    # on GPU/TPU means the memory saving is not being realized.
    donation_dropped: int = 0
    # Fusion payoff, from the pool's labeled compile counters: compiled
    # programs per algorithm body (e.g. {"smms_shard": 1}) and substrate
    # runs per executed query.  Each algorithm's multi-round body is ONE
    # program, so a warm engine serves at 1.0 program-run per query
    # (capacity retries and cold compiles push it above 1).
    program_counts: Dict[str, int] = dataclasses.field(default_factory=dict)
    programs_per_query: float = 0.0

    def summary(self) -> Dict[str, Any]:
        d = dataclasses.asdict(self)
        for k, v in d.items():
            if isinstance(v, float):
                d[k] = round(v, 6)
        return d


# ---------------------------------------------------------------------------
# The engine
# ---------------------------------------------------------------------------


class QueryEngine:
    """Concurrent sort/join serving over the cluster front door.

    Parameters
    ----------
    max_pending : admission-queue bound (backpressure / shedding beyond
        it — see the module docstring for the per-class semantics).
    max_batch   : micro-batch size cap.
    batch_window_s : age-out for a cold batching bucket.  Continuous
        batching dispatches full, hot, or engine-idle buckets
        immediately; the window only bounds how long a cold bucket may
        wait for batchmates while the engine is busy.
    workers     : micro-batch executor threads (1 = execute inline in
        the dispatcher; substrates serialize per-substrate regardless).
    pool        : a SubstratePool (or any ``(*axes) -> Substrate``
        provider); defaults to a fresh pool of jit-compiling vmap
        substrates.  Passing one engine's pool to another shares the
        compiled programs too.
    tracer      : a :class:`repro.obs.Tracer` for per-request span
        trees; defaults to the process-global tracer (disabled unless
        ``repro.obs.enable()`` was called), so tracing costs nothing
        until someone opts in.  ``engine.tracer.last()`` /
        ``QueryResult.trace`` expose the captured trees.
    result_cache_size : content-addressed LRU of finished results
        (see :class:`ResultCache`).  0 disables.  Cached hits are
        flagged (``QueryResult.cached``) and counted in
        ``ServeStats.result_cache_hits``.
    result_cache : a :class:`ResultCache` instance to SHARE (replica
        mode); overrides ``result_cache_size``.
    autostart   : start the dispatcher thread immediately.
    """

    def __init__(self, *, max_pending: int = 256, max_batch: int = 8,
                 batch_window_s: float = 0.002, workers: int = 1,
                 pool: Optional[SubstratePool] = None,
                 result_cache_size: int = 64,
                 result_cache: Optional[ResultCache] = None,
                 tracer: Optional[obs_trace.Tracer] = None,
                 autostart: bool = True):
        if max_pending < 1 or max_batch < 1 or workers < 1:
            raise ValueError("max_pending, max_batch and workers must be >= 1")
        self.max_batch = int(max_batch)
        self.batch_window_s = float(batch_window_s)
        self.pool = pool if pool is not None else SubstratePool()
        self._admit = _PriorityAdmission(int(max_pending))
        self._batcher = ContinuousBatcher(
            max_batch=self.max_batch, window_s=self.batch_window_s,
            scheduler=LengthBucketScheduler(max_batch=self.max_batch))
        self._exec = (ThreadPoolExecutor(max_workers=workers,
                                         thread_name_prefix="serve-worker")
                      if workers > 1 else None)
        self._ids = itertools.count()
        self._batch_ids = itertools.count()
        self._lock = threading.Lock()          # stats below
        self.tracer = tracer if tracer is not None \
            else obs_trace.get_tracer()
        # Engine-local metrics registry: request counters + streaming
        # latency histograms (overall and per class), so a mid-run
        # stats() is O(buckets) however long the engine has served.
        self.metrics = MetricsRegistry()
        self._latency_hist = self.metrics.histogram(
            "serve_request_latency_seconds")
        self._exec_hist = self.metrics.histogram("serve_exec_seconds")
        self._first_submit: Optional[float] = None
        self._last_done: Optional[float] = None
        self._inflight: Dict[str, List[_Ticket]] = {}
        self._inflight_lock = threading.Lock()
        self.results = result_cache if result_cache is not None \
            else ResultCache(int(result_cache_size))
        self.result_cache_size = self.results.size
        from repro.planner import planner_stats
        self._planner_base = planner_stats()
        # stats() reports deltas since construction for the pool too —
        # an engine handed an already-warm pool must show 0 recompiles
        self._pool_base = (self.pool.stats()
                           if isinstance(self.pool, SubstratePool)
                           else collections.Counter())
        self._closed = False
        # guards ONLY the closed flag's idempotency — never held across
        # a blocking queue operation (the old code blocked in put()
        # under this lock, deadlocking a concurrent close(); admission's
        # own lock now orders submits against close atomically)
        self._close_lock = threading.Lock()
        self._started = False
        self._dispatcher = threading.Thread(target=self._dispatch_loop,
                                            name="serve-dispatcher",
                                            daemon=True)
        if autostart:
            self.start()

    # ---- lifecycle ----------------------------------------------------
    def start(self) -> "QueryEngine":
        if not self._started:
            self._started = True
            self._dispatcher.start()
        return self

    def close(self, wait: bool = True) -> None:
        """Stop admitting; drain and serve everything already admitted.

        Never blocks on the admission queue: closing wakes blocked
        submitters (they raise :class:`EngineClosedError`) and the
        dispatcher, which flushes its buckets and exits.
        """
        with self._close_lock:
            if self._closed:
                return
            self._closed = True
            self._admit.close()
            if not self._started:    # never started: fail queued tickets
                self._drain_failed("engine closed before start()")
                return
        if wait:
            self._dispatcher.join()
            if self._exec is not None:
                self._exec.shutdown(wait=True)
            # belt-and-braces: nothing can be queued here (the closed
            # admission refuses puts and the dispatcher drained), but a
            # hung .result() is the worst failure mode serving has
            self._drain_failed("engine closed while the request was "
                               "in the admission queue")

    def __enter__(self) -> "QueryEngine":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.close()

    # ---- engine-local metric helpers (the registry backs ServeStats) --
    def _count(self, name: str, n: int = 1) -> None:
        self.metrics.counter("serve_events_total", event=name).inc(n)

    def _count_value(self, name: str) -> int:
        return int(self.metrics.counter_value("serve_events_total",
                                              event=name))

    def _drain_failed(self, msg: str) -> None:
        for ticket in self._admit.drain():
            self._finalize(ticket, QueryResult(
                query_id=ticket.query_id, spec=ticket.spec, ok=False,
                error=msg))

    # ---- submission ---------------------------------------------------
    def submit(self, spec: QuerySpec, *, block: bool = True,
               timeout: Optional[float] = None) -> _Ticket:
        """Admit one query.  Returns a ticket; ``ticket.result()`` waits.

        Backpressure + shedding: when the admission queue is full, a
        submit first sheds the newest queued request of a strictly
        lower class (that ticket's ``result()`` raises
        :class:`ShedError`); with nothing worse queued, ``block=True``
        waits (up to ``timeout``) and ``block=False`` raises
        :class:`AdmissionError` immediately.
        """
        if self._closed:
            raise EngineClosedError("submit() on a closed engine")
        _tick("submitted")
        now = time.monotonic()
        ticket = _Ticket(next(self._ids), spec, now)
        try:
            shed = self._admit.put(ticket, block=block, timeout=timeout)
        except queue.Full:
            _tick("rejected")
            self._count("rejected")
            self._shed_metrics(ticket.priority, "rejected")
            raise AdmissionError(
                f"admission queue full ({self._admit.maxsize} pending)")
        except _AdmissionClosed:
            raise EngineClosedError("submit() on a closed engine")
        if shed is not None:
            self._shed(shed, ShedError(
                f"query {shed.query_id} (class "
                f"{_class_name(shed.priority)}) shed under overload for a "
                f"class-{_class_name(ticket.priority)} request"),
                "shed", reason="overload")
        _tick("admitted")
        with self._lock:
            # only an ADMITTED request starts the QPS wall clock — a
            # rejected burst must not deflate the lifetime throughput
            if self._first_submit is None:
                self._first_submit = now
        return ticket

    def run(self, specs: Sequence[QuerySpec],
            timeout: Optional[float] = None) -> List[QueryResult]:
        """Submit a whole trace and wait for every result (in order)."""
        tickets = [self.submit(s) for s in specs]
        return [t.result(timeout) for t in tickets]

    # ---- dispatch -----------------------------------------------------
    # Board budget, in multiples of max_batch: how many tickets may sit
    # on the batching board (open buckets + released-not-yet-executed
    # groups) at once.  The board is a small staging area, NOT a queue:
    # under overload the excess must stay in the bounded admission
    # queue, where class eviction and deadline expiry work — tickets
    # moved onto an unbounded board would be "queued unboundedly", the
    # exact failure mode shedding exists to prevent.
    _BOARD_BATCHES = 2

    def _dispatch_loop(self) -> None:
        batcher = self._batcher
        futures: List[Tuple[Any, tuple, List[_Ticket]]] = []
        # released-but-not-yet-executed groups, kept best-class-first.
        # Executing ONE group per cycle (not the whole release) is the
        # SLO lever: between any two batch executions the loop returns
        # to the admission queue, so a just-admitted high-priority
        # request waits at most one group execution before the
        # dispatcher sees it — never a full cycle's worth of batches.
        ready: List[Tuple[tuple, List[_Ticket]]] = []
        closed = False
        while True:
            if futures:
                live = []
                for fut, key, group in futures:
                    if fut.done():
                        try:
                            fut.result()
                        except Exception as exc:
                            self._fail_undone(group, exc)
                        batcher.mark_done(key)
                    else:
                        live.append((fut, key, group))
                futures = live
            now = time.monotonic()
            if ready:
                wait = 0.0            # work pending: don't sleep
            else:
                next_due = batcher.next_deadline(now)
                wait = (0.05 if next_due is None
                        else max(0.0, min(next_due - now, 0.05)))
            board = batcher.pending() + sum(len(g) for _, g in ready)
            budget = max(0, self._BOARD_BATCHES * self.max_batch - board)
            drained = 0
            if not closed and budget:
                try:
                    item = self._admit.get(timeout=wait)
                    while item is not None:
                        if self._enqueue(batcher, item):
                            drained += 1   # shed/failed tickets never
                        if drained >= budget:   # reached the board
                            break
                        item = self._admit.get(timeout=0)
                except _AdmissionClosed:
                    closed = True
            elif not ready and (futures or wait > 0):
                # board full (or closed) with nothing executable yet:
                # wait for the next bucket due-time / a worker to finish
                time.sleep(min(wait, 0.002) if wait > 0 else 0.0005)
            now = time.monotonic()
            idle = (not futures and not ready and drained == 0
                    and self._admit.qsize() == 0)
            ready.extend(batcher.release(now, idle=idle, flush=closed))
            # best class first: the overloaded engine spends its next
            # execution on the traffic with the tightest SLO
            ready.sort(key=lambda kg: min(t.priority for t in kg[1]))
            if ready:
                key, group = ready.pop(0)
                group = self._shed_expired(group)
                if group:
                    batcher.mark_dispatched(key, now)
                    if self._exec is not None:
                        futures.append(
                            (self._exec.submit(self._run_batch, group),
                             key, group))
                    else:
                        try:
                            self._run_batch(group)
                        except Exception as exc:
                            # the dispatcher must survive anything a
                            # batch can throw — a dead dispatcher hangs
                            # every pending and future query (reachable
                            # failures are caught per ticket in
                            # _run_batch/_execute; this is the backstop)
                            self._fail_undone(group, exc)
                        batcher.mark_done(key)
            if (closed and not futures and not ready
                    and batcher.pending() == 0):
                return

    def _enqueue(self, batcher: ContinuousBatcher,
                 ticket: _Ticket) -> bool:
        """Move an admitted ticket onto the batching board (or shed it).
        Returns True only when the ticket actually landed on the board
        (sheds don't consume board budget)."""
        now = time.monotonic()
        if ticket.expired(now):
            self._shed(ticket, DeadlineExceededError(
                f"query {ticket.query_id} deadline "
                f"({ticket.spec.deadline_s}s) passed before dispatch"),
                "expired", reason="deadline")
            return False
        try:
            key = ticket.spec.bucket_key()
            size = ticket.spec.size   # _run_batch needs both; a spec
        except Exception as exc:      # whose metadata can't be read must
            self._finalize(ticket, QueryResult(   # fail ITS ticket only
                query_id=ticket.query_id, spec=ticket.spec, ok=False,
                error=f"malformed query spec: {exc!r}"))
            return False
        ticket._status = "batched"
        batcher.add(key, ticket, size, now, ticket.deadline_at)
        return True

    def _shed_expired(self, group: List[_Ticket]) -> List[_Ticket]:
        """Deadline re-check at dispatch: queue+bucket time counts."""
        now = time.monotonic()
        keep = []
        for ticket in group:
            if ticket.expired(now):
                self._shed(ticket, DeadlineExceededError(
                    f"query {ticket.query_id} deadline "
                    f"({ticket.spec.deadline_s}s) passed before execution"),
                    "expired", reason="deadline")
            else:
                keep.append(ticket)
        return keep

    def _fail_undone(self, items: List[_Ticket], exc: Exception) -> None:
        """Backstop for 'impossible' dispatch errors: fail whatever the
        batch left unserved so no ticket blocks forever."""
        for it in items:
            if not it.done():
                self._finalize(it, QueryResult(
                    query_id=it.query_id, spec=it.spec, ok=False,
                    error=f"dispatch failure: {exc!r}"))

    # ---- execution ----------------------------------------------------
    def _run_batch(self, items: List[_Ticket]) -> None:
        if not items:
            return
        batch_id = next(self._batch_ids)
        _tick("batches")
        self._count("batches")
        leaders: List[Tuple[_Ticket, str]] = []
        for it in items:
            it._status = "executing"
            try:
                fp = it.spec.fingerprint()
            except Exception as exc:   # malformed operand bytes: fail the
                self._finalize(it, QueryResult(   # ticket, keep serving
                    query_id=it.query_id, spec=it.spec, ok=False,
                    error=f"unfingerprintable query spec: {exc!r}"))
                continue
            with self._inflight_lock:
                waiting = self._inflight.get(fp)
                if waiting is None:
                    self._inflight[fp] = [it]
                    leaders.append((it, fp))
                else:
                    waiting.append(it)
        for leader, fp in leaders:
            cached = self.results.get(fp)
            if cached is not None:
                result = self._from_cache(cached, leader, batch_id)
            else:
                result = self._execute(leader, batch_id)
                self._cache_put(fp, result)
            with self._inflight_lock:
                waiting = self._inflight.pop(fp)
            for w in waiting:
                self._finalize(w, result if w is leader
                               else self._replica(result, w))

    # ---- result LRU (content-addressed; pure algorithms => exact) -----
    def _cache_put(self, fp: str, result: QueryResult) -> None:
        if not result.ok:
            return
        # store a pristine report copy: the requester owns the delivered
        # report object and may decorate it — that must not leak into
        # later cache hits (each hit copies from this pristine one)
        self.results.put(fp, dataclasses.replace(
            result, report=_copy_report(result.report)))

    def _from_cache(self, cached: QueryResult, it: _Ticket,
                    batch_id: int) -> QueryResult:
        _tick("result_cache_hits")
        self._count("result_cache_hits")
        return dataclasses.replace(
            cached, query_id=it.query_id, spec=it.spec, batch_id=batch_id,
            cached=True, coalesced=False, exec_s=0.0,
            trace_id=None, trace=None,   # an LRU hit executed nothing
            report=_copy_report(cached.report))

    def _execute(self, it: _Ticket, batch_id: int) -> QueryResult:
        spec = it.spec
        t0 = time.monotonic()
        root = None
        # The ROOT span opens here — in the thread that runs the work —
        # so every instrumented layer below (planner, capacity retries,
        # substrate runs, tape phases, kernel dispatch events) attaches
        # to this request's tree via the thread's trace context.
        try:
            with self.tracer.trace("query", kind=spec.kind,
                                   query_id=it.query_id, batch=batch_id,
                                   tag=spec.tag) as root:
                value, report = run_spec(spec, substrate=self.pool)
            ok, error = True, None
        except Exception as exc:       # isolate failures per query
            value, report, ok, error = None, None, False, repr(exc)
        exec_s = time.monotonic() - t0
        return QueryResult(query_id=it.query_id, spec=spec, ok=ok,
                           value=value, report=report, error=error,
                           batch_id=batch_id, exec_s=exec_s,
                           trace_id=root.trace_id if root else None,
                           trace=root)

    def _replica(self, result: QueryResult, w: _Ticket) -> QueryResult:
        """A coalesced twin: same value, its own identity + report copy."""
        _tick("coalesced")
        self._count("coalesced")
        return dataclasses.replace(
            result, query_id=w.query_id, spec=w.spec, coalesced=True,
            report=_copy_report(result.report))

    # ---- delivery -----------------------------------------------------
    def _shed_metrics(self, priority: int, reason: str) -> None:
        """Tick shed counters in the engine AND global registries."""
        labels = {"class": _class_name(priority), "reason": reason}
        self.metrics.counter("serve_shed_total", **labels).inc()
        obs_metrics.REGISTRY.counter("serve_shed_total", **labels).inc()

    def _shed(self, ticket: _Ticket, exc: Exception, status: str,
              reason: str) -> None:
        """Fail a ticket with a typed shed error: its ``result()``
        raises ``exc`` (never a hung ``_done`` event)."""
        if not ticket.claim():
            return
        now = time.monotonic()
        result = QueryResult(query_id=ticket.query_id, spec=ticket.spec,
                             ok=False, error=repr(exc))
        result.latency_s = now - ticket.submitted_at
        with self._lock:
            self._last_done = now
        _tick(status)
        self._count(status)
        self._shed_metrics(ticket.priority, reason)
        ticket._status = status
        ticket._exc = exc
        ticket._result = result
        ticket._done.set()

    def _finalize(self, it: _Ticket, result: QueryResult) -> None:
        if not it.claim():        # already delivered (e.g. the backstop
            return                # raced a still-running worker)
        done = time.monotonic()
        result.latency_s = done - it.submitted_at
        with self._lock:
            self._last_done = done
        cname = _class_name(it.priority)
        if result.ok:
            self._count("served")
            self.metrics.counter("serve_requests_total",
                                 **{"class": cname,
                                    "outcome": "served"}).inc()
            if not result.coalesced and not result.cached:
                # a real execution (retries only counted once per run)
                self._count("executed")
                self._exec_hist.observe(result.exec_s)
                if result.capacity_retries:
                    self._count("capacity_retries",
                                result.capacity_retries)
            self._latency_hist.observe(result.latency_s)
            self.metrics.histogram("serve_request_latency_seconds",
                                   **{"class": cname}
                                   ).observe(result.latency_s)
            _tick("served")
            it._status = "done"
        else:
            self._count("failed")
            self.metrics.counter("serve_requests_total",
                                 **{"class": cname,
                                    "outcome": "failed"}).inc()
            _tick("failed")
            it._status = "failed"
        it._result = result
        it._done.set()

    # ---- metrics ------------------------------------------------------
    def pending(self) -> int:
        """Requests currently queued for admission (routing signal)."""
        return self._admit.qsize()

    def stats(self) -> ServeStats:
        from repro.planner import planner_stats
        now = planner_stats()
        delta = {k: now.get(k, 0) - self._planner_base.get(k, 0)
                 for k in set(now) | set(self._planner_base)}
        pool_now = (self.pool.stats() if isinstance(self.pool,
                                                    SubstratePool)
                    else collections.Counter())
        pool_stats = {k: pool_now.get(k, 0) - self._pool_base.get(k, 0)
                      for k in set(pool_now) | set(self._pool_base)}
        with self._lock:
            wall = ((self._last_done - self._first_submit)
                    if self._first_submit is not None
                    and self._last_done is not None else 0.0)
        served = self._count_value("served")
        executed = self._count_value("executed")
        hits = delta.get("cache_hits", 0)
        misses = delta.get("cache_misses", 0)
        shed_by_class: Dict[str, int] = {}
        shed = expired = 0
        for labels, v in self.metrics.counters_matching(
                "serve_shed_total").items():
            lab = dict(labels)
            shed_by_class[lab.get("class", "?")] = \
                shed_by_class.get(lab.get("class", "?"), 0) + int(v)
            if lab.get("reason") == "deadline":
                expired += int(v)
            elif lab.get("reason") == "overload":
                shed += int(v)
        served_by_class = {
            dict(labels).get("class", "?"): int(v)
            for labels, v in self.metrics.counters_matching(
                "serve_requests_total").items()
            if dict(labels).get("outcome") == "served"}
        latency_by_class = {
            dict(labels).get("class", "?"): {
                "p50": hist.quantile(0.50), "p99": hist.quantile(0.99),
                "p999": hist.quantile(0.999)}
            for labels, hist in self.metrics.histograms_matching(
                "serve_request_latency_seconds").items()
            if labels}   # the unlabeled histogram is the overall one
        # percentiles straight from the streaming histogram: O(buckets)
        # however many requests this engine has served
        return ServeStats(
            served=served,
            executed=executed,
            failed=self._count_value("failed"),
            rejected=self._count_value("rejected"),
            shed=shed,
            expired=expired,
            coalesced=self._count_value("coalesced"),
            result_cache_hits=self._count_value("result_cache_hits"),
            batches=self._count_value("batches"),
            wall_s=wall,
            qps=served / wall if wall > 0 else 0.0,
            p50_latency_s=self._latency_hist.quantile(0.50),
            p99_latency_s=self._latency_hist.quantile(0.99),
            peak_pending=self._admit.peak,
            shed_by_class=shed_by_class,
            served_by_class=served_by_class,
            latency_by_class=latency_by_class,
            plan_cache_hits=hits,
            plan_cache_misses=misses,
            sketch_runs=delta.get("sketch_runs", 0),
            plan_cache_hit_rate=(hits / (hits + misses)
                                 if hits + misses else 0.0),
            compiles=pool_stats.get("compiles", 0),
            program_cache_hits=pool_stats.get("program_cache_hits", 0),
            capacity_retries=self._count_value("capacity_retries"),
            donation_dropped=pool_stats.get("donation_dropped", 0),
            program_counts={k[len("compiles["):-1]: v
                            for k, v in sorted(pool_stats.items())
                            if k.startswith("compiles[") and v},
            programs_per_query=(pool_stats.get("runs", 0) / executed
                                if executed else 0.0),
        )


# ---------------------------------------------------------------------------
# Engine replicas: one front door, N engines, shared caches
# ---------------------------------------------------------------------------

class EngineReplicas:
    """N :class:`QueryEngine` replicas behind one front door.

    All replicas share ONE :class:`~repro.cluster.SubstratePool` (and
    with it every compiled program) and ONE :class:`ResultCache`.  The
    4-layer cache contract (DESIGN.md §9) is what makes that exact
    rather than approximate: (1) the planner's plan cache is
    process-global and thread-safe, (2) substrates serialize each
    ``run()`` under a per-substrate lock and hand back bound-snapshot
    tapes, so interleaved replicas can never corrupt each other's
    reports, (4) results are content-addressed over pure seeded
    algorithms, so a cross-replica hit is provably the same answer.
    Layer (3), in-flight coalescing, stays per-replica — identical
    queries racing on two replicas may execute twice, which costs work
    but never changes an answer.  ``tests/test_serve_slo.py`` pins
    replica-vs-single-engine results bitwise.

    Routing: least-pending replica, round-robin among ties; a
    non-blocking submit that one replica refuses is offered to the
    others before :class:`AdmissionError` propagates.

    ``suggest_replicas()`` is the QPS-derived autoscaling hook: it
    feeds the measured arrival rate and execution time into
    :func:`repro.cluster.substrate.recommend_pool_size`.
    """

    def __init__(self, replicas: int = 2, *,
                 pool: Optional[SubstratePool] = None,
                 result_cache_size: int = 64,
                 **engine_kw):
        if replicas < 1:
            raise ValueError("replicas must be >= 1")
        engine_kw.pop("result_cache", None)
        self.pool = pool if pool is not None else SubstratePool()
        self.results = ResultCache(int(result_cache_size))
        self.engines = [QueryEngine(pool=self.pool,
                                    result_cache=self.results,
                                    **engine_kw)
                        for _ in range(replicas)]
        self._rr = itertools.count()

    # ---- lifecycle ----------------------------------------------------
    def __enter__(self) -> "EngineReplicas":
        for e in self.engines:
            e.start()
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def close(self, wait: bool = True) -> None:
        for e in self.engines:
            e.close(wait=wait)

    # ---- traffic ------------------------------------------------------
    def submit(self, spec: QuerySpec, *, block: bool = True,
               timeout: Optional[float] = None) -> _Ticket:
        n = len(self.engines)
        start = next(self._rr) % n
        order = sorted(range(n),
                       key=lambda i: (self.engines[i].pending(),
                                      (i - start) % n))
        last: Optional[Exception] = None
        for i in order:
            try:
                return self.engines[i].submit(spec, block=block,
                                              timeout=timeout)
            except AdmissionError as exc:
                last = exc            # full here; try a sibling first
        raise last if last is not None else AdmissionError("no replicas")

    def run(self, specs: Sequence[QuerySpec],
            timeout: Optional[float] = None) -> List[QueryResult]:
        tickets = [self.submit(s) for s in specs]
        return [t.result(timeout) for t in tickets]

    # ---- metrics ------------------------------------------------------
    def replica_stats(self) -> List[ServeStats]:
        return [e.stats() for e in self.engines]

    def stats(self) -> ServeStats:
        """Fleet view: counts summed, percentiles worst-of-replicas
        (a fleet meets an SLO only if every replica does)."""
        per = self.replica_stats()
        agg = ServeStats()
        for s in per:
            for f in ("served", "executed", "failed", "rejected", "shed",
                      "expired", "coalesced", "result_cache_hits",
                      "batches", "plan_cache_hits", "plan_cache_misses",
                      "sketch_runs", "capacity_retries",
                      "program_cache_hits"):
                setattr(agg, f, getattr(agg, f) + getattr(s, f))
            for cls, v in s.shed_by_class.items():
                agg.shed_by_class[cls] = agg.shed_by_class.get(cls, 0) + v
            for cls, v in s.served_by_class.items():
                agg.served_by_class[cls] = \
                    agg.served_by_class.get(cls, 0) + v
            agg.wall_s = max(agg.wall_s, s.wall_s)
            agg.peak_pending = max(agg.peak_pending, s.peak_pending)
            agg.p50_latency_s = max(agg.p50_latency_s, s.p50_latency_s)
            agg.p99_latency_s = max(agg.p99_latency_s, s.p99_latency_s)
        # the pool is shared: count its compiles (and its donation
        # drops) once, not per replica
        agg.compiles = per[0].compiles if per else 0
        agg.donation_dropped = per[0].donation_dropped if per else 0
        agg.qps = agg.served / agg.wall_s if agg.wall_s > 0 else 0.0
        hm = agg.plan_cache_hits + agg.plan_cache_misses
        agg.plan_cache_hit_rate = agg.plan_cache_hits / hm if hm else 0.0
        return agg

    def suggest_replicas(self, *, target_utilization: float = 0.7,
                         max_replicas: int = 64) -> int:
        """QPS-derived sizing from observed traffic (Little's law)."""
        agg = self.stats()
        service = max(e.metrics.histogram("serve_exec_seconds").mean
                      for e in self.engines)
        return recommend_pool_size(agg.qps, service,
                                   target_utilization=target_utilization,
                                   max_replicas=max_replicas)
