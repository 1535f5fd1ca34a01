"""Shape-aware kernel dispatch — the switch between Pallas and jnp paths.

This module is the single entry the cluster hot loops call (`ops.sort`,
`ops.sort_kv`, `ops.searchsorted`, `ops.bucketize_histogram`,
`ops.sort_partition[_kv]`, `ops.merge_sorted_rows[_kv]`).  Each call
picks one of two backends:

* ``"reference"`` — the plain jnp implementation (``jnp.sort``,
  ``jnp.argsort``, ``jnp.searchsorted``).  Always available, always the
  semantic contract.
* ``"pallas"``    — the purpose-built kernels in ``bitonic.py`` /
  ``radix.py`` / ``bucketize.py`` / ``fused.py``, with the dispatch
  layer handling pad-to-pow2 with sort sentinels, key/index packing for
  stable payload sorts, and the dtype and shape eligibility gate
  (:func:`kernel_eligible`).  The reference is taken only for operands
  that gate refuses (exotic dtypes, >2D operands, rows too long for VMEM
  residency) and the refusal ticks a ``"reference"`` dispatch; an
  eligible operand always runs a kernel, never a silent fallback.

Within the pallas backend the sort family is the bitonic network
(``sort_kernel_choice``).  The LSD radix kernel runs only under
:func:`force_sort_kernel` (tests, budget benches): its in-kernel scatter
and gather have no Mosaic lowering, so it cannot run on TPU, and the
CPU interpreter emulates the scatter scalar-wise (~30x over the
network).  Radix dispatches tick ``DISPATCH_COUNTS[(op, "radix")]`` so
the fusion budgets stay enforceable per family.

Dispatch-count economy: the fused ``sort_partition[_kv]`` collapses the
sort → searchsorted chain into one kernel pass, and ``pad_pow2`` +
``prepadded=True`` / ``valid_len=`` let a round pad once instead of
once per op — see DESIGN.md §6 (fused execution) and the per-algorithm
budgets in ``benchmarks/bench_sort.DISPATCH_BUDGET``.

Every kernel-path result is bitwise-identical to the reference path —
payload-carrying sorts route through a (key, arange) lexicographic pair
sort (bitonic) or carry the stable permutation through the counting
passes (radix), either way reproducing the *stable* argsort permutation
exactly; the differential suites in ``tests/test_kernel_dispatch.py``
and ``tests/test_radix.py`` pin this.  The bitonic parity contract
covers NaN-free keys (the cluster pipeline's standing precondition:
keys strictly below the PAD sentinel).  NaN keys cannot be ordered by a
comparison network — the bitonic kernels then return a permutation of
the input (swap-based compare-exchange never fabricates or duplicates
values) while jnp.sort moves NaNs last.  The radix path's contract is
strictly wider: NaNs canonicalize to the all-ones key bits, so they
sort last in input order — full jnp.sort parity, NaNs included.

``backend=None`` resolves to the module default: ``DEFAULT_BACKEND``
(seeded from the ``REPRO_KERNEL_BACKEND`` env var) when set, otherwise
the platform's own — ``"pallas"`` on TPU, ``"reference"`` elsewhere —
so a whole test run can be flipped to the kernel path without touching
call sites.

Dispatch accounting — TWO counters with different semantics:

* ``DISPATCH_COUNTS[(op, path)]`` ticks once per *trace* (dispatch
  decision), NOT per execution: a query whose fused body is served from
  the compiled-program cache ticks nothing.  This is exactly what the
  fusion budget gates want ("how many kernel launches does one cold
  query trace?") and stays their contract.  Mirrored into the obs
  registry as ``kernel_dispatch_traces_total{op,path}``.
* ``kernel_dispatch_execs_total{op,path}`` (obs registry) ticks once
  per *execution* — a ``jax.debug.callback`` inserted at trace time
  fires every time the compiled program actually runs, so cached
  re-executions are visible.  Opt-in via ``REPRO_EXEC_COUNTS=1`` or
  :func:`enable_exec_counts` because the callback is baked into the
  compiled program: toggling only affects programs compiled *after* the
  flip (``reset_default_pool()`` to re-trace), and host callbacks add
  per-execution overhead, so the default stays off.

With tracing active (``repro.obs.trace``), every dispatch decision also
lands as a ``kernel_dispatch`` event on the enclosing span.

Kernel mode follows the platform, resolved at the first dispatch (never
at import): on CPU the kernels run through the Pallas interpreter (the
correctness path the test suites use), on TPU they compile with Mosaic.
:func:`platform` is the one place that asks JAX.
"""
from __future__ import annotations

import collections
import contextlib
import functools
import os
import threading

import jax
import jax.numpy as jnp

from . import bitonic, bucketize, fused, radix, flash_attention as fa
from .radix import key_to_bits, bits_to_key
from ..obs import trace as obs_trace
from ..obs.metrics import REGISTRY

BACKENDS = ("reference", "pallas")
# Explicit process-wide default; None = the platform's own
# (:func:`platform_backend`).
DEFAULT_BACKEND = os.environ.get("REPRO_KERNEL_BACKEND")

# Largest padded lane count a VMEM-resident kernel row may occupy: every
# main-path kernel compiles for v5e at this bound (tests/test_tpu_compile.py)
# under the kernels' 64 MiB scoped-VMEM limit.  Longer rows take the
# reference path.
MAX_KERNEL_LANES = 1 << 16

# Bound-row rows longer than this make the rank-merge kernel block its
# bound side into (1, RANK_MERGE_BOUND_BLOCK) slices (fused.merge_ranks
# bound_block=...) — the double-buffered variant whose per-step VMEM is
# O(block) instead of O(row).
RANK_MERGE_BOUND_BLOCK = 1 << 11

# force_sort_kernel override: None = the bitonic network.
_FORCE_SORT_KERNEL = None

# (op, path) -> number of dispatch decisions, counted at trace time.
# Ticks happen while substrates trace concurrently-submitted queries, so
# the read-modify-write goes under a lock (Counter.__iadd__ is not atomic).
DISPATCH_COUNTS: collections.Counter = collections.Counter()
_COUNTS_LOCK = threading.Lock()

# Execution-time counting (see the module docstring): when on, _tick
# inserts a host callback so kernel_dispatch_execs_total in the obs
# registry ticks per program EXECUTION, cached programs included.
EXEC_COUNTS_ENABLED = os.environ.get("REPRO_EXEC_COUNTS", "0") == "1"

_KERNEL_KEY_DTYPES = frozenset(
    jnp.dtype(d) for d in (jnp.float32, jnp.bfloat16, jnp.int32))

__all__ = [
    "sort", "sort_kv", "searchsorted", "bucketize_histogram",
    "sort_partition", "sort_partition_kv", "pad_pow2",
    "merge_sorted_rows", "merge_sorted_rows_kv", "flash_attention",
    "resolve_backend", "reset_dispatch_counts", "kernel_eligible",
    "sort_kernel_choice", "force_sort_kernel",
    "key_to_bits", "bits_to_key",
    "platform", "interpret_mode", "platform_backend",
    "BACKENDS", "DEFAULT_BACKEND", "DISPATCH_COUNTS",
    "MAX_KERNEL_LANES", "RANK_MERGE_BOUND_BLOCK",
    "EXEC_COUNTS_ENABLED",
    "enable_exec_counts", "exec_dispatch_counts",
]


@functools.lru_cache(maxsize=None)
def platform() -> str:
    """The backend JAX computes on ("cpu", "tpu", ...).  Asked at the
    first dispatch, not at import: importing ``repro`` initialises no
    backend."""
    return jax.default_backend()


def interpret_mode() -> bool:
    """Kernels run through the Pallas interpreter exactly on CPU."""
    return platform() == "cpu"


def platform_backend() -> str:
    """The default kernel backend of this platform: the kernels where
    they compile (TPU), the jnp reference elsewhere."""
    return "pallas" if platform() == "tpu" else "reference"


def resolve_backend(backend) -> str:
    """None -> module default; otherwise validate the explicit choice."""
    b = backend if backend is not None else (DEFAULT_BACKEND
                                             or platform_backend())
    if b not in BACKENDS:
        raise ValueError(f"unknown kernel backend {b!r}; "
                         f"expected one of {BACKENDS}")
    return b


def reset_dispatch_counts() -> None:
    """Clear the per-trace counter (the registry's execution counters
    are reset separately, via ``repro.obs.reset_registry``)."""
    with _COUNTS_LOCK:
        DISPATCH_COUNTS.clear()


def enable_exec_counts(on: bool = True) -> None:
    """Flip execution-time dispatch counting for future traces.

    Already-compiled programs keep their old behavior (the callback is
    baked in at trace time) — call
    ``repro.cluster.substrate.reset_default_pool()`` to re-trace.
    """
    global EXEC_COUNTS_ENABLED
    EXEC_COUNTS_ENABLED = bool(on)


def exec_dispatch_counts():
    """{(op, path): executions} from the registry's exec counter."""
    out = {}
    for labels, v in REGISTRY.counters_matching(
            "kernel_dispatch_execs_total").items():
        d = dict(labels)
        out[(d.get("op", "?"), d.get("path", "?"))] = int(v)
    return out


def _exec_tick(op: str, path: str) -> None:
    # Host callback body: fires once per execution of the compiled
    # program that traced the dispatch (jax.debug.callback), on a
    # runtime thread — the registry counter is its own lock domain.
    REGISTRY.counter("kernel_dispatch_execs_total", op=op, path=path).inc()


def _tick(op: str, path: str) -> None:
    with _COUNTS_LOCK:
        DISPATCH_COUNTS[(op, path)] += 1
    REGISTRY.counter("kernel_dispatch_traces_total", op=op, path=path).inc()
    obs_trace.event("kernel_dispatch", op=op, path=path)
    if EXEC_COUNTS_ENABLED:
        jax.debug.callback(functools.partial(_exec_tick, op, path))


_next_pow2 = bitonic._next_pow2


def _key_dtype_ok(x) -> bool:
    return jnp.dtype(x.dtype) in _KERNEL_KEY_DTYPES


def _lanes_ok(n: int) -> bool:
    return 1 <= _next_pow2(n) <= MAX_KERNEL_LANES


def pad_pow2(x: jnp.ndarray, fill=None) -> jnp.ndarray:
    """Pad the leading axis to the next power of two (min 2).

    ``fill`` defaults to the dtype's sort sentinel (+inf / iinfo.max),
    which sorts strictly last — the amortized-padding entry point: a
    round pads its operands ONCE, then calls ``sort``/``sort_kv`` with
    ``prepadded=True`` and ``searchsorted`` with ``valid_len=`` instead
    of letting every op pad and unpad its own copy.
    """
    n = x.shape[0]
    np2 = max(2, _next_pow2(n))
    if np2 == n:
        return x
    if fill is None:
        fill = bitonic.sort_sentinel(x.dtype)
    widths = ((0, np2 - n),) + ((0, 0),) * (x.ndim - 1)
    return jnp.pad(x, widths, constant_values=fill)


def kernel_eligible(op: str, x, y=None) -> bool:
    """Would the Pallas path take these operands?  Shape/dtype gate only.

    The dispatchers below call this before routing to a kernel; callers
    that pick between *algorithms* depending on kernel availability (the
    planner's sketch layer chooses its sorted-runs heavy-hitter pass vs
    the O(k)-memory Misra-Gries scan) consult it without dispatching.
    ``y`` is the second operand where the op has one (sort_kv values,
    searchsorted queries, merge payload).
    """
    if op == "sort":
        return x.ndim in (1, 2) and _key_dtype_ok(x) and _lanes_ok(x.shape[-1])
    if op == "sort_kv":
        return (x.ndim == 1 and _key_dtype_ok(x) and _lanes_ok(x.shape[0])
                and (y is None or y.shape[:1] == x.shape))
    if op == "searchsorted":
        return (x.ndim == 1 and y is not None and y.ndim == 1
                and x.shape[0] > 0 and y.shape[0] > 0 and _key_dtype_ok(x)
                and jnp.dtype(x.dtype) == jnp.dtype(y.dtype)
                and _lanes_ok(x.shape[0]))
    if op == "bucketize_histogram":
        return (x.ndim == 1 and y is not None and y.ndim == 1
                and _key_dtype_ok(x)
                and jnp.dtype(x.dtype) == jnp.dtype(y.dtype)
                and _lanes_ok(max(1, y.shape[0])))
    if op in ("sort_partition", "sort_partition_kv"):
        return (x.ndim == 1 and _key_dtype_ok(x) and _lanes_ok(x.shape[0])
                and y is not None and y.ndim == 1 and y.shape[0] > 0
                and jnp.dtype(x.dtype) == jnp.dtype(y.dtype)
                and _lanes_ok(y.shape[0]))
    if op == "radix":
        # the radix family's own gate: eligible sort operands whose key
        # dtype has a bit specialization (all of _KERNEL_KEY_DTYPES
        # today, but the radix core needs no pow2 padding)
        return x.ndim in (1, 2) and _key_dtype_ok(x) and _lanes_ok(x.shape[-1])
    if op in ("merge_sorted_rows", "merge_sorted_rows_kv"):
        if not _key_dtype_ok(x):
            return False
        tp2, cp2 = bitonic.merge_padded_shape(*x.shape)
        if _lanes_ok(tp2 * cp2):
            return True               # in-VMEM hierarchical network merge
        # rank-merge path: per-step VMEM is one query block and one
        # bound block, so only the row length is lane-bound; the row
        # count just sizes the grid
        return _lanes_ok(cp2) and tp2 <= 512
    raise ValueError(f"unknown op {op!r}")


def sort_kernel_choice() -> str:
    """Which sort-kernel family the pallas path runs: ``"bitonic"``,
    unless a :func:`force_sort_kernel` context pins ``"radix"``."""
    return _FORCE_SORT_KERNEL or "bitonic"


@contextlib.contextmanager
def force_sort_kernel(kind):
    """Pin :func:`sort_kernel_choice` to one family for the duration.

    ``kind``: ``"radix"``, ``"bitonic"``, or ``None`` (restore the
    default, bitonic).  Used by the differential tests and the
    dispatch-budget bench to exercise the radix paths, which nothing
    picks otherwise.  Affects *trace-time* decisions only —
    already-compiled programs keep the family they traced with
    (``reset_default_pool()`` to re-trace).
    """
    if kind not in (None, "bitonic", "radix"):
        raise ValueError(f"unknown sort kernel family {kind!r}")
    global _FORCE_SORT_KERNEL
    prev = _FORCE_SORT_KERNEL
    _FORCE_SORT_KERNEL = kind
    try:
        yield
    finally:
        _FORCE_SORT_KERNEL = prev


# ---------------------------------------------------------------------------
# sort / sort_kv
# ---------------------------------------------------------------------------

def sort(x: jnp.ndarray, *, backend=None, block_rows: int = 8,
         prepadded: bool = False) -> jnp.ndarray:
    """Ascending sort along the last axis.  x: (n,) or (rows, n).

    ``prepadded=True`` declares that the caller already padded the row
    to a power of two with the dtype's sort sentinel (``pad_pow2``):
    the kernel path skips its own pad/unpad round trip and the result
    *stays padded* (sentinel tail last) — the amortized-padding fast
    path for callers that chain several ops over one padded buffer.
    """
    if prepadded and x.shape[-1] != max(2, _next_pow2(x.shape[-1])):
        raise ValueError(f"prepadded=True requires a power-of-two row "
                         f"length (use ops.pad_pow2), got {x.shape[-1]}")
    b = resolve_backend(backend)
    if b == "pallas" and kernel_eligible("sort", x):
        x2 = x[None, :] if x.ndim == 1 else x
        if sort_kernel_choice() == "radix":
            _tick("sort", "radix")
            out, _ = radix.radix_sort(
                x2, block_rows=min(block_rows, x2.shape[0]),
                interpret=interpret_mode())
        else:
            _tick("sort", "pallas")
            out = bitonic.bitonic_sort(
                x2, block_rows=min(block_rows, x2.shape[0]),
                interpret=interpret_mode())
        return out[0] if x.ndim == 1 else out
    _tick("sort", "reference")
    return jnp.sort(x, axis=-1)


def sort_kv(keys: jnp.ndarray, values, *, backend=None, block_rows: int = 8,
            prepadded: bool = False):
    """Stable sort of (keys, values) by key: returns (sorted, permuted).

    keys: (n,); values: any array with leading dim n (extra trailing dims
    ride along).  Both backends realize ``order = jnp.argsort(keys)``
    (stable) exactly: the kernel path pair-sorts (key, arange) with a
    lexicographic network, so key ties keep input order bitwise.

    ``prepadded=True``: both operands were padded to the same power of
    two (keys with their sort sentinel via ``pad_pow2``); the kernel
    skips pad/unpad and outputs stay padded, pads sorted last (pad-slot
    ties resolve by position — identical to the reference argsort).
    """
    if prepadded and (keys.shape[0] != max(2, _next_pow2(keys.shape[0]))
                      or values.shape[:1] != keys.shape[:1]):
        raise ValueError("prepadded=True requires both operands padded to "
                         "the same power-of-two length (use ops.pad_pow2)")
    b = resolve_backend(backend)
    if b == "pallas" and kernel_eligible("sort_kv", keys, values):
        if sort_kernel_choice() == "radix":
            # the permutation channel comes out of the counting passes
            # for free — one gather carries the payload, no (key, iota)
            # lexicographic pair-sort
            _tick("sort_kv", "radix")
            ks, order = radix.radix_sort(keys[None, :],
                                         interpret=interpret_mode())
            return ks[0], values[order[0]]
        _tick("sort_kv", "pallas")
        n = keys.shape[0]
        iota = jnp.arange(n, dtype=jnp.int32)
        ks, order = bitonic.bitonic_sort_kv(keys[None, :], iota[None, :],
                                            block_rows=1,
                                            interpret=interpret_mode())
        return ks[0], values[order[0]]
    _tick("sort_kv", "reference")
    order = jnp.argsort(keys, axis=-1)
    if keys.ndim == 1:
        return keys[order], values[order]
    return (jnp.take_along_axis(keys, order, axis=-1),
            jnp.take_along_axis(values, order, axis=-1))


# ---------------------------------------------------------------------------
# searchsorted / bucketize
# ---------------------------------------------------------------------------

def searchsorted(sorted_arr: jnp.ndarray, queries: jnp.ndarray, *,
                 side: str = "left", backend=None, block_n: int = 1024,
                 valid_len=None) -> jnp.ndarray:
    """``jnp.searchsorted(sorted_arr, queries, side)`` with kernel dispatch.

    ``valid_len=m`` is the pre-padded fast path: ``sorted_arr`` may carry
    a sentinel tail past its m real elements (``pad_pow2``) and results
    are clamped to m.  Because sentinels sort last, the clamp reproduces
    the unpadded answer exactly — insertion points below m are untouched
    and any query landing in the tail belongs at m.
    """
    if side not in ("left", "right"):
        raise ValueError(f"side must be 'left' or 'right', got {side!r}")
    b = resolve_backend(backend)
    if b == "pallas" and kernel_eligible("searchsorted", sorted_arr, queries):
        _tick("searchsorted", "pallas")
        ids = bucketize.searchsorted(sorted_arr, queries, side=side,
                                     block_n=block_n,
                                     interpret=interpret_mode())
    else:
        _tick("searchsorted", "reference")
        ids = jnp.searchsorted(sorted_arr, queries,
                               side=side).astype(jnp.int32)
    if valid_len is not None:
        ids = jnp.minimum(ids, jnp.asarray(valid_len, ids.dtype))
    return ids


def sort_partition(x: jnp.ndarray, interior: jnp.ndarray, *, backend=None):
    """Fused local sort + contiguous-destination partition (one dispatch).

    x: (m,) unsorted keys; interior: (t-1,) ascending interior
    boundaries.  Returns ``(x_sorted, starts, lens)`` — bitwise equal to
    ``xs = sort(x)`` followed by ``partition_sorted(xs, interior)``, but
    the kernel path sorts the block AND binary-searches the boundaries
    over it in a single pass (no intermediate pad/unpad round trips).
    """
    b = resolve_backend(backend)
    m = x.shape[0]
    nq = int(interior.shape[0])
    if nq == 0:                         # t == 1: sort only, trivial partition
        xs = sort(x, backend=backend)
        cuts = jnp.zeros((0,), jnp.int32)
    elif (b == "pallas" and kernel_eligible("sort_partition", x, interior)
          and sort_kernel_choice() == "radix"):
        # no fused radix+search kernel: past the crossover the sort
        # dominates, so the split costs one extra (cheap) searchsorted
        # dispatch — the budget benches carry it as smms_radix /
        # terasort_radix
        xs = sort(x, backend=b)
        cuts = searchsorted(xs, interior, side="left", backend=b)
    elif b == "pallas" and kernel_eligible("sort_partition", x, interior):
        _tick("sort_partition", "pallas")
        xs, cuts = fused.sort_partition(x, interior,
                                        interpret=interpret_mode())
    else:
        _tick("sort_partition", "reference")
        xs = jnp.sort(x)
        cuts = jnp.searchsorted(xs, interior, side="left").astype(jnp.int32)
    starts = jnp.concatenate([jnp.zeros((1,), cuts.dtype), cuts])
    ends = jnp.concatenate([cuts, jnp.full((1,), m, cuts.dtype)])
    return xs, starts, ends - starts


def sort_partition_kv(keys: jnp.ndarray, values, interior: jnp.ndarray, *,
                      backend=None):
    """Payload-carrying :func:`sort_partition` (stable, one dispatch).

    keys: (m,); values: leading dim m (trailing dims ride along);
    interior: (t-1,).  Returns ``(keys_sorted, values_permuted, starts,
    lens)`` with the *stable* argsort permutation — bitwise equal to
    ``sort_kv`` + ``searchsorted(side='left')``.
    """
    b = resolve_backend(backend)
    m = keys.shape[0]
    nq = int(interior.shape[0])
    if nq == 0:
        ks, vs = sort_kv(keys, values, backend=backend)
        cuts = jnp.zeros((0,), jnp.int32)
    elif (b == "pallas"
          and kernel_eligible("sort_partition_kv", keys, interior)
          and values.shape[:1] == keys.shape[:1]
          and sort_kernel_choice() == "radix"):
        ks, vs = sort_kv(keys, values, backend=b)
        cuts = searchsorted(ks, interior, side="left", backend=b)
    elif (b == "pallas"
          and kernel_eligible("sort_partition_kv", keys, interior)
          and values.shape[:1] == keys.shape[:1]):
        _tick("sort_partition_kv", "pallas")
        ks, order, cuts = fused.sort_partition_kv(keys, interior,
                                                  interpret=interpret_mode())
        vs = values[order]
    else:
        _tick("sort_partition_kv", "reference")
        order = jnp.argsort(keys)
        ks, vs = keys[order], values[order]
        cuts = jnp.searchsorted(ks, interior, side="left").astype(jnp.int32)
    starts = jnp.concatenate([jnp.zeros((1,), cuts.dtype), cuts])
    ends = jnp.concatenate([cuts, jnp.full((1,), m, cuts.dtype)])
    return ks, vs, starts, ends - starts


def bucketize_histogram(keys: jnp.ndarray, boundaries: jnp.ndarray, t: int,
                        *, backend=None, block_n: int = 1024):
    """Fused bucket-id + histogram (SMMS Round-3 planning).

    keys: (n,); boundaries: (t-1,) ascending interior boundaries.
    Returns (ids (n,) int32, counts (t,) int32), ids per
    ``searchsorted(boundaries, key, side='right')``.
    """
    b = resolve_backend(backend)
    if b == "pallas" and kernel_eligible("bucketize_histogram", keys,
                                         boundaries):
        _tick("bucketize_histogram", "pallas")
        return bucketize.bucketize_histogram(keys, boundaries, t,
                                             block_n=block_n,
                                             interpret=interpret_mode())
    _tick("bucketize_histogram", "reference")
    ids = jnp.searchsorted(boundaries, keys, side="right").astype(jnp.int32)
    counts = jnp.zeros((t,), jnp.int32).at[jnp.clip(ids, 0, t - 1)].add(1)
    return ids, counts


# ---------------------------------------------------------------------------
# merge of sorted segments (the Round-3 receive side)
# ---------------------------------------------------------------------------

def _merge_fits_one_tile(t: int, c: int) -> bool:
    tp2, cp2 = bitonic.merge_padded_shape(t, c)
    return _lanes_ok(tp2 * cp2)


def _rank_merge(keys: jnp.ndarray):
    """Scale-out merge: global (key, flat-id) ranks + scatter.

    For inputs whose padded total exceeds one VMEM tile the in-kernel
    network cannot hold the array; instead every element's final
    position is its rank in the lexicographic (key, id) order — computed
    by the blocked ``fused.merge_ranks`` kernel one row-pair at a time —
    and a host-side scatter places keys and the stable permutation.
    Rows longer than ``RANK_MERGE_BOUND_BLOCK`` additionally block the
    bound side of the search (the double-buffered kernel variant), so
    per-step VMEM stays bounded however long the receive rows grow.
    Returns (merged (t*c,), order (t*c,) int32), bitwise equal to the
    stable flat argsort.
    """
    t, c = keys.shape
    kp = bitonic._pad_sorted_rows(keys, bitonic.sort_sentinel(keys.dtype))
    tp2, cp2 = kp.shape
    ip = bitonic._pad_iota_unique(t, c, tp2, cp2)
    bound_block = RANK_MERGE_BOUND_BLOCK if cp2 > RANK_MERGE_BOUND_BLOCK \
        else None
    pos = fused.merge_ranks(kp, ip, bound_block=bound_block,
                            interpret=interpret_mode()).reshape(-1)
    merged = jnp.zeros((tp2 * cp2,), keys.dtype).at[pos].set(kp.reshape(-1))
    order = jnp.zeros((tp2 * cp2,), jnp.int32).at[pos].set(ip.reshape(-1))
    return merged[:t * c], order[:t * c]


def merge_sorted_rows(x: jnp.ndarray, *, backend=None) -> jnp.ndarray:
    """Merge already-sorted rows into one sorted vector.  x: (t, c).

    Bitwise equal to ``jnp.sort(x.reshape(-1))``.  The kernel path runs
    the blocked log-t pairwise bitonic merge when the padded total fits
    one VMEM tile, and the rank-merge kernel (per-row tiles + scatter)
    beyond that — the receive side scales past a single tile instead of
    falling back to the reference sort.
    """
    b = resolve_backend(backend)
    if b == "pallas" and kernel_eligible("merge_sorted_rows", x):
        _tick("merge_sorted_rows", "pallas")
        if _merge_fits_one_tile(*x.shape):
            return bitonic.merge_sorted_rows(x, interpret=interpret_mode())
        return _rank_merge(x)[0]
    _tick("merge_sorted_rows", "reference")
    return jnp.sort(x.reshape(-1))


def merge_sorted_rows_kv(keys: jnp.ndarray, values, *, backend=None):
    """Merge sorted rows carrying payload.  keys: (t, c); values: (t, c, ...)
    or a tuple of such arrays.

    Returns (merged_keys (t*c,), merged_values (t*c, ...)).  Both backends
    realize the *stable* flat argsort (ties keep buffer order), so the
    kernel path is bitwise-identical to the reference."""
    b = resolve_backend(backend)
    t, c = keys.shape
    gather = lambda order: jax.tree.map(
        lambda v: v.reshape(t * c, *v.shape[2:])[order], values)
    if b == "pallas" and kernel_eligible("merge_sorted_rows_kv", keys):
        _tick("merge_sorted_rows_kv", "pallas")
        if _merge_fits_one_tile(t, c):
            merged, order = bitonic.merge_sorted_rows_argsort(
                keys, interpret=interpret_mode())
        else:
            merged, order = _rank_merge(keys)
        return merged, gather(order)
    _tick("merge_sorted_rows_kv", "reference")
    kflat = keys.reshape(-1)
    order = jnp.argsort(kflat)
    return kflat[order], gather(order)


# ---------------------------------------------------------------------------
# attention (unchanged: no jnp twin in the hot path)
# ---------------------------------------------------------------------------

def flash_attention(q, k, v, causal: bool = True, window=None,
                    block_q: int = 128, block_k: int = 128):
    """Blocked online-softmax attention with GQA + sliding window."""
    return fa.flash_attention(q, k, v, causal=causal, window=window,
                              block_q=block_q, block_k=block_k,
                              interpret=interpret_mode())
