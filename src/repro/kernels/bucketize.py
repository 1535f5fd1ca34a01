"""Searchsorted and fused bucketize + histogram Pallas kernels.

After Algorithm 1 produces the t-1 interior boundaries, every device must
(a) map each key to its destination bucket and (b) count keys per bucket
to size the exchange.  Done naively that is a searchsorted pass plus a
histogram pass (two HBM sweeps over the keys).  The bucketize kernel
fuses both: one sweep, bucket ids and per-block partial counts come out
together; the caller sums partial counts over blocks (a (blocks, t)
reduction, tiny).  The same search backs a plain ``searchsorted`` kernel
(both sides) used by the local-join and partition hot paths.

Search = compare-and-count.  For sorted bounds, ``searchsorted(b, q,
'left')`` is the number of bounds below q (``'right'``: at or below).
Queries stream through as (block, 1) columns; the bounds sit in VMEM
as dense (chunks, 128) rows, and each loop step compares one 128-bound
row against the whole query column and accumulates the hits — full-
width VPU work with no gather, which Mosaic cannot lower at these
shapes.  Sortedness of the bounds prunes the loop: chunks whose last
bound is already below the block's smallest query count whole, chunks
whose first bound is not below its largest count nothing, so only the
chunks between are compared (for a sorted query block that is about
block/128 + 2 of them).  Padding (queries and bounds, with the dtype's
sort sentinel) never changes an answer: counts are clamped to the real
bound count, and padded queries are sliced off.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from .bitonic import compiler_params, sort_sentinel, widen

__all__ = ["bucketize_histogram", "searchsorted"]

# Bounds compared per loop step: one dense 128-lane row.
CHUNK = 128


def key_less(side: str):
    """``less(bounds, queries)`` for one key channel: b < q ('left') or
    b <= q ('right')."""
    if side == "left":
        return lambda b, q: b[0] < q[0]
    if side == "right":
        return lambda b, q: b[0] <= q[0]
    raise ValueError(f"side must be 'left' or 'right', got {side!r}")


def pair_less(b, q):
    """Lexicographic (key, id) order: bound pair strictly below query."""
    return (b[0] < q[0]) | ((b[0] == q[0]) & (b[1] < q[1]))


def chunk_range(bound_refs, less, q_min, q_max):
    """[lo, hi): the chunks of dense sorted bound rows that need compares.

    ``bound_refs``: (chunks, CHUNK) refs of one sorted bound sequence
    (row-major).  ``q_min`` / ``q_max``: channel tuples of scalars
    bounding the query block in ``less`` order.  Chunks before ``lo``
    lie wholly below every query; chunks from ``hi`` on lie wholly at or
    above every query.
    """
    last = tuple(r[:, CHUNK - 1:CHUNK] for r in bound_refs)
    first = tuple(r[:, 0:1] for r in bound_refs)
    lo = jnp.sum(less(last, q_min).astype(jnp.int32))
    hi = jnp.sum(less(first, q_max).astype(jnp.int32))
    return lo, hi


def count_below(queries, chunk, less, lo, hi):
    """Per query, how many bounds are ``less`` than it, for sorted bounds.

    ``queries``: channel tuple of (bq, 1) columns.  ``chunk(c)``: the
    channel tuple of bound chunk c, each (1, CHUNK).  Chunks in
    [0, lo) count whole, chunks in [hi, ...) count nothing (see
    :func:`chunk_range`).  Returns (bq, 1) int32.
    """
    bq = queries[0].shape[0]

    def body(c, acc):
        return acc + less(chunk(c), queries).astype(jnp.int32)

    acc = lax.fori_loop(lo, hi, body, jnp.zeros((bq, CHUNK), jnp.int32))
    return jnp.sum(acc, axis=1, keepdims=True) + lo * CHUNK


def dense_chunk(bound_refs):
    """``chunk(c)`` reader over dense (chunks, CHUNK) bound refs."""
    return lambda c: tuple(r[pl.ds(c, 1), :] for r in bound_refs)


def dense_bounds(bounds: jnp.ndarray, fill) -> jnp.ndarray:
    """(..., n) sorted bounds -> (..., ceil(n/CHUNK), CHUNK) 32-bit rows
    (:func:`widen`: Mosaic loads single rows of 32-bit data only), padded
    with ``fill``."""
    bounds = widen(bounds)
    n = bounds.shape[-1]
    width = max(1, -(-n // CHUNK)) * CHUNK
    widths = ((0, 0),) * (bounds.ndim - 1) + ((0, width - n),)
    bp = jnp.pad(bounds, widths, constant_values=fill)
    return bp.reshape(bounds.shape[:-1] + (width // CHUNK, CHUNK))


def _query_columns(queries: jnp.ndarray, block_n: int, fill=None):
    """(nq,) -> (blocks, block_n, 1) 32-bit columns.  Padding repeats
    the last query unless ``fill`` is given, so a sorted block stays
    tight."""
    queries = widen(queries)
    nq = queries.shape[0]
    pad = (-nq) % block_n
    if fill is None:
        qp = jnp.pad(queries, (0, pad), mode="edge")
    else:
        qp = jnp.pad(queries, (0, pad), constant_values=fill)
    return qp.reshape(-1, block_n, 1)


def _nan_free(reduce, q, ignore):
    """``reduce`` over the non-NaN queries (a NaN query counts no bound
    under any ``less`` and must not widen or empty the chunk range)."""
    if jnp.issubdtype(q.dtype, jnp.floating):
        q = jnp.where(jnp.isnan(q), jnp.asarray(ignore, q.dtype), q)
    return reduce(q)


def _column_search(q, b_ref, less, n_bounds: int):
    """Clamped count of bounds ``less`` than each query of column q."""
    lo, hi = chunk_range((b_ref,), less, (_nan_free(jnp.min, q, jnp.inf),),
                         (_nan_free(jnp.max, q, -jnp.inf),))
    cnt = count_below((q,), dense_chunk((b_ref,)), less, lo, hi)
    return jnp.minimum(cnt, n_bounds)


def _searchsorted_kernel(q_ref, b_ref, ids_ref, *, n_bounds: int,
                         side: str):
    ids_ref[...] = _column_search(q_ref[...], b_ref, key_less(side),
                                  n_bounds)


def _bucketize_kernel(q_ref, b_ref, ids_ref, counts_ref, *, n_bounds: int):
    ids = _column_search(q_ref[...], b_ref, key_less("right"), n_bounds)
    ids_ref[...] = ids                     # in [0, n_bounds] = [0, t-1]

    # per-block histogram, 128 buckets at a time: one-hot compare of the
    # id column against a bucket row, summed down the column
    def body(c, carry):
        bucket = c * CHUNK + lax.broadcasted_iota(jnp.int32, (1, CHUNK), 1)
        hits = jnp.sum((ids == bucket).astype(jnp.int32), axis=0,
                       keepdims=True)
        counts_ref[:, pl.ds(pl.multiple_of(c * CHUNK, CHUNK), CHUNK)] = hits
        return carry

    lax.fori_loop(0, counts_ref.shape[-1] // CHUNK, body, 0)


@functools.partial(jax.jit, static_argnames=("t", "block_n", "interpret"))
def bucketize_histogram(keys: jnp.ndarray, boundaries: jnp.ndarray, t: int,
                        block_n: int = 1024, interpret: bool = True):
    """keys: (n,), boundaries: (t-1,) ascending. Returns (ids (n,), counts (t,)).

    Buckets are [b_k, b_{k+1}): id = searchsorted(boundaries, key, 'right').
    Duplicate boundaries (heavy hitters) leave their middle buckets empty,
    exactly as the jnp reference does; t need not be a power of two.
    """
    n = keys.shape[0]
    n_bounds = boundaries.shape[0]
    if n_bounds == 0:                       # t == 1: everything in bucket 0
        return (jnp.zeros((n,), jnp.int32),
                jnp.full((1,), n, jnp.int32))
    pad = (-n) % block_n
    qc = _query_columns(keys, block_n, fill=sort_sentinel(keys.dtype))
    bp = dense_bounds(boundaries, sort_sentinel(boundaries.dtype))
    blocks = qc.shape[0]
    t_lanes = -(-t // CHUNK) * CHUNK
    col = pl.BlockSpec((None, block_n, 1), lambda i: (i, 0, 0))
    ids, partial = pl.pallas_call(
        functools.partial(_bucketize_kernel, n_bounds=n_bounds),
        grid=(blocks,),
        in_specs=[col, pl.BlockSpec(bp.shape, lambda i: (0, 0))],
        out_specs=(col, pl.BlockSpec((None, 1, t_lanes),
                                     lambda i: (i, 0, 0))),
        out_shape=(jax.ShapeDtypeStruct(qc.shape, jnp.int32),
                   jax.ShapeDtypeStruct((blocks, 1, t_lanes), jnp.int32)),
        compiler_params=compiler_params(),
        interpret=interpret,
        name="bucketize_histogram",
    )(qc, bp)
    counts = jnp.sum(partial[:, 0, :t], axis=0)
    if pad:
        # padded keys (= sort sentinel) land in the last bucket; remove them
        counts = counts.at[t - 1].add(-pad)
    return ids.reshape(-1)[:n], counts


@functools.partial(jax.jit, static_argnames=("side", "block_n", "interpret"))
def searchsorted(sorted_arr: jnp.ndarray, queries: jnp.ndarray,
                 side: str = "left", block_n: int = 1024,
                 interpret: bool = True) -> jnp.ndarray:
    """Kernel-backed ``jnp.searchsorted(sorted_arr, queries, side)``.

    sorted_arr: (n,) ascending (duplicates fine); queries: (q,).  The
    sorted array lives in VMEM whole; queries stream through in blocks.
    """
    nq = queries.shape[0]
    n = sorted_arr.shape[0]
    if n == 0 or nq == 0:
        return jnp.zeros((nq,), jnp.int32)
    key_less(side)                          # validate before tracing
    block_n = min(block_n, max(8, -(-nq // 8) * 8))
    qc = _query_columns(queries, block_n)
    bp = dense_bounds(sorted_arr, sort_sentinel(sorted_arr.dtype))
    col = pl.BlockSpec((None, block_n, 1), lambda i: (i, 0, 0))
    ids = pl.pallas_call(
        functools.partial(_searchsorted_kernel, n_bounds=n, side=side),
        grid=(qc.shape[0],),
        in_specs=[col, pl.BlockSpec(bp.shape, lambda i: (0, 0))],
        out_specs=col,
        out_shape=jax.ShapeDtypeStruct(qc.shape, jnp.int32),
        compiler_params=compiler_params(),
        interpret=interpret,
        name="searchsorted",
    )(qc, bp)
    return ids.reshape(-1)[:nq]
