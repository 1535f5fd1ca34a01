"""Fused multi-op Pallas kernels — one dispatch where the pipeline had three.

The cluster hot loops used to issue separate kernels for steps that read
the same VMEM-resident block: Round-3 partitioning ran ``ops.sort`` →
``ops.searchsorted`` → ``partition_sorted`` as three dispatches, each
with its own pad-to-pow2 / unpad round trip through HBM.  This module
holds the fused alternatives (the FlashAttention treatment applied to
the shuffle pipeline):

* ``sort_partition``    — bitonic-sort a block AND count the sorted keys
  below each of the t-1 destination boundaries over the freshly sorted
  block in the same kernel pass.  One HBM read, one write, zero
  intermediate materialization.  Used by Terasort's Round 3 (sort and
  partition are adjacent there; SMMS sorts in Round 1, before the sample
  gather, so only its partition half can fuse).
* ``sort_partition_kv`` — the payload-carrying variant: lexicographic
  (key, iota) pair sort (= the *stable* argsort permutation, bitwise)
  plus the same in-kernel boundary search.  Used by RandJoin's
  tuple-to-interval routing.
* ``merge_ranks``       — the scale-out path for merging sorted rows
  that do NOT fit one VMEM tile: every element's final position is its
  rank in the global lexicographic (key, flat-index) order, a sum over
  bound rows of how many of the row's pairs sort below it.  The grid is
  (query rows × query blocks × bound rows × bound blocks) with the rank
  accumulated across the (sequential) bound axes, so each step touches
  one query block and one bound block.  A host-side scatter places keys
  (and the stable permutation) by rank.

The searches are ``bucketize``'s compare-and-count (no in-kernel
gather).  In the rank merge the query block is a sorted run, so the
chunk-range pruning leaves about block/128 + 2 bound chunks per step:
near-linear work rather than a full cross product.

Sentinel discipline matches ``bitonic.py``: padding uses the dtype's
sort sentinel for keys and *unique* continuation ids for the index
channel (uniqueness is what makes the rank positions collision-free).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .bitonic import (MIN_LANES, _next_pow2, compiler_params,
                      sort_network_block, sort_sentinel, widen)
from .bucketize import (CHUNK, chunk_range, count_below, dense_chunk,
                        key_less, pair_less)

__all__ = ["sort_partition", "sort_partition_kv", "merge_ranks"]


def _sort_partition_kernel(*refs, m: int, n_chans: int):
    """Sort the row's channels, then count sorted keys < each query."""
    ins, q_ref = refs[:n_chans], refs[n_chans]
    outs, cuts_ref = refs[n_chans + 1:2 * n_chans + 1], refs[-1]
    for o_ref, v in zip(outs, sort_network_block(*(r[...] for r in ins))):
        o_ref[...] = v
    ks = outs[0]

    def chunk(c):
        return (ks[:, pl.ds(pl.multiple_of(c * CHUNK, CHUNK), CHUNK)],)

    cnt = count_below((q_ref[...],), chunk, key_less("left"), 0,
                      ks.shape[-1] // CHUNK)
    cuts_ref[...] = jnp.minimum(cnt, m)


def _pad_row(x: jnp.ndarray, fill) -> jnp.ndarray:
    """(n,) -> (1, pow2 >= MIN_LANES) padded with ``fill``."""
    n = x.shape[0]
    p = max(MIN_LANES, _next_pow2(n))
    return jnp.pad(x, (0, p - n), constant_values=fill)[None, :]


def _sort_partition_call(chans, queries, interpret: bool):
    """One-block fused sort + left count; returns (sorted chans, cuts).

    Keys travel as 32-bit (:func:`widen`) through the kernel."""
    m = chans[0].shape[0]
    nq = queries.shape[0]
    rows = [_pad_row(w, sort_sentinel(w.dtype)) for w in map(widen, chans)]
    qc = jnp.pad(widen(queries), (0, (-nq) % 8), mode="edge")[:, None]
    full = lambda a: pl.BlockSpec(a.shape, lambda i: (0,) * a.ndim)
    out = pl.pallas_call(
        functools.partial(_sort_partition_kernel, m=m, n_chans=len(rows)),
        grid=(1,),
        in_specs=[full(r) for r in rows] + [full(qc)],
        out_specs=tuple(full(r) for r in rows) + (full(qc),),
        out_shape=tuple(jax.ShapeDtypeStruct(r.shape, r.dtype)
                        for r in rows)
        + (jax.ShapeDtypeStruct(qc.shape, jnp.int32),),
        compiler_params=compiler_params(),
        interpret=interpret,
        name=f"sort_partition{'_kv' if len(rows) > 1 else ''}",
    )(*rows, qc)
    return ([o[0, :m].astype(c.dtype) for o, c in zip(out[:-1], chans)],
            out[-1][:nq, 0])


@functools.partial(jax.jit, static_argnames=("interpret",))
def sort_partition(x: jnp.ndarray, queries: jnp.ndarray,
                   interpret: bool = True):
    """Fused ascending sort + left-searchsorted of ``queries``.

    x: (m,) unsorted keys; queries: (q,) ascending boundary values.
    Returns (x_sorted (m,), cuts (q,) int32) with
    ``cuts == jnp.searchsorted(x_sorted, queries, side='left')`` —
    bitwise equal to the unfused ``ops.sort`` → ``ops.searchsorted``
    pipeline, in ONE kernel dispatch.
    """
    (xs,), cuts = _sort_partition_call([x], queries, interpret)
    return xs, cuts


@functools.partial(jax.jit, static_argnames=("interpret",))
def sort_partition_kv(keys: jnp.ndarray, queries: jnp.ndarray,
                      interpret: bool = True):
    """Fused stable pair sort + boundary search.

    keys: (m,); queries: (q,) ascending.  Returns
    (keys_sorted (m,), order (m,) int32, cuts (q,) int32) where ``order``
    is the *stable* argsort permutation (ties keep input position —
    realized by the lexicographic (key, iota) network) and ``cuts`` is
    the left-searchsorted of the queries over the sorted keys.
    """
    iota = jnp.arange(keys.shape[0], dtype=jnp.int32)
    (ks, order), cuts = _sort_partition_call([keys, iota], queries,
                                             interpret)
    return ks, order, cuts


# ---------------------------------------------------------------------------
# rank-based merge: sorted rows too large for one VMEM tile
# ---------------------------------------------------------------------------

def _pair_bounds(keys, ids):
    """Lexicographic (min, max) pairs of a (key, id) column: the extreme
    key, then the extreme id among the rows holding it."""
    kmin, kmax = jnp.min(keys), jnp.max(keys)
    imin = jnp.min(jnp.where(keys == kmin, ids, jnp.iinfo(jnp.int32).max))
    imax = jnp.max(jnp.where(keys == kmax, ids, jnp.iinfo(jnp.int32).min))
    return (kmin, imin), (kmax, imax)


def _rank_kernel(qk_ref, qi_ref, bk_ref, bi_ref, pos_ref):
    """Accumulate one bound block's contribution to the query block's rank.

    Grid axes 2 and 3 walk the bound rows and their column blocks
    sequentially; the output block is revisited (same index map every
    step) and accumulated, initialized on the first step.  A row's
    contribution is the count of its pairs below each query, and counts
    are additive over any column partition of the row.  Searching a row
    against itself contributes the element's own in-row position (pairs
    are strictly increasing), so no self-row special case is needed.
    """
    @pl.when((pl.program_id(2) == 0) & (pl.program_id(3) == 0))
    def _init():
        pos_ref[...] = jnp.zeros_like(pos_ref)

    qk, qi = qk_ref[...], qi_ref[...]                 # (bn, 1) columns
    q_min, q_max = _pair_bounds(qk, qi)
    lo, hi = chunk_range((bk_ref, bi_ref), pair_less, q_min, q_max)
    pos_ref[...] += count_below((qk, qi), dense_chunk((bk_ref, bi_ref)),
                                pair_less, lo, hi)


@functools.partial(jax.jit,
                   static_argnames=("block_n", "bound_block", "interpret"))
def merge_ranks(keys: jnp.ndarray, ids: jnp.ndarray, block_n: int = 1024,
                bound_block: int = None,
                interpret: bool = True) -> jnp.ndarray:
    """Global rank of every (key, id) pair.  keys/ids: (t, c), rows sorted.

    Rows must be lexicographically increasing in (key, id) — sorted keys
    with unique ascending tie-break ids, which is exactly what
    ``ops``' merge dispatcher feeds it.  Returns (t, c) int32 positions:
    element (i, j)'s index in the fully merged order.  Positions are a
    permutation of [0, t*c) because the pairs are globally unique.

    ``bound_block=None`` holds each full bound row in VMEM per grid
    step.  An int blocks the bound rows into column slices of about
    ``bound_block`` pairs (rounded up to whole (8, 128) tiles) on a
    fourth grid axis, which the Pallas pipeline double-buffers; per-step
    VMEM then drops to O(bound_block).  Ranks are bitwise identical
    either way (counts are additive over the column partition).
    """
    t, c = keys.shape
    keys = widen(keys)                  # 32-bit through the kernel
    width = -(-c // CHUNK) * CHUNK
    bn = min(-(-block_n // CHUNK) * CHUNK, width)
    width = -(-width // bn) * bn
    bb = width
    if bound_block is not None:
        tile = 8 * CHUNK                    # one (8, 128) tile of bounds
        bb = min(-(-int(bound_block) // tile) * tile, width)
        width = -(-width // math.lcm(bn, bb)) * math.lcm(bn, bb)
        bb = min(bb, width)
    # pad so both the query blocking and the bound blocking divide the
    # width; never hit by the ops dispatcher (c is pow2 and the block
    # sizes are pow2), guarded for direct callers
    pad = width - c
    if pad:
        keys = jnp.pad(keys, ((0, 0), (0, pad)),
                       constant_values=sort_sentinel(keys.dtype))
        ids = jnp.pad(ids, ((0, 0), (0, pad)),
                      constant_values=jnp.iinfo(jnp.int32).max)
    ids = ids.astype(jnp.int32)
    col = pl.BlockSpec((None, bn, 1), lambda i, j, k, b: (i, j, 0))
    row = pl.BlockSpec((None, bb // CHUNK, CHUNK),
                       lambda i, j, k, b: (k, b, 0))
    pos = pl.pallas_call(
        _rank_kernel,
        grid=(t, width // bn, t, width // bb),
        in_specs=[col, col, row, row],
        out_specs=col,
        out_shape=jax.ShapeDtypeStruct((t, width, 1), jnp.int32),
        compiler_params=compiler_params(),
        interpret=interpret,
        name="merge_ranks",
    )(keys[..., None], ids[..., None],
      keys.reshape(t, width // CHUNK, CHUNK),
      ids.reshape(t, width // CHUNK, CHUNK))
    return pos[:, :c, 0]
