"""Bitonic sort / merge networks as Pallas TPU kernels — the SMMS hot path.

The paper's hot spot is the per-machine sort (O((n/t) log(n/t)) of the
total cost).  On TPU the comparison network must be *vectorial*: a scalar
heap/quicksort is hostile to the 8x128 VPU.  A bitonic network is branch-
free, oblivious (fixed schedule — static shapes), and every compare-
exchange substage is a few full-width selects over lane rotations.

Layout choice: the network runs along the LAST (lane) dimension with the
block resident in VMEM.  The partner of lane i at distance d is lane
i ^ d, read through two lane rotations (``pltpu.roll`` by d and by n-d)
and a select on bit d of the lane iota — no reshape, no gather, both of
which Mosaic refuses at these shapes.  The direction bit of stage k is
bit k+1 of the lane index, a broadcast compare.  Substages run in
``fori_loop``s with a traced distance, so the kernel body stays small
however long the row is.  Rows are padded to at least ``MIN_LANES``
(one lane tile) so every rotation spans whole 128-lane vregs, and 16-bit
keys are widened to 32 bits before the kernel and narrowed after
(Mosaic rotates and dynamically slices 32-bit data only; the widening
is exact and order-preserving).

Three kernels:

* ``bitonic_sort``     — full row sort, n log^2 n compare-exchanges.
* ``bitonic_sort_kv``  — pair sort, keys primary / values tie-break
  (lexicographic).  Feeding ``arange(n)`` as the value channel makes the
  result *bitwise equal to a stable argsort* — how the dispatch layer in
  ``repro.kernels.ops`` routes payload-carrying sorts.
* ``merge_sorted_rows`` — merge of t already-sorted rows (the Round-3
  receive buffer: every sender's segment lands sorted).  log t pairwise
  bitonic-merge levels, n log n total — asymptotically cheaper than
  re-sorting the receive buffer from scratch.  Each level launches ONE
  pallas_call over a **blocked grid**: independent row-group blocks of
  ~MERGE_TILE_LANES lanes merge in parallel (no monolithic
  whole-array block); inputs past one VMEM tile route to the
  rank-merge kernel in ``fused.py`` instead.

Every block is a (1, rows, lanes) slice of a 3-D view whose last two
dimensions are the whole array's, which satisfies Mosaic's (8, 128)
block-tiling rule for any row count.

Cost: for the m = n/t <= 64k row blocks SMMS uses, the whole row fits
VMEM and each kernel is memory-light (one HBM read + write per row).

Sentinel discipline: rows are padded to a power of two with the dtype's
``sort sentinel`` — +inf for floats, iinfo.max for ints — so padding
sorts strictly last (or ties with real sentinels, which is harmless: the
first n output slots are still exactly the sorted real data).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "bitonic_sort",
    "bitonic_sort_kv",
    "merge_sorted_rows",
    "merge_sorted_rows_argsort",
    "sort_network_block",
    "merge_network_block",
    "sort_sentinel",
    "MIN_LANES",
]

# Narrowest kernel row: one full lane tile, so every lane rotation moves
# whole vregs (rows are padded up to it with the sort sentinel).
MIN_LANES = 128

# Scoped-VMEM ceiling for the network kernels: a 64k-lane (key, value)
# row pair keeps a handful of full-row temporaries live per substage,
# past the compiler's 16 MiB default but well inside a v5e core's VMEM.
VMEM_LIMIT_BYTES = 64 * 1024 * 1024


def compiler_params():
    return pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES)


def sort_sentinel(dtype) -> jnp.ndarray:
    """The value that sorts last for ``dtype``: +inf (floats), max (ints)."""
    dtype = jnp.dtype(dtype)
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.asarray(jnp.inf, dtype)
    return jnp.asarray(jnp.iinfo(dtype).max, dtype)


def widen(x: jnp.ndarray) -> jnp.ndarray:
    """Exact, order-preserving 32-bit carrier for sub-32-bit keys."""
    if jnp.dtype(x.dtype).itemsize >= 4:
        return x
    if jnp.issubdtype(x.dtype, jnp.floating):
        return x.astype(jnp.float32)
    return x.astype(jnp.int32)


def _lane_iota(shape) -> jnp.ndarray:
    return lax.broadcasted_iota(jnp.int32, shape, len(shape) - 1)


def _partner(x, d, upper):
    """``x[..., i ^ d]`` for a power-of-two lane distance d (static or
    traced): lanes with bit d clear read lane i + d, the others i - d.
    ``pltpu.roll`` follows ``jnp.roll`` (lane i moves to i + shift)."""
    n = x.shape[-1]
    ax = x.ndim - 1
    return jnp.where(upper, pltpu.roll(x, d, ax), pltpu.roll(x, n - d, ax))


def _select(chans, partners, upper, desc):
    """One compare-exchange given each lane's partner values.

    Lexicographic over the channels (keys first, then tie-breakers).
    Swap-based rather than min/max so a NaN never propagates to its
    partner: an unordered pair simply doesn't swap, which preserves the
    input multiset (NaN keys are outside the bitwise-parity contract but
    must not corrupt their neighbours)."""
    gt = None
    for c, p in zip(reversed(chans), reversed(partners)):
        lo = jnp.where(upper, p, c)          # value at the pair's lower lane
        hi = jnp.where(upper, c, p)
        gt = lo > hi if gt is None else (lo > hi) | ((lo == hi) & gt)
    swap = gt != desc
    return tuple(jnp.where(swap, p, c) for c, p in zip(chans, partners))


def _exchange(chans, d, lane, desc):
    upper = (lane & d) != 0
    return _select(chans, tuple(_partner(c, d, upper) for c in chans),
                   upper, desc)


def sort_network_block(*chans):
    """Bitonic sort of each row of ``chans[0]``: (rows, n), n a power of 2.

    Further channels (same shape) ride along and break key ties
    lexicographically — ``(keys, arange)`` yields the stable argsort.
    Kernel-body code: the lane rotations lower only inside a Pallas
    kernel (compiled or interpreted).  Returns the tuple of channels.
    """
    rows, n = chans[0].shape
    logn = int(math.log2(n))
    assert 1 << logn == n, "n must be a power of 2"
    lane = _lane_iota(chans[0].shape)

    def stage(k, chans):                 # runs of length 2^(k+1) get sorted
        desc = (jnp.right_shift(lane, k + 1) & 1) == 1

        def substage(s, chans):          # exchange distance 2^(k-s)
            return _exchange(chans, jnp.left_shift(1, k - s), lane, desc)

        return lax.fori_loop(0, k + 1, substage, chans)

    return lax.fori_loop(0, logn, stage, tuple(chans))


def merge_network_block(*chans, run: int):
    """Merge rows of ``chans[0]`` whose length-``run`` chunks are each
    sorted ascending (lexicographically with the tie channels).

    (rows, n); n and run powers of 2, run divides n.  log2(n/run)
    merge levels — n log n work instead of the full network's n log^2 n.
    Each level first compares lane i with its mirror i ^ (2*lvl - 1)
    inside every 2*lvl group (the "flip", built from log2(2*lvl) lane
    butterflies), which leaves two bitonic halves; ascending half-cleaners
    then finish the level.  Kernel-body code, like
    :func:`sort_network_block`.
    """
    rows, n = chans[0].shape
    lane = _lane_iota(chans[0].shape)
    chans = tuple(chans)
    lvl = run
    while lvl < n:
        def butterfly(b, ch):
            d = jnp.left_shift(1, b)
            upper = (lane & d) != 0
            return tuple(_partner(c, d, upper) for c in ch)

        mirrored = lax.fori_loop(0, int(math.log2(2 * lvl)), butterfly,
                                 chans)
        chans = _select(chans, mirrored, (lane & lvl) != 0, False)

        def half_clean(s, ch, lvl=lvl):
            return _exchange(ch, jnp.right_shift(lvl, s + 1), lane, False)

        chans = lax.fori_loop(0, int(math.log2(lvl)), half_clean, chans)
        lvl *= 2
    return chans


def _network_kernel(*refs, network):
    """Run ``network`` over the block: channel refs in, as many out."""
    k = len(refs) // 2
    for o_ref, v in zip(refs[k:], network(*(r[...] for r in refs[:k]))):
        o_ref[...] = v


_sort_kernel = functools.partial(_network_kernel, network=sort_network_block)


def _next_pow2(n: int) -> int:
    return 1 << max(0, (n - 1).bit_length())


def _row_blocks(kernel, chans, block_rows: int, interpret: bool, name: str):
    """Run ``kernel`` over (rows, n) channels in (block_rows, n) blocks.

    The channels are viewed as (groups, block_rows, n) so every block
    spans the trailing two dimensions whole, and carried as 32-bit
    (:func:`widen`) through the kernel.
    """
    rows, n = chans[0].shape
    views = [widen(c).reshape(rows // block_rows, block_rows, n)
             for c in chans]
    spec = pl.BlockSpec((None, block_rows, n), lambda i: (i, 0, 0))
    out = pl.pallas_call(
        kernel,
        grid=(rows // block_rows,),
        in_specs=[spec] * len(chans),
        out_specs=tuple([spec] * len(chans)),
        out_shape=tuple(jax.ShapeDtypeStruct(v.shape, v.dtype)
                        for v in views),
        compiler_params=compiler_params(),
        interpret=interpret,
        name=name,
    )(*views)
    return [o.reshape(rows, n).astype(c.dtype) for o, c in zip(out, chans)]


def _padded_width(n: int) -> int:
    return max(MIN_LANES, _next_pow2(n))


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def bitonic_sort(x: jnp.ndarray, block_rows: int = 8,
                 interpret: bool = True) -> jnp.ndarray:
    """Row-wise ascending sort via the Pallas bitonic kernel.

    x: (rows, n).  n is padded to a power of 2 (at least ``MIN_LANES``)
    with the dtype's sort sentinel, stripped after.  ``interpret=True``
    runs the kernel body through the Pallas interpreter (the CPU path);
    ``repro.kernels.ops`` derives the flag from the platform.
    """
    rows, n = x.shape
    np2 = _padded_width(n)
    block_rows = min(block_rows, rows)
    rpad = (-rows) % block_rows
    xp = (x if rpad == 0 and np2 == n else
          jnp.pad(x, ((0, rpad), (0, np2 - n)),
                  constant_values=sort_sentinel(x.dtype)))
    (out,) = _row_blocks(_sort_kernel, [xp], block_rows, interpret,
                         "bitonic_sort")
    return out[:rows, :n]


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def bitonic_sort_kv(keys: jnp.ndarray, values: jnp.ndarray,
                    block_rows: int = 8, interpret: bool = True):
    """Row-wise (key, value) pair sort, values breaking key ties.

    keys/values: (rows, n), same shape.  Sorting (keys, arange(n)) yields
    the stable argsort permutation in the value channel.
    """
    rows, n = keys.shape
    np2 = _padded_width(n)
    block_rows = min(block_rows, rows)
    rpad = (-rows) % block_rows
    if rpad == 0 and np2 == n:
        kp, vp = keys, values
    else:
        kp = jnp.pad(keys, ((0, rpad), (0, np2 - n)),
                     constant_values=sort_sentinel(keys.dtype))
        vp = jnp.pad(values, ((0, rpad), (0, np2 - n)),
                     constant_values=sort_sentinel(values.dtype))
    ok, ov = _row_blocks(_sort_kernel, [kp, vp], block_rows, interpret,
                         "bitonic_sort_kv")
    return ok[:rows, :n], ov[:rows, :n]


def merge_padded_shape(t: int, c: int):
    """(rows, cols) the merge kernels pad t sorted rows of c to.

    Both powers of two; cols widens past the row's own power of two
    until the flat total spans at least one lane tile (``MIN_LANES``).
    ``ops`` sizes its eligibility gate with the same numbers.
    """
    tp2 = max(1, _next_pow2(t))
    cp2 = max(2, _next_pow2(c))
    while tp2 * cp2 < MIN_LANES:
        cp2 *= 2
    return tp2, cp2


def _pad_sorted_rows(x: jnp.ndarray, sentinel) -> jnp.ndarray:
    """Pad (t, c) sorted rows to :func:`merge_padded_shape` — rows stay
    sorted."""
    t, c = x.shape
    tp2, cp2 = merge_padded_shape(t, c)
    return jnp.pad(x, ((0, tp2 - t), (0, cp2 - c)), constant_values=sentinel)


def _pad_iota_unique(t: int, c: int, tp2: int, cp2: int) -> jnp.ndarray:
    """Flat-index channel for (t, c) rows padded to (tp2, cp2).

    Real slots carry their row-major flat index in [0, t*c); pad slots
    carry *unique* ids >= t*c, ascending along each row.  Uniqueness is
    what keeps lexicographic (key, id) pairs strictly increasing per
    row (pads sort after every real element among equal keys) and makes
    rank-merge positions collision-free.
    """
    row = jnp.arange(tp2, dtype=jnp.int32)[:, None]
    col = jnp.arange(cp2, dtype=jnp.int32)[None, :]
    real = (row < t) & (col < c)
    flatpos = row * cp2 + col
    return jnp.where(real, row * c + col, t * c + flatpos)


# Soft per-block lane target for the hierarchical merge: levels whose
# runs still fit pick a grid of independent blocks of ~this size; the
# top levels (which must see whole runs) may exceed it up to the
# caller's hard VMEM cap.
MERGE_TILE_LANES = 1 << 12


def _merge_levels(kp: jnp.ndarray, ip, run: int, interpret: bool):
    """Hierarchically merge (rows, c) sorted runs down to one sorted row.

    Each level groups rows into blocks of ``rpb`` rows and launches ONE
    pallas_call with ``grid=(rows/rpb,)`` — every grid block merges its
    rows independently in VMEM (length-``run`` runs -> one sorted run of
    rpb*c).  Levels repeat until a single row remains.  ``ip`` is an
    optional tie-break/permutation channel merged lexicographically.
    """
    rows, c = kp.shape
    chans = [kp] if ip is None else [kp, ip]
    while rows > 1:
        rpb = min(rows, max(2, MERGE_TILE_LANES // c))
        nb = rows // rpb
        merge = functools.partial(merge_network_block, run=c)
        chans = _row_blocks(functools.partial(_network_kernel, network=merge),
                            [ch.reshape(nb, rpb * c) for ch in chans], 1,
                            interpret, "bitonic_merge")
        rows, c = nb, rpb * c
    flat = [ch.reshape(-1) for ch in chans]
    return flat[0], (None if ip is None else flat[1])


@functools.partial(jax.jit, static_argnames=("interpret",))
def merge_sorted_rows(x: jnp.ndarray, interpret: bool = True) -> jnp.ndarray:
    """Merge t sorted rows into one sorted vector.  x: (t, c), rows asc.

    Returns (t*c,) — bitwise equal to ``jnp.sort(x.reshape(-1))``.
    Blocked grid: each merge level runs independent row-group blocks of
    ~MERGE_TILE_LANES lanes across the grid (not one monolithic block),
    so the receive side parallelizes across tiles; only the final level
    holds whole runs.
    """
    t, c = x.shape
    xp = _pad_sorted_rows(x, sort_sentinel(x.dtype))
    merged, _ = _merge_levels(xp, None, xp.shape[1], interpret)
    return merged[:t * c]


@functools.partial(jax.jit, static_argnames=("interpret",))
def merge_sorted_rows_argsort(keys: jnp.ndarray, interpret: bool = True):
    """Merge t sorted rows carrying the stable permutation.  keys: (t, c).

    Returns (merged_keys (t*c,), order (t*c,) int32) where ``order`` is
    the flat index into ``keys.reshape(-1)`` — bitwise equal to a stable
    ``jnp.argsort(keys.reshape(-1))`` (ties resolve by buffer position).
    """
    t, c = keys.shape
    kp = _pad_sorted_rows(keys, sort_sentinel(keys.dtype))
    tp2, cp2 = kp.shape
    ip = _pad_iota_unique(t, c, tp2, cp2)
    merged, order = _merge_levels(kp, ip, cp2, interpret)
    return merged[:t * c], order[:t * c]
