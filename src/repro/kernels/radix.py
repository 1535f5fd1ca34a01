"""LSD radix / counting sort as a Pallas kernel — the wide-row sort family.

Bitonic's n log^2 n comparator count loses on wide rows: at n = 2^14
the network runs 105 compare-exchange substages over the full row,
while an LSD radix sort of 32-bit keys is 8 counting passes (4 bits
each).  No platform runs it well today: Mosaic lowers neither the
in-kernel scatter nor the per-lane gather, so on TPU the sort family
stays bitonic, and the CPU interpreter prices the scatter out.  The
kernel is reached through ``ops.force_sort_kernel`` only
(``ops.sort_kernel_choice``).

Key specialization: one *unsigned* radix core serves every eligible
dtype through a monotone bijection into sortable unsigned bits —
:func:`key_to_bits` / :func:`bits_to_key`:

* int32   -> ``x XOR 0x80000000`` (offset-binary).
* float32 -> bitcast, then ``u XOR 0x80000000`` when the sign bit is
  clear and ``NOT u`` when it is set — IEEE-754 bit patterns become
  totally ordered as unsigned ints (negative-payload NaNs first,
  positive-payload NaNs last; -0.0 just below +0.0).
* bf16    -> the 16-bit variant of the float fold, carried in the low
  16 bits of the uint32 — the key width halves, so the radix core runs
  4 passes instead of 8.

The kernel sorts *bits + permutation*: every pass scatters an int32
index channel alongside the key bits, so the caller gets the stable
argsort permutation for free and ``ops.sort_kv`` carries payloads
through one gather instead of a (key, iota) lexicographic pair sort.

Stability and parity: each counting pass places equal digits in input
order (rank = prefix count), so the whole LSD sort is stable.  Before
the passes, keys are canonicalized onto ``jnp.sort``'s comparator
equivalence classes (:func:`_sort_ready_bits`): XLA's float compare is
NOT a bit-pattern total order — every NaN (either sign, any payload)
sorts last as one class, and -0.0 equals +0.0 — so all NaNs map to the
all-ones pattern and the bijected -0.0 folds onto +0.0 (each tie then
keeps input order, exactly like the stable reference).  The folds
happen in the *bits* domain: the arithmetic spelling ``x + 0.0`` is
algebraically simplified away by XLA, which silently un-folds -0.0.
Output keys are gathered from the *original* input through the
permutation, so bit patterns (NaN payloads, -0.0) survive untouched —
the radix path's parity contract is strictly wider than bitonic's
NaN-free one.

Pass structure (per (block_rows, n) tile, all passes in ONE kernel):
``digit = (bits >> shift) & (B-1)``; a (rows, n, B) one-hot against a
bin iota gives, via one inclusive cumsum along n, each element's rank
within its bin AND the per-bin totals; an exclusive cumsum of the
totals yields the bin starts; ``position = starts[digit] + rank - 1``;
then a stable in-VMEM scatter of (bits, index).  The counting
histogram never leaves VMEM — HBM traffic is one read + one write of
the (bits, index) pair for the whole kernel, however many passes run.
Rows need no power-of-two padding: counting passes have no network
structure, so any n >= 1 sorts directly.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = [
    "key_to_bits",
    "bits_to_key",
    "key_bits",
    "radix_sort",
    "DEFAULT_RADIX_BITS",
]

# Digits per counting pass: 4 bits = 16 bins keeps the (rows, n, 16)
# one-hot rank tensor comfortably in VMEM for 64k-lane rows while
# needing only 8 passes for 32-bit keys (4 for bf16).
DEFAULT_RADIX_BITS = 4

_I32_MIN = jnp.int32(-(1 << 31))


def key_bits(dtype) -> int:
    """Sort-significant key width in bits: 16 for bf16, 32 otherwise."""
    dtype = jnp.dtype(dtype)
    if dtype == jnp.dtype(jnp.bfloat16):
        return 16
    if dtype in (jnp.dtype(jnp.float32), jnp.dtype(jnp.int32)):
        return 32
    raise TypeError(f"no radix key specialization for dtype {dtype}")


def key_to_bits(x: jnp.ndarray) -> jnp.ndarray:
    """Monotone bijection: keys -> sortable unsigned bits (uint32).

    The *unsigned* order of the result equals the key order — numeric
    for ints, IEEE-754 total order over bit patterns for floats (so
    -0.0 < +0.0 and NaN payloads land at the extremes by sign).  Exact
    bijection: every bit pattern, NaNs and -0.0 included, round-trips
    through :func:`bits_to_key`.  bf16 keys map into [0, 2^16), which
    is what lets the radix core halve its pass count.
    """
    dtype = jnp.dtype(x.dtype)
    if dtype == jnp.dtype(jnp.int32):
        return jax.lax.bitcast_convert_type(
            jnp.bitwise_xor(x, _I32_MIN), jnp.uint32)
    if dtype == jnp.dtype(jnp.float32):
        u = jax.lax.bitcast_convert_type(x, jnp.int32)
        mask = jnp.where(u < 0, jnp.int32(-1), _I32_MIN)
        return jax.lax.bitcast_convert_type(
            jnp.bitwise_xor(u, mask), jnp.uint32)
    if dtype == jnp.dtype(jnp.bfloat16):
        u = jax.lax.bitcast_convert_type(x, jnp.uint16).astype(jnp.uint32)
        mask = jnp.where(u >= 0x8000, jnp.uint32(0xFFFF), jnp.uint32(0x8000))
        return jnp.bitwise_xor(u, mask)
    raise TypeError(f"no radix key specialization for dtype {dtype}")


def bits_to_key(bits: jnp.ndarray, dtype) -> jnp.ndarray:
    """Exact inverse of :func:`key_to_bits`.  bits: uint32."""
    dtype = jnp.dtype(dtype)
    if dtype == jnp.dtype(jnp.int32):
        return jnp.bitwise_xor(
            jax.lax.bitcast_convert_type(bits, jnp.int32), _I32_MIN)
    if dtype == jnp.dtype(jnp.float32):
        b = jax.lax.bitcast_convert_type(bits, jnp.int32)
        mask = jnp.where(b < 0, _I32_MIN, jnp.int32(-1))
        return jax.lax.bitcast_convert_type(
            jnp.bitwise_xor(b, mask), jnp.float32)
    if dtype == jnp.dtype(jnp.bfloat16):
        mask = jnp.where(bits >= 0x8000,
                         jnp.uint32(0x8000), jnp.uint32(0xFFFF))
        u = jnp.bitwise_xor(bits, mask).astype(jnp.uint16)
        return jax.lax.bitcast_convert_type(u, jnp.bfloat16)
    raise TypeError(f"no radix key specialization for dtype {dtype}")


def _sort_ready_bits(x: jnp.ndarray) -> jnp.ndarray:
    """Canonicalized key bits as the kernel's int32 carrier.

    :func:`key_to_bits` with ``jnp.sort``'s comparator equivalence
    classes folded in.  XLA compares floats flush-to-zero, so every
    denormal (either sign) equals +-0.0: the whole class is one
    contiguous bijected band ``[2^(kb-1) - 2^mant, 2^(kb-1) + 2^mant)``
    and folds onto the bijected +0.0 point (the tie then keeps input
    order, exactly like the stable reference).  Every NaN — either
    sign, any payload — maps to the all-ones pattern (NaNs sort last,
    in input order; only NaN patterns can biject to all-ones, so
    nothing collides).  The carrier is int32 — TPU-native — and the
    kernel extracts digits through a uint32 bitcast, so the *unsigned*
    bit order is what gets sorted.
    """
    dtype = jnp.dtype(x.dtype)
    bits = key_to_bits(x)
    if dtype != jnp.dtype(jnp.int32):
        kb = key_bits(dtype)
        mant = 1 << (7 if kb == 16 else 23)          # mantissa span
        pos_zero = jnp.uint32(1 << (kb - 1))
        allones = jnp.uint32((1 << kb) - 1)
        denorm = (bits >= pos_zero - mant) & (bits < pos_zero + mant)
        bits = jnp.where(denorm, pos_zero, bits)
        bits = jnp.where(jnp.isnan(x), allones, bits)
    return jax.lax.bitcast_convert_type(bits, jnp.int32)


def _pass_positions(bits, shift: int, radix_bits: int):
    """Destinations of one stable counting pass over ``(bits >> shift)``.

    bits: (rows, n) int32, already in this pass's input order.  Pure
    jnp — this is the kernel body's workhorse and runs standalone under
    interpret mode.  The inclusive cumsum of the one-hot digit tensor
    yields both the within-bin rank of every element and (its last
    slice) the per-bin totals, so one reduction feeds both sides of
    ``position = start + rank - 1``.
    """
    rows, n = bits.shape
    nbins = 1 << radix_bits
    u = jax.lax.bitcast_convert_type(bits, jnp.uint32)
    digit = ((u >> shift) & (nbins - 1)).astype(jnp.int32)      # (rows, n)
    onehot = (digit[:, :, None]
              == jax.lax.broadcasted_iota(jnp.int32, (1, 1, nbins), 2)
              ).astype(jnp.int32)
    ranks = jnp.cumsum(onehot, axis=1)                          # inclusive
    totals = ranks[:, -1, :]                                    # (rows, nbins)
    starts = jnp.cumsum(totals, axis=1) - totals                # exclusive
    rank = jnp.take_along_axis(ranks, digit[:, :, None], axis=2)[:, :, 0]
    return jnp.take_along_axis(starts, digit, axis=1) + rank - 1


def _radix_kernel(b_ref, i_ref, ob_ref, oi_ref, *, passes: int,
                  radix_bits: int):
    """All LSD passes over one (block_rows, n) tile.

    Only the permutation channel moves through the per-pass scatter;
    the key bits stay put in ``b_ref`` and each pass re-gathers them
    through the current permutation (gathers are cheap where scatters
    are not, and it halves the channel traffic of the scatter).
    """
    bits0 = b_ref[...]
    idx = i_ref[...]
    rows, n = bits0.shape
    row_iota = jax.lax.broadcasted_iota(jnp.int32, (rows, n), 0)
    for p in range(passes):
        cur = jnp.take_along_axis(bits0, idx, axis=1)
        pos = _pass_positions(cur, p * radix_bits, radix_bits)
        idx = jnp.zeros_like(idx).at[row_iota, pos].set(
            idx, unique_indices=True, mode="promise_in_bounds")
    ob_ref[...] = jnp.take_along_axis(bits0, idx, axis=1)
    oi_ref[...] = idx


@functools.partial(jax.jit,
                   static_argnames=("radix_bits", "block_rows", "interpret"))
def radix_sort(x: jnp.ndarray, radix_bits: int = DEFAULT_RADIX_BITS,
               block_rows: int = 8, interpret: bool = True):
    """Stable row-wise ascending sort via the Pallas radix kernel.

    x: (rows, n), any n >= 1 (no power-of-two padding needed).  Returns
    ``(sorted, order)``: ``order`` (rows, n) int32 is the *stable*
    argsort permutation of each row, and ``sorted`` is gathered from
    the original ``x`` through it — bitwise equal to ``jnp.sort`` /
    stable ``jnp.argsort`` for every input, NaN (either sign, payload
    bits preserved), -0.0 and infinities included (the comparator
    equivalence classes — see the module docstring).
    Interpret mode only: Mosaic lowers neither the in-kernel scatter nor
    the per-lane gather, so ``ops.sort_kernel_choice`` picks this kernel
    only under ``ops.force_sort_kernel``.
    """
    rows, n = x.shape
    if n == 0:
        return x, jnp.zeros((rows, 0), jnp.int32)
    block_rows = min(block_rows, rows)
    passes = -(-key_bits(x.dtype) // radix_bits)
    bits = _sort_ready_bits(x)
    rpad = (-rows) % block_rows
    if rpad:
        bits = jnp.pad(bits, ((0, rpad), (0, 0)))
    idx = jax.lax.broadcasted_iota(jnp.int32, bits.shape, 1)
    spec = pl.BlockSpec((block_rows, n), lambda i: (i, 0))
    _, oi = pl.pallas_call(
        functools.partial(_radix_kernel, passes=passes,
                          radix_bits=radix_bits),
        grid=((rows + rpad) // block_rows,),
        in_specs=[spec, spec],
        out_specs=(spec, spec),
        out_shape=(jax.ShapeDtypeStruct(bits.shape, jnp.int32),
                   jax.ShapeDtypeStruct(idx.shape, jnp.int32)),
        interpret=interpret,
    )(bits, idx)
    order = oi[:rows]
    return jnp.take_along_axis(x, order, axis=-1), order
