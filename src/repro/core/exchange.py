"""Bucketed redistribution (the paper's Round-3 shuffle) on an SPMD machine.

MPI lets Round 3 send "however many objects landed in bucket k" — a ragged
exchange.  XLA cannot: every buffer shape is static.  The central hardware
adaptation of this repo is that the paper's k-bound *is* the static shape:
(alpha, k)-minimality proves each device receives at most ``k * m`` objects,
so a compile-time capacity ``C = ceil(cap_factor * m)`` with a validity mask
is safe (cap_factor = the algorithm's k bound + slack).  This is exactly the
MoE capacity-factor trick, justified by Theorem 1 / Theorem 3 instead of by
prayer.

Two backends:

* ``static``  — dense ``lax.all_to_all`` of (t, C/t) tiles padded with a
  sentinel.  Works under ``shard_map`` *and* ``vmap`` (used by unit tests).
* ``ragged``  — ``lax.ragged_all_to_all`` with exact send sizes into a
  C-sized output buffer.  shard_map only; saves the padding bandwidth.

Both report dropped-object counts so callers can detect capacity overflow —
a fault, recovered by the CapacityPolicy retry loop in repro.cluster.

Traffic accounting goes through a CollectiveTape (repro.cluster) when one
is supplied, so the (alpha, k) report is assembled from counters measured
inside the jitted program rather than hand-built phase lists.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.kernels import ops

__all__ = [
    "PAD",
    "partition_sorted",
    "build_send_buffer",
    "static_exchange",
    "ragged_exchange",
    "exchange_sorted_segments",
    "exchange_routed_rows",
    "return_routed_rows",
    "RoutedRows",
    "flat_receive_capacity",
    "staged_receive_capacities",
]

# Sentinel key for padded slots.  Keys are required to be finite floats or
# ints strictly below the sentinel; sorts push pads to the end.
PAD = jnp.inf


def _null_tape():
    from repro.cluster.collectives import CollectiveTape
    return CollectiveTape()


def partition_sorted(x_sorted: jnp.ndarray, interior: jnp.ndarray,
                     valid_len: Optional[int] = None
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Split a locally sorted vector into t contiguous destination segments.

    interior: (t-1,) interior boundaries b_1..b_{t-1}.  Element e goes to
    bucket k iff b_k <= e < b_{k+1} (b_0 = -inf, b_t = +inf).
    Returns (starts, lens), each (t,).

    Both backends run the same t-1 binary searches over the (sorted)
    local keys — `ops.searchsorted` dispatches them to the Pallas
    branch-free search kernel — and agree bitwise: segment k holds
    exactly the keys with b_k <= key < b_{k+1}.

    ``valid_len=m`` declares ``x_sorted`` pre-padded past its m real
    keys with the sort sentinel (the once-per-round padding contract of
    ``ops.pad_pow2``); cuts are clamped to m, which reproduces the
    unpadded answer exactly.
    """
    m = valid_len if valid_len is not None else x_sorted.shape[0]
    cuts = ops.searchsorted(x_sorted, interior, side="left",
                            valid_len=(None if valid_len is None
                                       else m))                # (t-1,)
    starts = jnp.concatenate([jnp.zeros((1,), cuts.dtype), cuts])
    ends = jnp.concatenate([cuts, jnp.full((1,), m, cuts.dtype)])
    return starts, ends - starts


def build_send_buffer(x_sorted: jnp.ndarray, starts: jnp.ndarray,
                      lens: jnp.ndarray, cap_per_pair: int,
                      values: Optional[jnp.ndarray] = None,
                      pad_key=PAD, valid_len: Optional[int] = None):
    """Pack t contiguous segments into a (t, C) tile, sentinel-padded.

    Returns (keys_buf, values_buf_or_None, dropped) where dropped counts
    objects beyond per-pair capacity (0 when capacity is adequate).
    ``values`` may be one array or a tuple of arrays, each with leading
    dim m.  ``valid_len`` bounds the gather when ``x_sorted`` carries a
    padded tail (segment indices never reach it — lens sum to valid_len).
    """
    t = starts.shape[0]
    m = valid_len if valid_len is not None else x_sorted.shape[0]
    cols = jnp.arange(cap_per_pair)
    idx = starts[:, None] + cols[None, :]                      # (t, C)
    valid = cols[None, :] < lens[:, None]
    safe = jnp.clip(idx, 0, m - 1)
    keys = jnp.where(valid, x_sorted[safe], jnp.asarray(pad_key, x_sorted.dtype))

    def pack(v):
        vals_g = v[safe]                                       # (t, C, ...)
        mask = valid.reshape(t, cap_per_pair, *([1] * (v.ndim - 1)))
        return jnp.where(mask, vals_g, jnp.zeros_like(vals_g))

    vals = jax.tree.map(pack, values) if values is not None else None
    dropped = jnp.sum(jnp.maximum(lens - cap_per_pair, 0))
    return keys, vals, dropped


def static_exchange(keys_buf: jnp.ndarray, axis_name: str,
                    values_buf: Optional[jnp.ndarray] = None,
                    tape=None, sent=None):
    """Dense all_to_all of (t, C) tiles: row k goes to device k.

    When a tape is supplied, the exchange is recorded with ``sent`` (the
    caller's off-device object count) and a PAD-aware received count; the
    values buffer rides along untracked (the paper counts objects, and a
    key+payload pair is one object).
    """
    tape = tape if tape is not None else _null_tape()
    recv_k = tape.all_to_all(keys_buf, axis_name, split_axis=0,
                             concat_axis=0, sent=sent, pad=PAD)
    recv_v = None
    if values_buf is not None:
        recv_v = tape.all_to_all(values_buf, axis_name, split_axis=0,
                                 concat_axis=0, track=False)
    return recv_k, recv_v


def ragged_exchange(x_sorted: jnp.ndarray, starts: jnp.ndarray,
                    lens: jnp.ndarray, axis_name: str, capacity: int,
                    values: Optional[jnp.ndarray] = None,
                    pad_key=PAD, tape=None, sent=None):
    """Exact-size exchange via lax.ragged_all_to_all (shard_map only).

    capacity: static receive-buffer size; Theorem 1/3 bound the true
    receive count, so ceil(k_bound * m) slots suffice.  ``values`` (same
    leading shape as x_sorted) ride through a second ragged exchange with
    the same size/offset vectors.
    Returns (recv_keys (capacity,), recv_values_or_None, recv_count).
    """
    tape = tape if tape is not None else _null_tape()
    sizes = lens.astype(jnp.int32)
    # L[src, dst] — everyone learns the full size matrix (t^2 ints, tiny).
    size_matrix = tape.all_gather(sizes, axis_name, track=False)   # (t, t)
    me = lax.axis_index(axis_name)
    # Where my chunk lands in dst's buffer: sum of earlier senders' sizes.
    col_excl = jnp.cumsum(size_matrix, axis=0) - size_matrix   # (t, t)
    output_offsets = col_excl[me].astype(jnp.int32)            # (t,)
    recv_sizes = size_matrix[:, me].astype(jnp.int32)          # (t,)
    in_offsets = starts.astype(jnp.int32)
    out = jnp.full((capacity,), jnp.asarray(pad_key, x_sorted.dtype))
    recv = tape.ragged_all_to_all(
        x_sorted, out, in_offsets, sizes, output_offsets, recv_sizes,
        axis_name=axis_name, sent=sent)
    recv_v = None
    if values is not None:
        out_v = jnp.zeros((capacity,) + values.shape[1:], values.dtype)
        recv_v = tape.ragged_all_to_all(
            values, out_v, in_offsets, sizes, output_offsets, recv_sizes,
            axis_name=axis_name, track=False)
    return recv, recv_v, jnp.sum(recv_sizes)


class RoutedRows(NamedTuple):
    """Landed state of :func:`exchange_routed_rows` — everything the
    receive side needs to unpack the tiles AND everything the send side
    needs to invert the routing for a return trip."""
    recv_keys: jnp.ndarray      # (t, cap_pair) owner keys; PAD = empty slot
    recv_payload: jnp.ndarray   # (t, cap_pair, w) payload rows, zeros on pads
    perm: jnp.ndarray           # (n,) stable argsort of owner (send order)
    dest_sorted: jnp.ndarray    # (n,) int32 destination of each sorted row
    starts: jnp.ndarray         # (t,) first sorted row addressed to dest k
    lens: jnp.ndarray           # (t,) rows addressed to dest k
    cap_pair: int               # static per-(src, dst) tile capacity
    local_drop: jnp.ndarray     # rows dropped at send (pair overflow)


def exchange_routed_rows(owner: jnp.ndarray, payload: jnp.ndarray, *,
                         axis_name, t: int, cap_pair: int,
                         tape=None) -> RoutedRows:
    """Deliver payload row i to machine ``owner[i]`` through the flat
    static exchange — the slot-major transpose as a first-class routed
    exchange (MoE expert dispatch's shuffle).

    owner: (n,) int destinations in [0, t).  payload: (n, w) rows (meta
    columns + features).  The rows are stably sorted by owner (the same
    ``ops.sort_kv`` permutation realization the sort algorithms use, so
    the Pallas kernel path applies), cut into t contiguous segments, and
    packed into the (t, cap_pair) tile of :func:`static_exchange`; the
    staged topology is not offered here because the payload rows are not
    1-D-mergeable (the staged path's intermediate hop re-merges, which
    only 1-D key/value columns support).  Per-pair overflow is counted
    in ``local_drop`` — the caller's CapacityPolicy retry loop recovers,
    exactly as for the sort shuffles.
    """
    tape = tape if tape is not None else _null_tape()
    n = owner.shape[0]
    iota = jnp.arange(n, dtype=jnp.int32)
    owner_f = owner.astype(jnp.float32)
    owner_sorted, perm = ops.sort_kv(owner_f, iota)
    pay_sorted = payload[perm]
    interior = jnp.arange(1, t, dtype=jnp.float32)
    starts, lens = partition_sorted(owner_sorted, interior)
    keys_buf, vals_buf, local_drop = build_send_buffer(
        owner_sorted, starts, lens, cap_pair, pay_sorted)
    me = lax.axis_index(axis_name)
    sent = n - lens[me]
    recv_k, recv_v = static_exchange(keys_buf, axis_name, vals_buf,
                                     tape=tape, sent=sent)
    return RoutedRows(recv_k, recv_v, perm, owner_sorted.astype(jnp.int32),
                      starts, lens, cap_pair, local_drop)


def return_routed_rows(back_tiles: jnp.ndarray, routed: RoutedRows, *,
                       axis_name, tape=None, sent=None, received=None
                       ) -> jnp.ndarray:
    """Invert :func:`exchange_routed_rows`: ship processed rows home.

    ``back_tiles``: (t, cap_pair, w_out) where tile j holds the
    processed versions of the rows source j landed here, in landed
    order — ``lax.all_to_all`` applied twice is an involution, so tile
    j of the second exchange arrives at j in exactly the (dst, col)
    layout j packed its send buffer with.  Rows that overflowed the
    pair capacity on the way out come back as zeros.  Returns (n, w_out)
    rows in the caller's ORIGINAL (pre-sort) order.  ``sent``/
    ``received`` feed the tape (the return tiles are dense payload with
    no sentinel, so the caller supplies the true counts).
    """
    tape = tape if tape is not None else _null_tape()
    ret = tape.all_to_all(back_tiles, axis_name, split_axis=0,
                          concat_axis=0, sent=sent, received=received)
    n = routed.perm.shape[0]
    p = jnp.arange(n, dtype=jnp.int32)
    offset = p - routed.starts[routed.dest_sorted]
    ok = offset < routed.cap_pair
    safe = jnp.clip(offset, 0, routed.cap_pair - 1)
    rows = jnp.where(ok[:, None], ret[routed.dest_sorted, safe],
                     jnp.zeros((), ret.dtype))
    out = jnp.zeros((n,) + ret.shape[2:], ret.dtype)
    return out.at[routed.perm].set(rows)


def flat_receive_capacity(m: int, t: int, cap_factor: float) -> int:
    """Receive-buffer slots of the flat exchange: t * ceil-per-pair.

    This is the exact formula the flat path sizes its landing buffer
    with — exported so the planner's topology model and the benchmark's
    peak-receive-bytes report price the same quantization the hardware
    pays (at large t, ``cap_total/t`` rounds *up* per pair, and a single
    hot pair forces the whole factor through the retry loop).
    """
    return int(-(-int(cap_factor * m) // t) * t)


def staged_receive_capacities(m: int, t1: int, t2: int, cap_factor: float,
                              overlap_chunks: int = 2) -> Tuple[int, int]:
    """(stage-1, stage-2) receive-buffer slots of the staged exchange.

    Stage 1 lands (t1, C1) with C1 = ceil(cap_factor*m / t1); stage 2
    lands (t2, C2) with C2 rounded up so ``overlap_chunks`` divides it.
    Per-pair loads at each stage are m/t1-scale rather than m/t-scale,
    so the base factor survives quantization that forces the flat path
    into capacity retries.
    """
    c1 = -(-int(cap_factor * m) // t1)
    chunks = max(1, int(overlap_chunks))
    c2 = -(-int(cap_factor * m) // t2)
    c2 = -(-c2 // chunks) * chunks
    return t1 * c1, t2 * c2


def _staged_exchange(x_sorted, interior, starts, lens, *, axis_names,
                     t1: int, t2: int, m: int, cap_factor: float,
                     values, valid_len, overlap_chunks: int,
                     tape, phase_prefix: str) -> "ExchangeResult":
    """Two-level compacted exchange (AMS-style): group-hop, merge,
    re-partition, final hop with overlapped chunk merges.

    Objects travel to their *destination group* along ``axis_names[0]``
    first (one segment per group: t2 consecutive flat segments fused),
    are merged and re-cut against the group's t2-1 interior boundaries,
    then travel to their final machine along ``axis_names[1]``.  The
    boundaries are global, so the final per-machine multiset is exactly
    the flat path's — sorted output parity is bitwise.  Per-stage
    capacities are ceil(cap_factor*m / t1) and / t2 (m/sqrt(t)-scale
    pair loads), which is where the peak-receive win over the flat
    t * ceil(cap_factor*m / t) buffer comes from.

    Values of tied keys leave in the flat path's order too: by source
    machine, then by position in its sorted run.  The stable merges keep
    ties in buffer order, which after two hops and chunked merges is
    hop-major, so each object carries its flat origin id beside its
    value and the landing is re-ordered by (key, origin) at the end.
    """
    a1, a2 = axis_names
    chunks = max(1, int(overlap_chunks))
    c1 = -(-int(cap_factor * m) // t1)
    c2 = -(-int(cap_factor * m) // t2)
    c2 = -(-c2 // chunks) * chunks
    if values is not None:
        rows = x_sorted.shape[0]
        if t1 * t2 * rows > jnp.iinfo(jnp.int32).max:
            raise ValueError(f"{t1 * t2} machines x {rows} rows overflow "
                             "the int32 origin ids of the staged exchange")
        src = lax.axis_index(a1) * t2 + lax.axis_index(a2)
        values = (values, src * rows + jnp.arange(rows, dtype=jnp.int32))
    # group segmentation: group g's segment = flat segments [g*t2, (g+1)*t2)
    g_starts = starts[::t2]                                      # (t1,)
    g_ends = jnp.concatenate([starts[t2::t2],
                              jnp.full((1,), m, starts.dtype)])
    g_lens = g_ends - g_starts
    kbuf1, vbuf1, drop1 = build_send_buffer(x_sorted, g_starts, g_lens, c1,
                                            values, valid_len=valid_len)
    me1 = lax.axis_index(a1)
    sent1 = m - g_lens[me1]
    aux = {}

    def restage(rk, rv):
        # merge the t1 landed sorted rows, then re-cut by MY group's
        # interior boundaries b[g*t2+1 .. g*t2+t2-1] (global indices
        # interior[g*t2 .. g*t2+t2-2]) — the same side='left' rule the
        # flat partition applies, so routing is identical.
        if rv is not None:
            merged, merged_v = ops.merge_sorted_rows_kv(rk, rv)
        else:
            merged = ops.merge_sorted_rows(rk)
            merged_v = None
        count1 = jnp.sum(merged < jnp.asarray(PAD, merged.dtype)
                         ).astype(jnp.int32)
        local_interior = lax.dynamic_slice(interior, (me1 * t2,), (t2 - 1,))
        s2_starts, s2_lens = partition_sorted(merged, local_interior,
                                              valid_len=count1)
        kbuf2, vbuf2, drop2 = build_send_buffer(merged, s2_starts, s2_lens,
                                                c2, merged_v,
                                                valid_len=count1)
        aux["drop2"] = drop2
        return kbuf2, vbuf2, count1 - s2_lens[lax.axis_index(a2)]

    def chunk_fn(rk, rv):
        if rv is not None:
            return ops.merge_sorted_rows_kv(rk, rv)
        return ops.merge_sorted_rows(rk), None

    outs, sent2 = tape.staged_all_to_all(
        kbuf1, (a1, a2), values_buf=vbuf1, sent=sent1, pad=PAD,
        restage=restage, chunks=chunks, chunk_fn=chunk_fn,
        phase_prefix=phase_prefix)
    if len(outs) == 1:
        final_k, final_v = outs[0]
    else:
        # cross-run merge of the per-chunk merges (each run is sorted)
        stacked = jnp.stack([ck for ck, _ in outs])
        if values is not None:
            final_k, final_v = ops.merge_sorted_rows_kv(
                stacked, jax.tree.map(lambda *cv: jnp.stack(cv),
                                      *[cv for _, cv in outs]))
        else:
            final_k = ops.merge_sorted_rows(stacked)
            final_v = None
    if values is not None:
        # (key, origin) order: stable sort by origin, then by key
        final_v, origin = final_v
        iota = jnp.arange(origin.shape[0], dtype=jnp.int32)
        _, by_origin = ops.sort_kv(origin, iota)
        final_k, perm = ops.sort_kv(final_k[by_origin], by_origin)
        final_v = final_v[perm]
    count = jnp.sum(final_k < jnp.asarray(PAD, final_k.dtype)
                    ).astype(jnp.int32)
    dropped = tape.psum(drop1 + aux["drop2"], (a1, a2)).astype(jnp.int32)
    return ExchangeResult(final_k, final_v, count, sent1 + sent2, dropped)


class ExchangeResult(NamedTuple):
    keys: jnp.ndarray             # (capacity,) sorted ascending, pads last
    values: Optional[jnp.ndarray]
    count: jnp.ndarray            # valid objects received (scalar)
    sent: jnp.ndarray             # objects sent to other devices (scalar)
    dropped: jnp.ndarray          # global dropped count (scalar, psum'd)


def exchange_sorted_segments(x_sorted: jnp.ndarray,
                             interior: jnp.ndarray,
                             *, axis_name, t: int,
                             cap_factor: float,
                             values: Optional[jnp.ndarray] = None,
                             backend: str = "static",
                             merge: bool = True,
                             sort_input: bool = False,
                             valid_len: Optional[int] = None,
                             tape=None,
                             staged_shape: Optional[Tuple[int, int]] = None,
                             overlap_chunks: int = 2,
                             phase_prefix: str = "shuffle"
                             ) -> ExchangeResult:
    """Round-3 shuffle: deliver bucket k of every device to device k.

    x_sorted: (m,) locally sorted keys.  interior: (t-1,) boundaries.
    Output capacity = ceil(cap_factor * m) rounded up to a multiple of t.

    On the static backend every sender's tile row lands already sorted,
    so the receive side merges the t rows (``ops.merge_sorted_rows``)
    rather than re-sorting; the ragged backend's receive buffer has
    device-dependent run offsets, so it re-sorts.

    ``sort_input=True`` takes *unsorted* keys and runs the fused
    ``ops.sort_partition[_kv]`` kernel — sort and boundary search in a
    single dispatch (Terasort's Round 3, where the two are adjacent).
    ``valid_len=m`` accepts keys (and values) pre-padded past m real
    objects with the sort sentinel (``ops.pad_pow2``), avoiding per-op
    pad/unpad round trips; mutually exclusive with ``sort_input``.

    ``staged_shape=(t1, t2)`` selects the two-level staged topology:
    ``axis_name`` must then be the (sub-axis-1, sub-axis-2) name pair of
    a t1 x t2 substrate and the shuffle runs as two ~sqrt(t)-way hops
    (see :func:`_staged_exchange`); the per-stage traffic lands in its
    own tape phase (``"<phase_prefix> s1"`` / ``"s2"``), so staged
    callers must NOT wrap this call in their own phase context.
    Output keys are bitwise equal to the flat path's.
    """
    if backend not in ("static", "ragged"):
        raise ValueError(f"unknown exchange backend {backend!r}; "
                         "expected 'static' or 'ragged'")
    if sort_input and valid_len is not None:
        raise ValueError("sort_input=True takes unpadded input; "
                         "valid_len cannot be combined with it")
    if staged_shape is not None:
        t1, t2 = int(staged_shape[0]), int(staged_shape[1])
        if t1 * t2 != t or min(t1, t2) < 2:
            raise ValueError(f"staged_shape {staged_shape} must factor "
                             f"t={t} with both sub-axes >= 2")
        if backend != "static":
            raise NotImplementedError(
                "staged exchange supports the static backend only")
        if not merge:
            raise ValueError("staged exchange implies merge=True "
                             "(the intermediate hop re-partitions a "
                             "merged vector)")
    m = valid_len if valid_len is not None else x_sorted.shape[0]
    cap_total = int(-(-int(cap_factor * m) // t) * t)  # round up to mult of t
    cap_pair = cap_total // t
    if sort_input:
        if values is not None:
            x_sorted, values, starts, lens = ops.sort_partition_kv(
                x_sorted, values, interior)
        else:
            x_sorted, starts, lens = ops.sort_partition(x_sorted, interior)
    else:
        starts, lens = partition_sorted(x_sorted, interior,
                                        valid_len=valid_len)
    if staged_shape is not None:
        return _staged_exchange(
            x_sorted, interior, starts, lens, axis_names=tuple(axis_name),
            t1=t1, t2=t2, m=m, cap_factor=cap_factor, values=values,
            valid_len=valid_len, overlap_chunks=overlap_chunks,
            tape=tape if tape is not None else _null_tape(),
            phase_prefix=phase_prefix)
    me = lax.axis_index(axis_name)
    sent = m - lens[me]  # objects leaving this device
    tape = tape if tape is not None else _null_tape()

    recv2d = recv_v2d = None
    if backend == "ragged":
        if valid_len is not None:      # exact-size sends: strip the pad tail
            x_sorted = x_sorted[:m]
            values = values[:m] if values is not None else None
        recv, recv_v, count = ragged_exchange(
            x_sorted, starts, lens, axis_name, cap_total, values=values,
            tape=tape, sent=sent)
        dropped = jnp.zeros((), jnp.int32)
    else:
        keys_buf, vals_buf, local_drop = build_send_buffer(
            x_sorted, starts, lens, cap_pair, values, valid_len=valid_len)
        recv2d, recv_v2d = static_exchange(keys_buf, axis_name, vals_buf,
                                           tape=tape, sent=sent)
        recv = recv2d.reshape(-1)
        recv_v = recv_v2d.reshape(-1, *recv_v2d.shape[2:]) if recv_v2d is not None else None
        count = jnp.sum(recv < jnp.asarray(PAD, recv.dtype)).astype(jnp.int32)
        dropped = tape.psum(local_drop, axis_name).astype(jnp.int32)

    if merge:
        if recv2d is not None:          # static: per-sender rows are sorted
            if recv_v2d is not None:
                recv, recv_v = ops.merge_sorted_rows_kv(recv2d, recv_v2d)
            else:
                recv = ops.merge_sorted_rows(recv2d)
        elif recv_v is not None:
            recv, recv_v = ops.sort_kv(recv, recv_v)
        else:
            recv = ops.sort(recv)
        # pads (= inf) land at the end in every path
    return ExchangeResult(recv, recv_v, count, sent, dropped)
