"""On-device data sketches — the planner's one-pass statistics phase.

Three sketches, each jit-compatible and shard-local (no collectives in
the body, so the same code runs under vmap virtual machines and a
shard_map mesh):

* **Heavy hitters** — per-shard top-``HH_K`` keys with counts.  On
  kernel-eligible shards (1-2D float32/bfloat16/int32 rows that fit
  VMEM, per ``ops.kernel_eligible``) this is the *sorted-runs* pass:
  one ``ops.sort`` plus two ``ops.searchsorted`` sweeps yield exact
  run lengths, and ``top_k`` keeps the heaviest.  Ineligible
  shards fall back to a streaming :func:`misra_gries` ``lax.scan`` with
  O(HH_K) state.  Either way the per-shard summaries merge by summing
  counts per key — the standard Misra-Gries merge, a lower bound on the
  true count, refined against the CountMin upper bound host-side.
* **CountMin** — a (depth, width) table of hashed counts; point queries
  overestimate by at most the collision mass.  All shards share the
  same row salts, so tables merge by elementwise addition and the
  merged inner product ``min_d <S_d, T_d>`` estimates the join size.
* **KMV distinct count** — the ``KMV_K`` smallest distinct hash values;
  merging keeps the smallest of the union and the k-th minimum
  estimates the distinct-key count.

Shard sketches are computed on-device in one pass and merged host-side
into a :class:`TableProfile` (and a pair of them + join-size estimate
into a :class:`DataProfile`); the sketch round is recorded on the
substrate's CollectiveTape as a ``round0 sketch`` phase whose network
cost is the all_gather of the t fixed-size sketch vectors.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional, Tuple

import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.kernels import ops

__all__ = [
    "HH_K", "CM_DEPTH", "CM_WIDTH", "KMV_K", "SKETCH_SAMPLE",
    "ShardSketch", "TableProfile", "DataProfile",
    "misra_gries", "shard_sketch", "sketch_size", "countmin_query",
    "merge_shard_sketches", "sketch_table", "profile_sorted_shards",
    "profile_join_tables", "expert_counts_estimate",
]

HH_K = 8          # heavy-hitter slots per shard
CM_DEPTH = 3      # CountMin rows
CM_WIDTH = 512    # CountMin columns (power of two)
KMV_K = 64        # distinct-count minima retained
# The planner's per-shard work cap: shards longer than this are strided
# down to ~this many keys and the sketch counts scaled back up, keeping
# the sketch pass O(SKETCH_SAMPLE log SKETCH_SAMPLE) per machine
# regardless of shard size (the <10%-of-join-time overhead budget).
SKETCH_SAMPLE = 512

_I32_MAX = np.iinfo(np.int32).max
# Odd multiplicative salts (Knuth/xxhash constants); row d of every
# shard's CountMin uses salt d, so tables merge by addition.
_CM_SALTS = np.array([0x9E3779B1, 0x85EBCA77, 0xC2B2AE3D, 0x27D4EB2F],
                     dtype=np.uint32)
_KMV_SALT = np.uint32(0x2545F491)


class ShardSketch(NamedTuple):
    """One shard's fixed-size summary (all arrays static-shaped)."""
    n: jnp.ndarray            # () int32 — valid (unmasked) objects, full shard
    heavy_keys: jnp.ndarray   # (HH_K,) key dtype
    heavy_counts: jnp.ndarray # (HH_K,) int32, 0 = empty slot (sample counts)
    countmin: jnp.ndarray     # (CM_DEPTH, CM_WIDTH) int32 (sample counts)
    kmv: jnp.ndarray          # (KMV_K,) int32 ascending minima, I32_MAX = empty
    scale: jnp.ndarray        # () int32 — subsample stride; counts x scale
                              # approximate the full shard


def sketch_size(hh_k: int = HH_K, cm_depth: int = CM_DEPTH,
                cm_width: int = CM_WIDTH, kmv_k: int = KMV_K) -> int:
    """Objects in one shard sketch — the sketch phase's network unit."""
    return 1 + 2 * hh_k + cm_depth * cm_width + kmv_k


def _to_u32(keys: jnp.ndarray) -> jnp.ndarray:
    """Reinterpret 32-bit keys as uint32 for hashing (int32 and float32)."""
    return lax.bitcast_convert_type(keys, jnp.uint32)


def _cm_hash(keys_u32: jnp.ndarray, depth: int, width: int) -> jnp.ndarray:
    """(depth, n) int32 CountMin column ids; uint32 arithmetic wraps."""
    salts = jnp.asarray(_CM_SALTS[:depth])[:, None]
    h = keys_u32[None, :] * salts + (salts >> 3)
    h = h ^ (h >> 15)
    return (h % jnp.uint32(width)).astype(jnp.int32)


def _kmv_hash(keys_u32: jnp.ndarray) -> jnp.ndarray:
    """(n,) int32 hash in [0, 2^31) — KMV needs an orderable hash."""
    h = keys_u32 * _KMV_SALT + jnp.uint32(0x9E3779B9)
    h = h ^ (h >> 16)
    return (h >> jnp.uint32(1)).astype(jnp.int32)


def misra_gries(keys: jnp.ndarray, k: int, masked=None
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Streaming Misra-Gries heavy hitters: k slots, one ``lax.scan`` pass.

    Returns ``(slot_keys (k,), slot_counts (k,))``; a slot count of 0
    means empty.  Guarantee: any key with true count > n/(k+1) occupies
    a slot, and slot counts undercount by at most n/(k+1).  O(k) state —
    the fallback when a shard is not kernel-eligible for the sorted-runs
    pass.  ``masked`` keys are skipped.
    """
    iota = jnp.arange(k)

    def step(carry, x):
        sk, sc = carry
        match = (sk == x) & (sc > 0)
        has = jnp.any(match)
        empty = sc == 0
        any_empty = jnp.any(empty)
        first_empty = jnp.argmax(empty)
        ins = (~has) & any_empty & (iota == first_empty)
        dec = (~has) & (~any_empty)
        nk = jnp.where(ins, x, sk)
        nc = jnp.where(match, sc + 1,
                       jnp.where(ins, 1, jnp.where(dec, sc - 1, sc)))
        if masked is not None:
            valid = x != masked
            nk = jnp.where(valid, nk, sk)
            nc = jnp.where(valid, nc, sc)
        return (nk, nc), None

    init = (jnp.zeros((k,), keys.dtype), jnp.zeros((k,), jnp.int32))
    (sk, sc), _ = lax.scan(step, init, keys)
    return sk, sc


def _pad_to(x: jnp.ndarray, k: int, value=0) -> jnp.ndarray:
    return x if x.shape[0] >= k else jnp.pad(x, (0, k - x.shape[0]),
                                             constant_values=value)


def shard_sketch(keys: jnp.ndarray, *, hh_k: int = HH_K,
                 cm_depth: int = CM_DEPTH, cm_width: int = CM_WIDTH,
                 kmv_k: int = KMV_K, masked=None,
                 sample: Optional[int] = None) -> ShardSketch:
    """One pass over a shard: heavy hitters + CountMin + KMV minima.

    ``masked`` is the padding sentinel (``MASKED_KEY`` for dealt join
    shards, None for dense sort shards); masked slots contribute to no
    sketch.  ``sample`` caps the per-shard work: longer shards are
    strided down to ~sample keys, the stride is returned as
    ``ShardSketch.scale``, and the merge multiplies counts back up
    (``n`` stays the exact full-shard count either way).  Shapes are
    static — safe under jit, vmap and shard_map.
    """
    n_full = keys.shape[0]
    full_valid = (jnp.ones((n_full,), bool) if masked is None
                  else keys != jnp.asarray(masked, keys.dtype))
    n_valid = jnp.sum(full_valid).astype(jnp.int32)

    stride = 1
    if sample is not None and n_full > sample:
        stride = -(-n_full // sample)
        keys = keys[::stride]
    n = keys.shape[0]
    valid = full_valid[::stride] if stride > 1 else full_valid
    ku = _to_u32(keys)
    kk = min(kmv_k, n)

    # -- heavy hitters: kernel-eligible shards take the sorted-runs pass
    # (one ops.sort + two ops.searchsorted sweeps, exact counts); the
    # sorted order is reused to dedupe the KMV hashes for free.
    if ops.kernel_eligible("sort", keys):
        xs = ops.sort(keys)
        lo = ops.searchsorted(xs, xs, side="left")
        hi = ops.searchsorted(xs, xs, side="right")
        first = lo == jnp.arange(n, dtype=lo.dtype)
        if masked is not None:
            first = first & (xs != jnp.asarray(masked, xs.dtype))
        cnt = jnp.where(first, hi - lo, 0)
        hc, idx = lax.top_k(cnt, min(hh_k, n))
        hk = xs[idx]
        # distinct hash values: one hash per run representative
        hv = jnp.where(first, _kmv_hash(_to_u32(xs)), _I32_MAX)
        mins = -lax.top_k(-hv, kk)[0]                  # k smallest, asc
    else:
        # streaming Misra-Gries, O(hh_k) state; KMV pays its own sort
        sk, sc = misra_gries(keys, hh_k, masked=masked)
        hc, idx = lax.top_k(sc, hh_k)
        hk = sk[idx]
        hv = jnp.where(valid, _kmv_hash(ku), _I32_MAX)
        hs = ops.sort(hv)
        prev = jnp.concatenate([jnp.full((1,), -1, jnp.int32), hs[:-1]])
        dedup = jnp.where(hs == prev, _I32_MAX, hs)
        mins = -lax.top_k(-dedup, kk)[0]
    hk = _pad_to(hk, hh_k)
    hc = _pad_to(hc.astype(jnp.int32), hh_k)
    mins = _pad_to(mins, kmv_k, value=_I32_MAX)

    # -- CountMin: one scatter-add per row, shared salts across shards
    h = _cm_hash(ku, cm_depth, cm_width)                   # (depth, n)
    rows = jnp.arange(cm_depth)[:, None]
    cm = jnp.zeros((cm_depth, cm_width), jnp.int32).at[rows, h].add(
        valid.astype(jnp.int32)[None, :])
    return ShardSketch(n_valid, hk, hc, cm, mins,
                       jnp.asarray(stride, jnp.int32))


# ---------------------------------------------------------------------------
# host-side merge -> TableProfile / DataProfile
# ---------------------------------------------------------------------------

def countmin_query(cm: np.ndarray, keys: np.ndarray) -> np.ndarray:
    """Point-query a (merged) CountMin table: min over rows, >= truth.

    Pure numpy mirror of the device-side :func:`_cm_hash` (uint32
    arithmetic wraps identically in both) — the merge path calls this
    several times per plan and a jnp host round-trip per call would
    dominate the planner's overhead budget.
    ``tests/test_planner.py::test_countmin_query_matches_device_hash``
    pins the two hash implementations against each other.
    """
    keys = np.atleast_1d(np.asarray(keys))
    if keys.dtype.kind in "iu":
        ku = keys.astype(np.int32, copy=False).view(np.uint32)
    else:
        ku = keys.astype(np.float32, copy=False).view(np.uint32)
    depth, width = cm.shape
    salts = _CM_SALTS[:depth][:, None]
    h = ku[None, :] * salts + (salts >> 3)
    h = h ^ (h >> np.uint32(15))
    idx = (h % np.uint32(width)).astype(np.int64)
    return np.min(cm[np.arange(depth)[:, None], idx], axis=0)


@dataclasses.dataclass(frozen=True)
class TableProfile:
    """Merged sketch summary of one table (or one (t, m) sort input)."""
    n: int                     # total valid objects
    t: int                     # shards merged
    distinct: float            # KMV estimate
    heavy_keys: np.ndarray     # (<=HH_K,) heaviest keys, count-descending
    heavy_counts: np.ndarray   # (<=HH_K,) CountMin-refined count estimates
    countmin: np.ndarray       # (depth, width) merged table

    @property
    def duplication(self) -> float:
        """Average copies per distinct key (1.0 = all keys unique)."""
        return self.n / max(self.distinct, 1.0)

    @property
    def top_count(self) -> float:
        return float(self.heavy_counts[0]) if len(self.heavy_counts) else 0.0

    @property
    def top_share(self) -> float:
        return self.top_count / max(self.n, 1)


def _kmv_estimate(minima: np.ndarray, kmv_k: int) -> float:
    u = np.unique(minima)
    u = u[u < _I32_MAX]
    if len(u) == 0:
        return 0.0
    if len(u) < kmv_k:
        return float(len(u))          # saw every distinct hash — exact
    kth = float(u[kmv_k - 1])
    return (kmv_k - 1) / ((kth + 1.0) / 2.0**31)


def merge_shard_sketches(sk: ShardSketch, hh_k: int = HH_K,
                         kmv_k: int = KMV_K) -> TableProfile:
    """Merge t shard sketches (leading axis t on every field) host-side.

    Subsampled shards (scale > 1) have their heavy/CountMin counts
    multiplied back up; ``n`` is exact regardless."""
    n_shards = np.asarray(sk.n).reshape(-1)
    t = len(n_shards)
    n = int(n_shards.sum())
    scale = np.asarray(sk.scale, np.int64).reshape(-1)            # (t,)
    cm = (np.asarray(sk.countmin, np.int64).reshape(t, *sk.countmin.shape[-2:])
          * scale[:, None, None]).sum(axis=0)

    hk = np.asarray(sk.heavy_keys).reshape(t, -1)
    hc = np.asarray(sk.heavy_counts, np.int64).reshape(t, -1) * scale[:, None]
    agg = {}
    for key, cnt in zip(hk.reshape(-1), hc.reshape(-1)):
        if cnt > 0:
            agg[key.item()] = agg.get(key.item(), 0) + int(cnt)
    top = sorted(agg.items(), key=lambda kv: -kv[1])[:hh_k]
    if top:
        keys = np.asarray([k for k, _ in top], dtype=hk.dtype)
        # The MG-merged sums are lower bounds (exact whenever the key
        # made every shard's top-k — guaranteed for truly heavy keys);
        # the CountMin upper bound would add collision mass, so it only
        # serves as the sanity clip.  true count in [counts, upper].
        lower = np.asarray([c for _, c in top], dtype=np.int64)
        upper = countmin_query(cm, keys).astype(np.int64)
        counts = np.minimum(lower, upper)
        order = np.argsort(-counts, kind="stable")
        keys, counts = keys[order], counts[order]
    else:
        keys = np.asarray([], dtype=hk.dtype)
        counts = np.asarray([], dtype=np.int64)

    distinct = _kmv_estimate(np.asarray(sk.kmv).reshape(-1), kmv_k)
    return TableProfile(n=n, t=t, distinct=distinct, heavy_keys=keys,
                        heavy_counts=counts, countmin=cm)


@dataclasses.dataclass(frozen=True)
class DataProfile:
    """A join pair's profile: both tables + cross statistics."""
    s: TableProfile
    t: TableProfile
    est_join_size: float       # CountMin inner product min_d <S_d, T_d>
    heavy_keys: np.ndarray     # union of both tables' heavy keys
    heavy_products: np.ndarray # est count in S x est count in T, per key

    @property
    def max_heavy_product(self) -> float:
        return float(self.heavy_products.max()) if len(self.heavy_products) \
            else 0.0

    @property
    def size_ratio(self) -> float:
        """min(|S|,|T|) / max(|S|,|T|) in [0, 1]."""
        lo, hi = sorted((self.s.n, self.t.n))
        return lo / max(hi, 1)


def _estimate_join_size(cm_s: np.ndarray, cm_t: np.ndarray) -> float:
    """min over rows of the CountMin inner product — >= W, excess bounded
    by the collision mass |S||T|/width."""
    return float(np.min(np.sum(cm_s * cm_t, axis=1)))


def build_data_profile(ps: TableProfile, pt: TableProfile) -> DataProfile:
    union = np.unique(np.concatenate([ps.heavy_keys, pt.heavy_keys])) \
        if len(ps.heavy_keys) or len(pt.heavy_keys) \
        else np.asarray([], dtype=np.int32)
    if len(union):
        prod = (countmin_query(ps.countmin, union).astype(np.float64)
                * countmin_query(pt.countmin, union).astype(np.float64))
    else:
        prod = np.asarray([], dtype=np.float64)
    return DataProfile(s=ps, t=pt,
                       est_join_size=_estimate_join_size(ps.countmin,
                                                         pt.countmin),
                       heavy_keys=union, heavy_products=prod)


# ---------------------------------------------------------------------------
# substrate drivers: sketch every shard in one program, tape the phase
# ---------------------------------------------------------------------------

SKETCH_PHASE = "round0 sketch"


@functools.lru_cache(maxsize=None)
def _single_body(t_total: int, masked, sample):
    """Stable per-parameter body function — jitting substrates cache by
    function identity, so the closure must be created once, not per call."""
    size = sketch_size()

    def body(xl, tape):
        with tape.phase(SKETCH_PHASE):
            sk = shard_sketch(xl, masked=masked, sample=sample)
            tape.record(sent=size, received=size * t_total)
        return sk

    return body


@functools.lru_cache(maxsize=None)
def _pair_body(t_total: int, masked, sample):
    size = 2 * sketch_size()

    def body(sl, tl, tape):
        with tape.phase(SKETCH_PHASE):
            a = shard_sketch(sl, masked=masked, sample=sample)
            b = shard_sketch(tl, masked=masked, sample=sample)
            tape.record(sent=size, received=size * t_total)
        return a, b

    return body


def sketch_table(x_shards: jnp.ndarray, substrate, *, masked=None,
                 sample: Optional[int] = SKETCH_SAMPLE):
    """Sketch a (t, m) sharded table on the substrate.

    Returns ``(TableProfile, tape)`` — the tape carries the sketch
    phase (each machine ships its fixed-size sketch, receives all t).
    ``sample=None`` disables the per-shard subsampling cap."""
    body = _single_body(substrate.t, masked, sample)
    sk, tape = substrate.run(body, x_shards)
    return merge_shard_sketches(sk), tape


def profile_sorted_shards(x: jnp.ndarray, substrate, *,
                          sample: Optional[int] = SKETCH_SAMPLE):
    """Profile a dense (t, m) sort input.  Returns (TableProfile, tape)."""
    return sketch_table(jnp.asarray(x), substrate, sample=sample)


def expert_counts_estimate(profile: TableProfile,
                           num_experts: int) -> np.ndarray:
    """Estimated per-expert assignment counts from a routing-id profile.

    The expert-id domain is tiny ([0, E)), so the whole histogram is a
    CountMin point-query sweep — an upper bound inflated by collision
    mass — refined by the Misra-Gries heavy hitters wherever one of the
    top keys IS that expert (the merged MG count is exact for truly hot
    experts, and ``min(MG-exact-side, CM)`` is the same refinement the
    TableProfile merge applies).  The sweep is rescaled so the total
    matches the exact assignment count ``profile.n`` — plan_slots only
    consumes ratios, but the capacity test reads absolute loads.
    """
    keys = np.arange(num_experts, dtype=np.int32)
    est = countmin_query(profile.countmin, keys).astype(np.float64)
    for key, cnt in zip(np.asarray(profile.heavy_keys).astype(np.int64),
                        np.asarray(profile.heavy_counts, np.float64)):
        if 0 <= key < num_experts:
            est[key] = min(est[key], cnt) if est[key] > 0 else cnt
    total = est.sum()
    if total > 0 and profile.n > 0:
        est = est * (profile.n / total)
    return np.maximum(est, 0.0)


def _deal(keys: np.ndarray, t: int, masked) -> jnp.ndarray:
    n = len(keys)
    pad = (-n) % t
    k = np.concatenate([np.asarray(keys),
                        np.full(pad, masked, np.asarray(keys).dtype)])
    return jnp.asarray(k.reshape(t, -1))


def profile_join_tables(s_keys: np.ndarray, t_keys: np.ndarray,
                        t_machines: int, substrate, *, masked,
                        sample: Optional[int] = SKETCH_SAMPLE):
    """Profile both join tables in ONE substrate program (one sketch round).

    Returns ``(DataProfile, tape)``."""
    ss = _deal(s_keys, t_machines, masked)
    ts = _deal(t_keys, t_machines, masked)
    body = _pair_body(substrate.t, masked, sample)
    (sk_s, sk_t), tape = substrate.run(body, ss, ts)
    profile = build_data_profile(merge_shard_sketches(sk_s),
                                 merge_shard_sketches(sk_t))
    return profile, tape
