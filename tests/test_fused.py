"""The fused execution layer: fused kernels, amortized padding, the
shared compiled-program pool, donation plumbing, and the dispatch-count
budget that keeps fusion from silently regressing.

Differential contract: every fused op is bitwise-identical to its
unfused op chain on BOTH backends (the same oracle discipline as
tests/test_kernel_dispatch.py), including adversarial inputs — heavy
duplicates, presorted/reversed, data infs, non-pow2 lengths, int32.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import cluster
from repro.cluster import VmapSubstrate, default_pool, reset_default_pool
from repro.cluster.substrate import DONATION_PLATFORMS
from repro.data import uniform_keys
from repro.kernels import ops

T, M = 4, 192


def adversarial_keys(rng, m, dtype):
    kind = rng.integers(0, 5)
    if dtype == np.int32:
        x = rng.integers(0, max(2, m // 8), m).astype(np.int32)
    else:
        x = rng.normal(size=m).astype(np.float32)
        x[: m // 4] = x[0]                       # heavy duplicates
        if kind == 4:
            x[-3:] = np.inf                      # data infs (below PAD use)
    if kind == 1:
        x = np.sort(x)
    elif kind == 2:
        x = np.sort(x)[::-1].copy()
    elif kind == 3:
        x[:] = x[0]                              # all equal
    return x


# ---------------------------------------------------------------------------
# fused sort+partition kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,t", [(192, 4), (1024, 8), (100, 6), (7, 3)])
@pytest.mark.parametrize("dtype", [np.float32, np.int32])
def test_sort_partition_differential(rng, m, t, dtype):
    x = adversarial_keys(rng, m, dtype)
    interior = np.sort(rng.choice(x, t - 1)).astype(dtype)
    xj, ij = jnp.asarray(x), jnp.asarray(interior)
    got = {}
    for b in ("reference", "pallas"):
        got[b] = ops.sort_partition(xj, ij, backend=b)
    for a, p in zip(got["reference"], got["pallas"]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(p))
    # == the unfused chain, exactly
    xs = jnp.sort(xj)
    cuts = jnp.searchsorted(xs, ij, side="left")
    np.testing.assert_array_equal(np.asarray(got["reference"][0]), xs)
    np.testing.assert_array_equal(np.asarray(got["reference"][1])[1:], cuts)
    assert int(np.asarray(got["reference"][2]).sum()) == m


@pytest.mark.parametrize("m,t", [(192, 4), (333, 7)])
def test_sort_partition_kv_stability(rng, m, t):
    """Tie-heavy keys: the permutation must be the STABLE argsort."""
    x = rng.integers(0, 9, m).astype(np.int32)
    v = np.arange(m, dtype=np.int32)
    interior = np.sort(rng.integers(0, 9, t - 1)).astype(np.int32)
    res = {b: ops.sort_partition_kv(jnp.asarray(x), jnp.asarray(v),
                                    jnp.asarray(interior), backend=b)
           for b in ("reference", "pallas")}
    for a, p in zip(res["reference"], res["pallas"]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(p))
    order = np.argsort(x, kind="stable")
    np.testing.assert_array_equal(np.asarray(res["pallas"][1]), v[order])


def test_sort_partition_empty_interior():
    """t=1: no boundaries — still sorts, trivial single segment."""
    x = jnp.asarray(np.r_[3.0, 1.0, 2.0].astype(np.float32))
    for b in ("reference", "pallas"):
        xs, starts, lens = ops.sort_partition(x, jnp.zeros((0,), jnp.float32),
                                              backend=b)
        np.testing.assert_array_equal(np.asarray(xs), [1.0, 2.0, 3.0])
        assert np.asarray(starts).tolist() == [0]
        assert np.asarray(lens).tolist() == [3]


# ---------------------------------------------------------------------------
# blocked merge: hierarchical grid + the rank path past one tile
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("t,c", [(8, 512), (16, 1024), (3, 100), (1, 64)])
def test_merge_blocked_grid_differential(rng, t, c):
    x = np.sort(rng.normal(size=(t, c)).astype(np.float32), axis=1)
    ref = ops.merge_sorted_rows(jnp.asarray(x), backend="reference")
    ker = ops.merge_sorted_rows(jnp.asarray(x), backend="pallas")
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(ker))
    v = np.arange(t * c, dtype=np.int32).reshape(t, c)
    rk, rv = ops.merge_sorted_rows_kv(jnp.asarray(x), jnp.asarray(v),
                                      backend="reference")
    kk, kv = ops.merge_sorted_rows_kv(jnp.asarray(x), jnp.asarray(v),
                                      backend="pallas")
    np.testing.assert_array_equal(np.asarray(rk), np.asarray(kk))
    np.testing.assert_array_equal(np.asarray(rv), np.asarray(kv))


def test_merge_scales_past_one_tile(rng):
    """Total > MAX_KERNEL_LANES: the rank-merge path runs (no fallback)
    and stays bitwise-identical — including stability under heavy ties."""
    t, c = 4, ops.MAX_KERNEL_LANES // 2          # 4 rows -> 2x the tile cap
    assert t * c > ops.MAX_KERNEL_LANES
    keys = np.sort(rng.integers(0, 7, (t, c)).astype(np.int32), axis=1)
    assert ops.kernel_eligible("merge_sorted_rows", jnp.asarray(keys))
    ops.reset_dispatch_counts()
    ker = ops.merge_sorted_rows(jnp.asarray(keys), backend="pallas")
    assert ops.DISPATCH_COUNTS[("merge_sorted_rows", "pallas")] == 1
    ref = ops.merge_sorted_rows(jnp.asarray(keys), backend="reference")
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(ker))
    v = np.arange(t * c, dtype=np.int32).reshape(t, c)
    rk, rv = ops.merge_sorted_rows_kv(jnp.asarray(keys), jnp.asarray(v),
                                      backend="reference")
    kk, kv = ops.merge_sorted_rows_kv(jnp.asarray(keys), jnp.asarray(v),
                                      backend="pallas")
    np.testing.assert_array_equal(np.asarray(rk), np.asarray(kk))
    np.testing.assert_array_equal(np.asarray(rv), np.asarray(kv))


# ---------------------------------------------------------------------------
# amortized padding fast paths
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["reference", "pallas"])
def test_prepadded_round_trip(rng, backend):
    m = 100
    x = adversarial_keys(rng, m, np.float32)
    xp = ops.pad_pow2(jnp.asarray(x))
    assert xp.shape[0] == 128
    s_plain = ops.sort(jnp.asarray(x), backend=backend)
    s_pad = ops.sort(xp, backend=backend, prepadded=True)
    np.testing.assert_array_equal(np.asarray(s_plain),
                                  np.asarray(s_pad)[:m])
    assert np.all(np.asarray(s_pad)[m:] == np.inf)
    q = jnp.asarray(np.sort(rng.choice(x, 5)).astype(np.float32))
    np.testing.assert_array_equal(
        np.asarray(ops.searchsorted(s_plain, q, side="left",
                                    backend=backend)),
        np.asarray(ops.searchsorted(s_pad, q, side="left", backend=backend,
                                    valid_len=m)))
    # a query landing in the sentinel tail clamps to m — the unpadded answer
    over = ops.searchsorted(s_pad, jnp.asarray([np.inf], jnp.float32),
                            side="right", backend=backend, valid_len=m)
    assert int(over[0]) == m
    v = jnp.asarray(np.arange(m, dtype=np.int32))
    k1, v1 = ops.sort_kv(jnp.asarray(x), v, backend=backend)
    k2, v2 = ops.sort_kv(xp, ops.pad_pow2(v, fill=0), backend=backend,
                         prepadded=True)
    np.testing.assert_array_equal(np.asarray(k1), np.asarray(k2)[:m])
    np.testing.assert_array_equal(np.asarray(v1), np.asarray(v2)[:m])


def test_prepadded_contract_enforced():
    with pytest.raises(ValueError, match="power-of-two"):
        ops.sort(jnp.zeros(100, jnp.float32), prepadded=True)


# ---------------------------------------------------------------------------
# the shared pool: compile-once across calls (the terasort-outlier fix)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("algorithm", ["terasort", "smms"])
def test_compile_counter_front_door(algorithm):
    """The sampled-boundaries/sort program compiles ONCE; repeat calls
    are program-cache hits (pinned via Substrate.stats)."""
    reset_default_pool()
    x = jnp.asarray(uniform_keys(T * M, seed=31).reshape(T, M))
    cluster.sort(x, algorithm=algorithm)
    sub = default_pool()(T)
    first = sub.stats_snapshot()
    assert first["compiles"] >= 1
    for _ in range(2):
        cluster.sort(x, algorithm=algorithm)
    second = sub.stats_snapshot()
    assert second["compiles"] == first["compiles"], (first, second)
    assert (second["program_cache_hits"]
            == first.get("program_cache_hits", 0) + 2)
    # per-body compile labels exist (ServeStats' program_counts source)
    assert any(k.startswith("compiles[") for k in second), second


def test_pool_shares_programs_across_algorithm_params():
    """Distinct params are distinct programs; same params share one."""
    reset_default_pool()
    x = jnp.asarray(uniform_keys(T * M, seed=32).reshape(T, M))
    cluster.sort(x, algorithm="smms", r=2)
    sub = default_pool()(T)
    base = sub.stats_snapshot()["compiles"]
    cluster.sort(x, algorithm="smms", r=3)       # new static kwarg -> compile
    assert sub.stats_snapshot()["compiles"] == base + 1
    cluster.sort(x, algorithm="smms", r=3)       # warm now
    assert sub.stats_snapshot()["compiles"] == base + 1


# ---------------------------------------------------------------------------
# donation plumbing
# ---------------------------------------------------------------------------

def test_donation_plumbing_and_gating():
    """donate=True threads donate_argnums through Substrate.run; on
    platforms without donation support it is dropped and counted."""
    x = jnp.asarray(uniform_keys(T * M, seed=33).reshape(T, M))
    sub = VmapSubstrate(T, jit=True)
    (keys, _), rep = cluster.sort(x, algorithm="smms", cap_factor=4.0,
                                  donate=True, substrate=sub)
    assert np.all(np.diff(np.asarray(keys)) >= 0)
    stats = sub.stats_snapshot()
    if jax.default_backend() in DONATION_PLATFORMS:
        assert stats.get("donated_runs", 0) == 1
    else:
        assert stats.get("donated_runs", 0) == 0
        assert stats.get("donation_dropped", 0) == 1
    # retry-capable schedules must NOT donate (the retry re-reads x)
    sub2 = VmapSubstrate(T, jit=True)
    cluster.sort(x, algorithm="smms", donate=True, substrate=sub2)
    s2 = sub2.stats_snapshot()
    assert s2.get("donated_runs", 0) == 0
    assert s2.get("donation_dropped", 0) == 0


# ---------------------------------------------------------------------------
# dispatch-count budget (the CI perf-smoke assertion, unit-sized)
# ---------------------------------------------------------------------------

def test_dispatch_budget_sorts(kernel_path):
    from benchmarks.bench_sort import DISPATCH_BUDGET
    kernel_path("pallas")
    x = jnp.asarray(uniform_keys(T * M, seed=34).reshape(T, M))
    for algorithm in ("smms", "terasort"):
        reset_default_pool()
        ops.reset_dispatch_counts()
        cluster.sort(x, algorithm=algorithm)
        ticks = sum(c for (op, path), c in ops.DISPATCH_COUNTS.items()
                    if path == "pallas")
        assert 0 < ticks <= DISPATCH_BUDGET[algorithm], (
            algorithm, dict(ops.DISPATCH_COUNTS))


# ---------------------------------------------------------------------------
# serving surface: programs-per-query
# ---------------------------------------------------------------------------

def test_serve_stats_program_counts():
    from repro.serve.query import QueryEngine, sort_query
    x1 = uniform_keys(T * M, seed=35).reshape(T, M)
    x2 = uniform_keys(T * M, seed=36).reshape(T, M)
    with QueryEngine(max_pending=8, result_cache_size=0) as eng:
        for x in (x1, x2, x1):
            r = eng.submit(sort_query(jnp.asarray(x),
                                      algorithm="smms")).result(120)
            assert r.ok, r.error
        st = eng.stats()
    assert st.program_counts.get("smms_shard") == 1, st.program_counts
    # one substrate run per executed query — 1.0 programs-per-query warm
    assert st.programs_per_query == 1.0, st
    assert st.compiles == 1
