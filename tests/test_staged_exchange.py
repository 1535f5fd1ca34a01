"""Staged (2-level) exchange: parity with the flat path, pinned bitwise.

The staged exchange factors the shard axis t = t1*t2 and replaces the
one t-way all_to_all with two sqrt(t)-way hops (AMS-style).  Everything
here checks the same invariant from different angles: the staged path
must produce *bitwise* the keys the flat path produces, its AlphaKReport
must agree on workload/k_workload, and the only sanctioned differences
are the extra tape phase (alpha = flat + 1) and the per-stage network
counters.  Planner coverage pins the topology decision rule; the kernel
test pins the double-buffered (blocked-bound) rank-merge variant against
the monolithic one.
"""
import warnings

import numpy as np
import jax.numpy as jnp
import pytest

from repro import cluster
from repro.cluster import ShardMapSubstrate, VmapSubstrate
from repro.core import smms_sort, terasort_sort
from repro.core.smms import resolve_exchange_topology
from repro.data import lidar_like, uniform_keys
from repro.kernels import fused
from repro.launch.mesh import STAGED_AXIS_NAMES, factor_shards
from repro.planner import choose_exchange, exchange_costs


def zipf_keys(n: int, seed: int = 0, domain: int = 97,
              theta: float = 1.2) -> np.ndarray:
    """Heavy-duplicate Zipf keys — stresses tie handling in the merges."""
    ranks = np.arange(1, domain + 1, dtype=np.float64)
    p = ranks ** -theta
    p /= p.sum()
    g = np.random.default_rng(seed)
    return g.choice(domain, size=n, p=p).astype(np.float32)


# ----------------------------------------------------------------------
# factorization helper
# ----------------------------------------------------------------------
def test_factor_shards_powers_of_two():
    assert factor_shards(4) == (2, 2)
    assert factor_shards(8) == (4, 2)
    assert factor_shards(16) == (4, 4)
    assert factor_shards(64) == (8, 8)
    assert factor_shards(256) == (16, 16)
    for t in (4, 8, 16, 64, 256):
        t1, t2 = factor_shards(t)
        assert t1 * t2 == t and t1 >= t2 >= 2


@pytest.mark.parametrize("t", [1, 2, 3, 6, 12, 100])
def test_factor_shards_rejects_small_and_non_pow2(t):
    assert factor_shards(t) is None
    with pytest.warns(UserWarning, match="flat"):
        assert factor_shards(t, warn=True) is None


# ----------------------------------------------------------------------
# tape primitive: pure relay == flat all_to_all, reassembled source-major
# ----------------------------------------------------------------------
def _flat_body(buf, tape=None):
    with tape.phase("shuffle"):
        return tape.all_to_all(buf, "i")


def _relay_body(buf, chunks, tape=None):
    outs, _ = tape.staged_all_to_all(buf, STAGED_AXIS_NAMES, chunks=chunks)
    return jnp.concatenate([ok for ok, _ in outs], axis=1)


@pytest.mark.parametrize("chunks", [1, 2])
def test_staged_relay_matches_flat_all_to_all(chunks, rng):
    t1, t2, c = 2, 2, 4
    t = t1 * t2
    blocks = rng.normal(size=(t, t1, t2, c)).astype(np.float32)

    flat_sub = VmapSubstrate(("i", t))
    flat_out, _ = flat_sub.run(_flat_body,
                               jnp.asarray(blocks.reshape(t, t, c)))
    flat_out = np.asarray(flat_out)            # (t, t, c): [dest, source]

    import functools
    staged_sub = VmapSubstrate((STAGED_AXIS_NAMES[0], t1),
                               (STAGED_AXIS_NAMES[1], t2))
    staged_out, tape = staged_sub.run(
        functools.partial(_relay_body, chunks=chunks),
        jnp.asarray(blocks.reshape(t1, t2, t1, t2, c)))
    # per machine the landing is (t2, t1*c); reassemble source-major
    landed = np.asarray(staged_out).reshape(t1, t2, t2, t1, c)
    landed = landed.swapaxes(2, 3).reshape(t, t, c)
    np.testing.assert_array_equal(landed, flat_out)
    names = [p.name for p in tape.phases(t)]
    assert names == ["shuffle s1", "shuffle s2"]


# ----------------------------------------------------------------------
# end-to-end parity: outputs AND reports, uniform + Zipf, both algorithms
# ----------------------------------------------------------------------
def _assert_reports_match(rep_flat, rep_staged, *, prefix):
    """Flat vs staged report parity: everything except the extra phase."""
    assert rep_flat.exchange_topology == "flat"
    assert rep_staged.exchange_topology == "staged"
    assert rep_staged.alpha == rep_flat.alpha + 1
    np.testing.assert_array_equal(rep_flat.workload, rep_staged.workload)
    assert rep_flat.k_workload == rep_staged.k_workload
    names = [p.name for p in rep_staged.phases]
    assert f"{prefix} s1" in names and f"{prefix} s2" in names
    assert not any(p.name == prefix for p in rep_staged.phases), (
        "the flat shuffle phase must not also appear on the staged tape")


@pytest.mark.parametrize("gen", [uniform_keys, lidar_like, zipf_keys])
@pytest.mark.parametrize("t", [8, 16])
def test_smms_staged_output_and_report_parity(gen, t):
    m = 512
    x = jnp.asarray(gen(t * m, seed=t).reshape(t, m))
    (kf, _), rf = smms_sort(x, r=2, exchange="flat")
    (ks, _), rs = smms_sort(x, r=2, exchange="staged")
    np.testing.assert_array_equal(np.asarray(kf), np.asarray(ks))
    np.testing.assert_array_equal(np.sort(np.asarray(x).ravel()),
                                  np.asarray(ks))
    _assert_reports_match(rf, rs, prefix="round3 shuffle")


@pytest.mark.parametrize("gen", [uniform_keys, zipf_keys])
def test_terasort_staged_output_and_report_parity(gen):
    t, m = 8, 512
    x = jnp.asarray(gen(t * m, seed=3).reshape(t, m))
    kf, rf = terasort_sort(x, seed=1, exchange="flat")
    ks, rs = terasort_sort(x, seed=1, exchange="staged")
    np.testing.assert_array_equal(np.asarray(kf), np.asarray(ks))
    _assert_reports_match(rf, rs, prefix="round3 shuffle")


@pytest.mark.parametrize("sorter", [smms_sort, terasort_sort])
@pytest.mark.parametrize("t,chunks,tied", [(8, 2, False), (4, 2, True),
                                           (4, 1, True), (8, 4, True)])
def test_staged_carries_values(sorter, t, chunks, tied, rng):
    """kv parity with the flat path and the stable global argsort.  The
    tied cases draw 2^14 keys from 1024 values, ~16 copies of each
    spread over all machines, so values of equal keys must leave in
    source-machine order, not in the order of the hops."""
    m = (1 << 14) // t
    keys = (rng.integers(0, 1024, size=(t, m)) if tied
            else rng.permutation(t * m).reshape(t, m)).astype(np.float32)
    vals = np.arange(t * m, dtype=np.int32).reshape(t, m)
    (kf, vf), _ = sorter(jnp.asarray(keys), values=jnp.asarray(vals),
                         exchange="flat")
    (ks, vs), _ = sorter(jnp.asarray(keys), values=jnp.asarray(vals),
                         exchange="staged", overlap_chunks=chunks)
    np.testing.assert_array_equal(np.asarray(kf), np.asarray(ks))
    np.testing.assert_array_equal(np.asarray(vf), np.asarray(vs))
    order = np.argsort(keys.reshape(-1), kind="stable")
    np.testing.assert_array_equal(np.asarray(vs), vals.reshape(-1)[order])


@pytest.mark.parametrize("with_values", [False, True])
def test_staged_pallas_matches_reference(with_values, kernel_path):
    t, m = 8, 512
    if with_values:        # tied keys: the (key, origin) re-order runs
        x = jnp.asarray(zipf_keys(t * m, seed=7).reshape(t, m))
        v = jnp.arange(t * m, dtype=jnp.int32).reshape(t, m)
    else:
        x = jnp.asarray(uniform_keys(t * m, seed=7).reshape(t, m))
        v = None
    kernel_path("reference")
    (k_ref, v_ref), _ = smms_sort(x, r=2, values=v, exchange="staged")
    kernel_path("pallas")
    (k_pal, v_pal), _ = smms_sort(x, r=2, values=v, exchange="staged")
    np.testing.assert_array_equal(np.asarray(k_ref), np.asarray(k_pal))
    if with_values:
        np.testing.assert_array_equal(np.asarray(v_ref), np.asarray(v_pal))


@pytest.mark.parametrize("chunks", [2, 4])
def test_overlap_chunk_count_is_output_invariant(chunks):
    t, m = 8, 512
    x = jnp.asarray(lidar_like(t * m, seed=5).reshape(t, m))
    (k2, _), _ = smms_sort(x, r=2, exchange="staged", overlap_chunks=2)
    (kc, _), rc = smms_sort(x, r=2, exchange="staged",
                            overlap_chunks=chunks)
    np.testing.assert_array_equal(np.asarray(k2), np.asarray(kc))
    assert rc.exchange_topology == "staged"


# ----------------------------------------------------------------------
# fallbacks: non-factorable t and single-axis substrates warn, stay flat
# ----------------------------------------------------------------------
def test_non_pow2_t_falls_back_to_flat():
    t, m = 6, 256
    x = jnp.asarray(uniform_keys(t * m, seed=4).reshape(t, m))
    with pytest.warns(UserWarning, match="flat"):
        (ks, _), rs = smms_sort(x, r=2, exchange="staged")
    assert rs.exchange_topology == "flat"
    assert rs.alpha == 3
    np.testing.assert_array_equal(np.sort(np.asarray(x).ravel()),
                                  np.asarray(ks))


def test_explicit_single_axis_substrate_falls_back():
    t = 8
    with pytest.warns(UserWarning, match="flat"):
        sub, shape = resolve_exchange_topology(
            VmapSubstrate(t), t, exchange="staged")
    assert shape is None


def test_two_axis_substrate_is_always_staged():
    sub = VmapSubstrate((STAGED_AXIS_NAMES[0], 4), (STAGED_AXIS_NAMES[1], 2))
    out, shape = resolve_exchange_topology(sub, 8, exchange="flat")
    assert shape == (4, 2) and out is sub


def test_one_device_shardmap_staged_request():
    """t=1 ShardMap: staged degrades to flat (warned), output still exact."""
    x = jnp.asarray(uniform_keys(64, seed=8).reshape(1, 64))
    with pytest.warns(UserWarning, match="flat"):
        (ks, _), rs = cluster.sort(x, substrate=ShardMapSubstrate(1),
                                   exchange="staged")
    assert rs.exchange_topology == "flat"
    np.testing.assert_array_equal(np.sort(np.asarray(x).ravel()),
                                  np.asarray(ks))


# ----------------------------------------------------------------------
# front door + planner: exchange="staged"/"auto" through cluster.sort
# ----------------------------------------------------------------------
def test_cluster_sort_staged_resolves_pooled_substrate():
    t, m = 16, 256
    x = jnp.asarray(uniform_keys(t * m, seed=6).reshape(t, m))
    (kf, _), _ = cluster.sort(x, exchange="flat")
    (ks, _), rs = cluster.sort(x, exchange="staged")
    np.testing.assert_array_equal(np.asarray(kf), np.asarray(ks))
    assert rs.exchange_topology == "staged"


def test_choose_exchange_decision_points():
    topo_small, costs_small = choose_exchange(8, 1024)
    assert topo_small == "flat"
    topo_big, costs_big = choose_exchange(256, 512)
    assert topo_big == "staged"
    assert costs_big["staged"]["peak_receive_objects"] < \
        costs_big["flat"]["peak_receive_objects"]
    for costs in (costs_small, costs_big):
        assert costs["flat"]["alpha_exchange"] == 1
    assert costs_big["staged"]["alpha_exchange"] == 2
    # non-factorable t never offers a staged candidate
    assert "staged" not in exchange_costs(6, 1024, cap_factor=2.0)


def test_auto_exchange_attaches_plan():
    t, m = 8, 512
    x = jnp.asarray(uniform_keys(t * m, seed=2).reshape(t, m))
    (ka, _), ra = cluster.sort(x, algorithm="auto", exchange="auto")
    plan = ra.query_plan
    assert plan.exchange in ("flat", "staged")
    assert "flat" in plan.exchange_costs
    assert ra.exchange_topology == plan.exchange
    (ke, _), _ = cluster.sort(x, algorithm=plan.algorithm,
                              exchange=plan.exchange)
    np.testing.assert_array_equal(np.asarray(ka), np.asarray(ke))


# ----------------------------------------------------------------------
# kernel: double-buffered (blocked-bound) rank merge is bitwise identical
# ----------------------------------------------------------------------
@pytest.mark.parametrize("t,c", [(4, 256), (3, 100), (8, 512)])
@pytest.mark.parametrize("bound_block", [64, 100, 1024])
def test_blocked_rank_merge_bitwise(t, c, bound_block, rng):
    keys = np.sort(rng.normal(size=(t, c)).astype(np.float32), axis=1)
    ids = np.broadcast_to(np.arange(t)[:, None], (t, c)).astype(np.int32)
    base = fused.merge_ranks(jnp.asarray(keys), jnp.asarray(ids))
    blocked = fused.merge_ranks(jnp.asarray(keys), jnp.asarray(ids),
                                bound_block=bound_block)
    np.testing.assert_array_equal(np.asarray(base), np.asarray(blocked))
