"""End-to-end kernel-path parity: cluster.sort / cluster.join with the
path pinned to the Pallas kernels vs the jnp reference (the
``kernel_path`` fixture) produce identical outputs AND identical
(alpha, k) reports, on uniform and Zipf-skewed inputs, on both
substrates.

Also fused-vs-round-by-round parity: the default front door now runs
each algorithm's whole multi-round body as ONE compiled program (the
shared jit pool); an explicit eager substrate executes the same body
round by round, op by op.  Both must agree bitwise — outputs AND
AlphaKReports — under VmapSubstrate and 1-device ShardMapSubstrate.
"""
import numpy as np
import jax.numpy as jnp
import pytest

from repro import cluster
from repro.cluster import ShardMapSubstrate, VmapSubstrate
from repro.data import uniform_keys, zipf_tables
from repro.kernels import ops

T, M = 4, 192          # deliberately non-power-of-two row length


def zipf_keys(n: int, seed: int) -> np.ndarray:
    """Skewed float sort keys: many ties, heavy hitters -> duplicate
    Algorithm-1 boundaries (the adversarial case for the bucketize path)."""
    s, _ = zipf_tables(n, 1, theta=0.7, seed=seed, domain=37)
    return s.astype(np.float32)


def assert_reports_equal(a, b):
    assert a.alpha == b.alpha
    np.testing.assert_array_equal(a.workload, b.workload)
    assert a.k_workload == b.k_workload
    assert a.k_network == b.k_network
    assert [p.name for p in a.phases] == [p.name for p in b.phases]
    for pa, pb in zip(a.phases, b.phases):
        np.testing.assert_array_equal(pa.sent, pb.sent)
        np.testing.assert_array_equal(pa.received, pb.received)


def run_sort_both(kernel_path, x, algorithm, substrate_factory, **kw):
    kernel_path("reference")
    (kr, vr), rep_r = cluster.sort(x, algorithm=algorithm,
                                   substrate=substrate_factory(), **kw)
    ops.reset_dispatch_counts()
    kernel_path("pallas")
    (kp, vp), rep_p = cluster.sort(x, algorithm=algorithm,
                                   substrate=substrate_factory(), **kw)
    # the kernel path must actually have run — not silently fallen back
    assert sum(c for (op, path), c in ops.DISPATCH_COUNTS.items()
               if path == "pallas") > 0, dict(ops.DISPATCH_COUNTS)
    return (kr, vr, rep_r), (kp, vp, rep_p)


@pytest.mark.parametrize("algorithm", ["smms", "terasort"])
@pytest.mark.parametrize("gen", ["uniform", "zipf"])
def test_sort_parity_vmap(kernel_path, algorithm, gen):
    if gen == "uniform":
        x = uniform_keys(T * M, seed=11).reshape(T, M)
    else:
        x = zipf_keys(T * M, seed=12).reshape(T, M)
    (kr, _, rep_r), (kp, _, rep_p) = run_sort_both(
        kernel_path, jnp.asarray(x), algorithm, lambda: VmapSubstrate(T))
    np.testing.assert_array_equal(np.asarray(kr), np.asarray(kp))
    assert_reports_equal(rep_r, rep_p)


def test_sort_parity_with_values(kernel_path):
    x = zipf_keys(T * M, seed=3).reshape(T, M)       # ties stress stability
    v = np.arange(T * M, dtype=np.int32).reshape(T, M)
    (kr, vr, rep_r), (kp, vp, rep_p) = run_sort_both(
        kernel_path, jnp.asarray(x), "smms", lambda: VmapSubstrate(T),
        values=jnp.asarray(v))
    np.testing.assert_array_equal(np.asarray(kr), np.asarray(kp))
    np.testing.assert_array_equal(np.asarray(vr), np.asarray(vp))
    assert_reports_equal(rep_r, rep_p)


def test_sort_parity_shardmap_single_device(kernel_path):
    """The mesh executor drives the same kernels (1x1 mesh in-process)."""
    x = uniform_keys(M, seed=7).reshape(1, M)
    (kr, _, rep_r), (kp, _, rep_p) = run_sort_both(
        kernel_path, jnp.asarray(x), "smms", lambda: ShardMapSubstrate(1))
    np.testing.assert_array_equal(np.asarray(kr), np.asarray(kp))
    assert_reports_equal(rep_r, rep_p)


def join_pairs(out):
    s = np.asarray(out.s_rows).reshape(-1)
    t = np.asarray(out.t_rows).reshape(-1)
    v = np.asarray(out.valid).reshape(-1)
    return set(zip(s[v].tolist(), t[v].tolist()))


@pytest.mark.parametrize("theta", [0.2, 0.8])      # mild and heavy skew
def test_join_repartition_parity(kernel_path, theta):
    n, t = 360, 6
    s_keys, t_keys = zipf_tables(n, n, theta=theta, seed=4, domain=60)
    rows = np.arange(n)
    results = {}
    for path in ("reference", "pallas"):
        if path == "pallas":
            ops.reset_dispatch_counts()
        kernel_path(path)
        out, rep = cluster.join(s_keys, rows, t_keys, rows,
                                algorithm="repartition", t_machines=t)
        results[path] = (out, rep)
    assert sum(c for (op, path), c in ops.DISPATCH_COUNTS.items()
               if path == "pallas") > 0
    out_r, rep_r = results["reference"]
    out_p, rep_p = results["pallas"]
    # identical outputs, slot for slot (not just as sets)
    np.testing.assert_array_equal(np.asarray(out_r.s_rows),
                                  np.asarray(out_p.s_rows))
    np.testing.assert_array_equal(np.asarray(out_r.t_rows),
                                  np.asarray(out_p.t_rows))
    np.testing.assert_array_equal(np.asarray(out_r.valid),
                                  np.asarray(out_p.valid))
    assert join_pairs(out_r) == join_pairs(out_p)
    assert_reports_equal(rep_r, rep_p)


def test_join_repartition_parity_shardmap_single_device(kernel_path):
    n = 150
    s_keys, t_keys = zipf_tables(n, n, theta=0.4, seed=8, domain=30)
    rows = np.arange(n)
    outs = []
    for path in ("reference", "pallas"):
        kernel_path(path)
        out, _ = cluster.join(s_keys, rows, t_keys, rows,
                              algorithm="repartition", t_machines=1,
                              substrate=ShardMapSubstrate(1))
        outs.append(out)
    np.testing.assert_array_equal(np.asarray(outs[0].s_rows),
                                  np.asarray(outs[1].s_rows))
    np.testing.assert_array_equal(np.asarray(outs[0].t_rows),
                                  np.asarray(outs[1].t_rows))
    assert join_pairs(outs[0]) == join_pairs(outs[1])


def test_join_statjoin_and_randjoin_parity(kernel_path):
    """The other two algorithms route localjoin/randjoin kernels too."""
    n, t = 240, 4
    s_keys, t_keys = zipf_tables(n, n, theta=0.5, seed=13, domain=40)
    rows = np.arange(n)
    for alg in ("statjoin", "randjoin"):
        got = []
        for path in ("reference", "pallas"):
            kernel_path(path)
            out, _ = cluster.join(s_keys, rows, t_keys, rows, algorithm=alg,
                                  t_machines=t)
            got.append(out)
        np.testing.assert_array_equal(np.asarray(got[0].s_rows),
                                      np.asarray(got[1].s_rows))
        np.testing.assert_array_equal(np.asarray(got[0].t_rows),
                                      np.asarray(got[1].t_rows))
        np.testing.assert_array_equal(np.asarray(got[0].valid),
                                      np.asarray(got[1].valid))


# ---------------------------------------------------------------------------
# Fused (one compiled program) vs round-by-round (eager) execution
# ---------------------------------------------------------------------------

def run_sort_fused_and_eager(x, algorithm, fused_factory, eager_factory, **kw):
    (kf, vf), rep_f = cluster.sort(x, algorithm=algorithm,
                                   substrate=fused_factory(), **kw)
    (ke, ve), rep_e = cluster.sort(x, algorithm=algorithm,
                                   substrate=eager_factory(), **kw)
    return (kf, vf, rep_f), (ke, ve, rep_e)


@pytest.mark.parametrize("algorithm", ["smms", "terasort"])
@pytest.mark.parametrize("path", ["reference", "pallas"])
def test_fused_vs_rounds_vmap(kernel_path, algorithm, path):
    """jit-compiled single program == eager round-by-round, bitwise."""
    kernel_path(path)
    x = jnp.asarray(zipf_keys(T * M, seed=21).reshape(T, M))
    (kf, _, rep_f), (ke, _, rep_e) = run_sort_fused_and_eager(
        x, algorithm,
        lambda: VmapSubstrate(T, jit=True), lambda: VmapSubstrate(T))
    np.testing.assert_array_equal(np.asarray(kf), np.asarray(ke))
    assert_reports_equal(rep_f, rep_e)


def test_fused_vs_rounds_with_values(kernel_path):
    kernel_path("pallas")
    x = jnp.asarray(zipf_keys(T * M, seed=22).reshape(T, M))
    v = jnp.asarray(np.arange(T * M, dtype=np.int32).reshape(T, M))
    (kf, vf, rep_f), (ke, ve, rep_e) = run_sort_fused_and_eager(
        x, "smms",
        lambda: VmapSubstrate(T, jit=True), lambda: VmapSubstrate(T),
        values=v)
    np.testing.assert_array_equal(np.asarray(kf), np.asarray(ke))
    np.testing.assert_array_equal(np.asarray(vf), np.asarray(ve))
    assert_reports_equal(rep_f, rep_e)


@pytest.mark.parametrize("algorithm", ["smms", "terasort"])
def test_fused_vs_rounds_shardmap_single_device(kernel_path, algorithm):
    kernel_path("pallas")
    x = jnp.asarray(uniform_keys(M, seed=23).reshape(1, M))
    (kf, _, rep_f), (ke, _, rep_e) = run_sort_fused_and_eager(
        x, algorithm,
        lambda: ShardMapSubstrate(1),             # jit=True default
        lambda: ShardMapSubstrate(1, jit=False))
    np.testing.assert_array_equal(np.asarray(kf), np.asarray(ke))
    assert_reports_equal(rep_f, rep_e)


def test_fused_vs_rounds_join(kernel_path):
    """The joins fuse too: pooled-jit output == eager output + report."""
    kernel_path("pallas")
    n, t = 240, 4
    s_keys, t_keys = zipf_tables(n, n, theta=0.6, seed=24, domain=40)
    rows = np.arange(n)
    outs, reps = [], []
    for sub in (VmapSubstrate(t, jit=True), VmapSubstrate(t)):
        out, rep = cluster.join(s_keys, rows, t_keys, rows,
                                algorithm="statjoin", t_machines=t,
                                substrate=sub)
        outs.append(out)
        reps.append(rep)
    np.testing.assert_array_equal(np.asarray(outs[0].s_rows),
                                  np.asarray(outs[1].s_rows))
    np.testing.assert_array_equal(np.asarray(outs[0].t_rows),
                                  np.asarray(outs[1].t_rows))
    np.testing.assert_array_equal(np.asarray(outs[0].valid),
                                  np.asarray(outs[1].valid))
    assert_reports_equal(reps[0], reps[1])


def test_front_door_default_is_fused():
    """substrate=None resolves to the shared jit pool: a repeated query
    reuses ONE compiled program (no recompile, a program-cache hit)."""
    from repro.cluster import default_pool, reset_default_pool
    reset_default_pool()
    x = jnp.asarray(uniform_keys(T * M, seed=25).reshape(T, M))
    cluster.sort(x, algorithm="smms")
    sub = default_pool()(T)
    first = sub.stats_snapshot()
    cluster.sort(x, algorithm="smms")
    second = sub.stats_snapshot()
    assert first["compiles"] >= 1
    assert second["compiles"] == first["compiles"]
    assert second["program_cache_hits"] > first.get("program_cache_hits", 0)


def test_kernel_path_keys_the_program(kernel_path):
    """The resolved kernel path is part of the compiled-program key: on
    one default pool, pinning the other path compiles a second program,
    and pinning the first again is a cache hit — with outputs and
    reports bitwise equal across the three calls."""
    from repro.cluster import default_pool
    x = jnp.asarray(uniform_keys(T * M, seed=26).reshape(T, M))
    v = jnp.asarray(np.arange(T * M, dtype=np.int32).reshape(T, M))
    sub = default_pool()(T)
    runs, stats = [], []
    for path in ("reference", "pallas", "reference"):
        kernel_path(path)
        runs.append(cluster.sort(x, algorithm="smms", values=v))
        stats.append(sub.stats_snapshot())
    ((k0, v0), rep0) = runs[0]
    for (k, vv), rep in runs[1:]:
        np.testing.assert_array_equal(np.asarray(k), np.asarray(k0))
        np.testing.assert_array_equal(np.asarray(vv), np.asarray(v0))
        assert_reports_equal(rep, rep0)
    assert stats[0]["compiles"] == 1
    assert stats[1]["compiles"] == 2
    assert stats[2]["compiles"] == 2
    assert (stats[2].get("program_cache_hits", 0)
            == stats[1].get("program_cache_hits", 0) + 1)
