"""Observability contracts: spans reconcile bitwise with the taped
report, the metrics registry survives thread stress, disabled tracing
records nothing, and the exporters/timers behave.

The load-bearing invariant is span-vs-report consistency: the phase
leaves a traced execution hangs under ``substrate.run`` are built from
the SAME ``bound_snapshot`` the ``AlphaKReport`` is, so every
per-machine sent/received count must match bitwise — any divergence
means the trace is lying about what the cluster moved.
"""
import json
import threading

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro import cluster, obs
from repro.cluster.substrate import SubstratePool, reset_default_pool
from repro.configs.base import MoEConfig
from repro.kernels import ops
from repro.models.moe import init_moe
from repro.obs import (Histogram, MetricsRegistry, Tracer, chrome_trace,
                       timeit, write_chrome_trace)
from repro.serve import QueryEngine, sort_query
from repro.serve.query import run_spec

BACKENDS = ["reference", "pallas"]


# ---------------------------------------------------------------------------
# span-vs-report bitwise consistency
# ---------------------------------------------------------------------------

def _phase_groups(root):
    """Phase leaves grouped per ``substrate.run`` span, execution order."""
    return [[c for c in s.children if c.name.startswith("phase:")]
            for s in root.walk() if s.name == "substrate.run"]


def _group_matches(group, phases) -> bool:
    if [c.name for c in group] != [f"phase:{p.name}" for p in phases]:
        return False
    return all(
        np.array_equal(np.asarray(c.attrs["sent"]), np.asarray(p.sent))
        and np.array_equal(np.asarray(c.attrs["received"]),
                           np.asarray(p.received))
        for c, p in zip(group, phases))


def assert_span_report_bitwise(root, report):
    groups = [g for g in _phase_groups(root) if g]
    assert groups, root.tree_str()
    assert any(_group_matches(g, report.phases) for g in groups), (
        root.tree_str(), [p.name for p in report.phases])


@pytest.mark.parametrize("kernel_backend", BACKENDS)
@pytest.mark.parametrize("algorithm", ["smms", "terasort"])
def test_sort_span_report_bitwise(algorithm, kernel_backend, rng):
    t, m = 4, 64
    x = jnp.asarray(rng.normal(size=(t, m)).astype(np.float32))
    tracer = Tracer(enabled=True)
    kw = {"seed": 3} if algorithm == "terasort" else {}
    with tracer.trace("q") as root:
        _, report = cluster.sort(x, algorithm=algorithm,
                                 substrate=SubstratePool(),
                                 kernel_backend=kernel_backend, **kw)
    assert_span_report_bitwise(root, report)
    # the dispatch decisions the cold trace made are on the span tree
    dispatch = [e for s in root.walk() for e in s.events
                if e.name == "kernel_dispatch"]
    assert dispatch and all(
        e.attrs["path"] == kernel_backend for e in dispatch)


@pytest.mark.parametrize("kernel_backend", BACKENDS)
def test_moe_span_report_bitwise(kernel_backend):
    d, e, tokens = 16, 4, 128
    cfg = MoEConfig(num_experts=e, top_k=2, d_ff_expert=8, extra_slots=4)
    params = init_moe(jax.random.key(0), d, cfg, jnp.float32)
    x = jnp.asarray(np.random.default_rng(2).normal(size=(tokens, d)),
                    jnp.float32)
    tracer = Tracer(enabled=True)
    with tracer.trace("q") as root:
        _, report = cluster.moe_dispatch(params, x, cfg, mode="cluster",
                                         t_machines=4,
                                         substrate=SubstratePool(),
                                         kernel_backend=kernel_backend)
    assert_span_report_bitwise(root, report)


def test_engine_trace_tree(rng):
    """One warm engine.submit: root query span, substrate child, phase
    leaves bitwise-equal to the result's own report; LRU repeats carry
    no trace (nothing executed)."""
    t, m = 4, 64
    x = jnp.asarray(rng.normal(size=(t, m)).astype(np.float32))
    spec = sort_query(x, algorithm="smms")
    pool = SubstratePool()
    run_spec(spec, substrate=pool)                   # warm caches
    tracer = Tracer(enabled=True)
    with QueryEngine(pool=pool, tracer=tracer) as eng:
        res = eng.run([spec])[0]
        rep = eng.run([spec])[0]                     # result-LRU hit
    assert res.ok and res.trace is not None
    assert res.trace.name == "query"
    assert res.trace_id == res.trace.trace_id
    assert res.trace.attrs["kind"] == "sort"
    assert_span_report_bitwise(res.trace, res.report)
    # warm engine: the program came from the cache, not a compile
    runs = [s for s in res.trace.walk() if s.name == "substrate.run"]
    assert runs and all(
        any(e.name == "program_cache_hit" for e in s.events)
        for s in runs)
    assert rep.cached and rep.trace is None and rep.trace_id is None
    # recorded on the tracer too, newest last
    assert tracer.last() is res.trace


def test_tracing_disabled_records_nothing(rng):
    t, m = 4, 64
    x = jnp.asarray(rng.normal(size=(t, m)).astype(np.float32))
    spec = sort_query(x, algorithm="smms")
    tracer = Tracer(enabled=False)
    with QueryEngine(pool=SubstratePool(), tracer=tracer,
                     result_cache_size=0) as eng:
        res = eng.run([spec])[0]
    assert res.ok
    assert res.trace is None and res.trace_id is None
    assert not tracer.traces and tracer.last() is None
    # module-level span()/event() outside any trace are no-ops
    with obs.span("orphan") as sp:
        obs.event("ignored")
        assert sp is None
    assert obs.current() is None


# ---------------------------------------------------------------------------
# the profiler trace
# ---------------------------------------------------------------------------

JOIN_SPANS = ("statjoin.statistics", "statjoin.plan", "statjoin.route",
              "substrate.compile", "substrate.run")
SORT_SPANS = ("capacity.attempt", "substrate.compile", "substrate.run",
              "smms.fetch", "smms.compact")


def _host_spans(logdir) -> dict:
    """{name: [(start_ns, end_ns), ...]} of the host planes of the one
    ``.xplane.pb`` a profiler session wrote under ``logdir``."""
    from jax.profiler import ProfileData
    path, = logdir.rglob("*.xplane.pb")
    out = {}
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    out.setdefault(e.name, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns))
    return out


def _join_call(rng):
    s_keys = rng.zipf(1.5, size=512).astype(np.int32) % 64
    t_keys = rng.permutation(64).astype(np.int32)
    out, _ = cluster.join(s_keys, np.arange(512, dtype=np.int32), t_keys,
                          np.arange(64, dtype=np.int32),
                          algorithm="statjoin", t_machines=4)
    jax.block_until_ready(out)


def _sort_call(rng):
    x = jnp.asarray(rng.normal(size=(4, 256)).astype(np.float32))
    cluster.sort(x, algorithm="smms", values=jnp.arange(1024).reshape(4, 256))


@pytest.mark.parametrize("call,names", [(_join_call, JOIN_SPANS),
                                        (_sort_call, SORT_SPANS)],
                         ids=["statjoin", "smms"])
def test_program_spans_reach_the_profiler_trace(call, names, rng, tmp_path):
    """With no obs root open, each layer's span still lands on a host
    plane of the profiler's trace, inside the call's interval."""
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("test.call"):
            call(rng)
    spans = _host_spans(tmp_path)
    (c0, c1), = spans["test.call"]
    for name in names:
        assert name in spans, (name, sorted(spans))
        for a, b in spans[name]:
            assert c0 <= a <= b <= c1, name
    if "statjoin.plan" in names:
        (s0, s1), = spans["statjoin.statistics"]
        (p0, p1), = spans["statjoin.plan"]
        (r0, r1), = spans["statjoin.route"]
        assert s1 <= p0 and p1 <= r0
        (run0, _), = spans["substrate.run"]
        assert r1 <= run0
    else:
        (f0, f1), = spans["smms.fetch"]
        (k0, _), = spans["smms.compact"]
        (_, a1), = spans["capacity.attempt"]
        assert a1 <= f0 and f1 <= k0
    # post-hoc children and instant events stay out of it
    assert not any(n.startswith("phase:") for n in spans)


def test_spans_without_root_or_profiler_build_no_tree(rng):
    tracer = obs.get_tracer()
    assert not tracer.enabled
    with obs.span("statjoin.plan") as sp:
        assert sp is None and obs.current() is None
    with Tracer(enabled=False).trace("query") as root:
        assert root is None
    _join_call(rng)
    _sort_call(rng)
    assert not tracer.traces and tracer.last() is None
    assert obs.current() is None


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_registry_thread_safety():
    """The test_serve N-thread stress pattern, aimed at the registry:
    interleaved counter incs + histogram observes from 8 threads must
    lose nothing."""
    reg = MetricsRegistry()
    n_threads, per_thread = 8, 500
    errors = []

    def worker(k):
        try:
            for i in range(per_thread):
                reg.counter("stress_total", thread=str(k)).inc()
                reg.counter("stress_total_all").inc()
                reg.histogram("stress_seconds").observe(1e-4 * (i + 1))
        except Exception as exc:  # pragma: no cover - surfaced below
            errors.append(exc)

    threads = [threading.Thread(target=worker, args=(k,))
               for k in range(n_threads)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert not errors
    assert reg.counter_value("stress_total_all") == n_threads * per_thread
    for k in range(n_threads):
        assert reg.counter_value("stress_total",
                                 thread=str(k)) == per_thread
    h = reg.histogram("stress_seconds")
    assert h.count == n_threads * per_thread
    assert h.quantile(0.5) <= h.quantile(0.99)


def test_histogram_quantiles():
    h = Histogram()
    for _ in range(50):
        h.observe(1e-3)
    for _ in range(50):
        h.observe(0.1)
    assert h.count == 100
    assert h.min == pytest.approx(1e-3) and h.max == pytest.approx(0.1)
    assert abs(h.mean - 0.0505) < 1e-6
    # quantiles are bucket-interpolated: exactness is not promised,
    # but ordering, clamping and bucket placement are
    assert h.quantile(0.0) == pytest.approx(h.min)
    assert h.quantile(1.0) == pytest.approx(h.max)
    q50, q99 = h.quantile(0.5), h.quantile(0.99)
    assert h.min <= q50 <= q99 <= h.max
    assert q50 <= 2e-3          # p50 sits in the low mode's bucket
    assert q99 >= 0.05          # p99 in the high mode's
    empty = Histogram()
    assert empty.quantile(0.5) == 0.0 and empty.count == 0


def test_registry_export_roundtrip():
    reg = MetricsRegistry()
    reg.counter("ticks_total", op="sort").inc(3)
    reg.histogram("lat_seconds").observe(0.01)
    doc = json.loads(json.dumps(reg.to_json()))
    assert doc  # serializable, non-empty
    text = reg.to_prometheus_text()
    assert "ticks_total" in text and 'op="sort"' in text
    assert "lat_seconds_bucket" in text and 'le="+Inf"' in text
    reg.reset()
    assert reg.counter_value("ticks_total", op="sort") == 0


# ---------------------------------------------------------------------------
# serve stats percentiles (streaming histogram, not a latency deque)
# ---------------------------------------------------------------------------

def test_serve_stats_percentiles(rng):
    t, m = 4, 64
    specs = [sort_query(jnp.asarray(rng.normal(size=(t, m))
                                    .astype(np.float32)),
                        algorithm="smms") for _ in range(6)]
    with QueryEngine(pool=SubstratePool()) as eng:
        results = eng.run(specs)
    assert all(r.ok for r in results)
    st = eng.stats()
    assert st.served == len(specs)
    assert 0.0 < st.p50_latency_s <= st.p99_latency_s
    # the histogram brackets every observed latency
    lats = [r.latency_s for r in results]
    assert st.p99_latency_s <= max(lats) * 1.5 + 1e-3


# ---------------------------------------------------------------------------
# execution-time dispatch counts (satellite: DISPATCH_COUNTS semantics)
# ---------------------------------------------------------------------------

def test_exec_counts_tick_per_execution(rng):
    """DISPATCH_COUNTS ticks per trace; kernel_dispatch_execs_total per
    execution — a warm re-run moves only the latter."""
    t, m = 4, 64
    x = jnp.asarray(rng.normal(size=(t, m)).astype(np.float32))
    pool = SubstratePool()
    ops.enable_exec_counts(True)
    try:
        cluster.sort(x, algorithm="smms", substrate=pool,
                     kernel_backend="reference")
        traces_cold = dict(ops.DISPATCH_COUNTS)
        execs_cold = ops.exec_dispatch_counts()
        assert traces_cold and execs_cold == traces_cold
        cluster.sort(x, algorithm="smms", substrate=pool,
                     kernel_backend="reference")     # warm: no re-trace
        assert dict(ops.DISPATCH_COUNTS) == traces_cold
        execs_warm = ops.exec_dispatch_counts()
        assert execs_warm == {k: 2 * v for k, v in traces_cold.items()}
    finally:
        ops.enable_exec_counts(False)
        reset_default_pool()


# ---------------------------------------------------------------------------
# exporters + timeit
# ---------------------------------------------------------------------------

def test_chrome_trace_export(tmp_path, rng):
    t, m = 4, 64
    x = jnp.asarray(rng.normal(size=(t, m)).astype(np.float32))
    tracer = Tracer(enabled=True)
    with tracer.trace("q") as root:
        cluster.sort(x, algorithm="smms", substrate=SubstratePool())
    doc = chrome_trace([root])
    events = doc["traceEvents"]
    assert events
    kinds = {e["ph"] for e in events}
    assert "X" in kinds and "M" in kinds          # spans + metadata
    for e in events:
        if e["ph"] == "X":
            assert e["dur"] >= 0 and isinstance(e["ts"], (int, float))
    names = {e["name"] for e in events if e["ph"] == "X"}
    assert "q" in names and "substrate.run" in names
    # numpy attrs (phase byte vectors) must serialize
    path = tmp_path / "trace.json"
    write_chrome_trace(str(path), [root])
    loaded = json.loads(path.read_text())
    assert loaded["traceEvents"]


def test_timeit_counts_and_setup():
    calls, setups = [], []
    res = timeit(lambda: calls.append(1) or len(calls),
                 reps=3, warmup=2, setup=lambda: setups.append(1))
    assert len(calls) == 5                 # 2 warmup + 3 timed
    assert len(setups) == 3                # once per timed rep only
    assert res.reps == 3 and res.warmup == 2
    assert res.last_result == 5
    assert len(res.times_s) == 3
    assert 0.0 <= res.best_s <= res.mean_s
    assert res.best_us == pytest.approx(res.best_s * 1e6)
    with pytest.raises(ValueError):
        timeit(lambda: None, reps=0)
