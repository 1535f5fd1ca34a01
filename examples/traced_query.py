"""End-to-end tracing walkthrough: one warm query, one span tree.

Submits a sort query through ``QueryEngine`` with the span tracer
enabled, prints the request's span tree (planner -> substrate ->
collective phases -> kernel dispatches), reconciles the phase leaves
against the same execution's (alpha, k) report, shows the engine's
histogram-backed ServeStats, and dumps the trace as Chrome-trace JSON
(open in chrome://tracing or https://ui.perfetto.dev).

    PYTHONPATH=src python examples/traced_query.py
"""
import numpy as np
import jax.numpy as jnp


def main():
    from repro.cluster import SubstratePool
    from repro.data import uniform_keys
    from repro.obs import Tracer, write_chrome_trace
    from repro.serve import QueryEngine, sort_query
    from repro.serve.query import run_spec

    t, m = 8, 512
    x = jnp.asarray(uniform_keys(t * m, seed=5).reshape(t, m))
    spec = sort_query(x, algorithm="auto")   # auto => planner spans too

    pool = SubstratePool()
    run_spec(spec, substrate=pool)           # warm compile + plan caches
    tracer = Tracer(enabled=True)
    with QueryEngine(pool=pool, tracer=tracer) as eng:
        res = eng.run([spec])[0]
    assert res.ok, res.error

    print("== span tree ==")
    print(res.trace.tree_str())

    print("== phase spans vs the (alpha, k) report ==")
    spans = {s.name: s for s in res.trace.walk()
             if s.name.startswith("phase:")}
    for ph in res.report.phases:
        sp = spans[f"phase:{ph.name}"]
        ok = (np.array_equal(np.asarray(sp.attrs["sent"]),
                             np.asarray(ph.sent))
              and np.array_equal(np.asarray(sp.attrs["received"]),
                                 np.asarray(ph.received)))
        print(f"  {ph.name:24s} recv/machine={np.asarray(ph.received)}"
              f"  span==report: {ok}")
        assert ok

    st = eng.stats()
    print("== ServeStats (histogram-backed percentiles) ==")
    print(f"  served={st.served} executed={st.executed} "
          f"p50={st.p50_latency_s * 1e3:.1f}ms "
          f"p99={st.p99_latency_s * 1e3:.1f}ms")

    out = "TRACE_example.json"
    write_chrome_trace(out, [res.trace])
    print(f"Chrome trace written to {out} "
          "(load in chrome://tracing or ui.perfetto.dev)")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
