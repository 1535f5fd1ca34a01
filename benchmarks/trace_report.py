"""Traced-query report: one warm ``engine.submit`` rendered as a span
tree, with the exchange phases joined against the roofline model.

Two suites:

* :func:`run` — a warm engine executes one traced SMMS sort per exchange
  topology (flat and staged).  For each query it renders the span tree,
  reconciles every ``phase:*`` leaf span bitwise against the
  ``AlphaKReport`` the same execution returned (both views are the same
  bound tape snapshot, so anything but equality is a plumbing bug),
  joins the shuffle phases against ``exchange_stage_bytes`` — the static
  receive buffer the roofline model predicts vs the bytes the tape
  actually received — and dumps the trace as Chrome-trace JSON
  (TRACE_query.json, loadable in ``chrome://tracing`` / Perfetto).

* :func:`run_overhead_gate` — the tracing-off contract: with the tracer
  disabled a warm query records zero traces and module-level ``span()``
  costs one ContextVar read, so the warm per-query time must not exceed
  the traced time by more than the noise bound asserted here.
"""
from __future__ import annotations

import json
import os
from typing import List, Optional

import numpy as np
import jax.numpy as jnp

from repro.cluster import SubstratePool
from repro.data import uniform_keys
from repro.launch.roofline import exchange_stage_bytes
from repro.obs import Tracer, chrome_trace, timeit, write_chrome_trace
from repro.serve import QueryEngine, sort_query
from repro.serve.query import run_spec

TRACE_JSON = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          os.pardir, "TRACE_query.json")

BYTES_PER_OBJ = 4   # int32 keys — what the exchange actually moves


def _phase_spans(root) -> List:
    """The ``phase:*`` leaf spans of a query trace, in execution order."""
    return [s for s in root.walk() if s.name.startswith("phase:")]


def reconcile(root, report) -> None:
    """Assert the span tree's phase leaves ARE the report's taped phases.

    Bitwise: both come from the same ``bound_snapshot``, so names, order
    and every per-machine sent/received count must match exactly.
    """
    spans = _phase_spans(root)
    assert [s.name for s in spans] == [
        f"phase:{p.name}" for p in report.phases], (
        [s.name for s in spans], [p.name for p in report.phases])
    for sp, ph in zip(spans, report.phases):
        assert np.array_equal(np.asarray(sp.attrs["sent"]),
                              np.asarray(ph.sent)), sp.name
        assert np.array_equal(np.asarray(sp.attrs["received"]),
                              np.asarray(ph.received)), sp.name


def exchange_rows(root, report, m: int, *,
                  overlap_chunks: int = 2) -> List[dict]:
    """Join shuffle phase spans against the roofline exchange model.

    Expected is the static per-shard receive buffer
    (``exchange_stage_bytes`` — the same arithmetic the runtime
    allocates); achieved is the peak per-shard bytes the tape recorded.
    Achieved can never exceed expected (the buffer IS the capacity);
    the fill fraction is how much of the provisioned roofline the
    actual skew used.
    """
    topology = getattr(report, "exchange_topology", "flat") or "flat"
    stages = exchange_stage_bytes(
        report.t, m, topology=topology, cap_factor=report.cap_factor,
        bytes_per_obj=BYTES_PER_OBJ, overlap_chunks=overlap_chunks)
    shuffle = [s for s in _phase_spans(root) if "shuffle" in s.name]
    assert len(shuffle) == len(stages), (
        [s.name for s in shuffle], [s.name for s in stages])
    rows = []
    for sp, st in zip(shuffle, stages):
        achieved = int(np.max(np.asarray(sp.attrs["received"]))
                       ) * BYTES_PER_OBJ
        assert achieved <= st.receive_bytes, (sp.name, achieved, st)
        rows.append({
            "phase": sp.name, "stage": st.name, "fanin": st.fanin,
            "expected_recv_bytes": int(st.receive_bytes),
            "achieved_recv_bytes": achieved,
            "fill": round(achieved / st.receive_bytes, 4),
        })
    return rows


def _traced_query(t: int, m: int, exchange: str, pool, tracer):
    """One warm traced submit: pool/plan caches are hot, the LRU is not.

    The engine's result cache would satisfy a repeat of the warming
    query without executing (trace=None by design), so warming goes
    through ``run_spec`` directly on the shared pool and the engine sees
    the spec exactly once.
    """
    x = jnp.asarray(uniform_keys(t * m, seed=7).reshape(t, m))
    spec = sort_query(x, algorithm="smms", exchange=exchange)
    run_spec(spec, substrate=pool)      # warm compile + plan caches
    engine = QueryEngine(pool=pool, tracer=tracer)
    try:
        res = engine.run([spec])[0]
    finally:
        engine.close()
    assert res.ok, res.error
    assert res.trace is not None and res.trace_id == res.trace.trace_id
    return res, res.report      # the same execution's taped report


def run(report_rows: List[str]) -> None:
    t, m = 8, 256
    pool = SubstratePool()
    tracer = Tracer(enabled=True)
    payload = {}
    for exchange in ("flat", "staged"):
        res, report = _traced_query(t, m, exchange, pool, tracer)
        root = res.trace
        reconcile(root, report)
        rows = exchange_rows(root, report, m)
        payload[exchange] = {
            "trace_id": res.trace_id,
            "tree": root.tree_str(),
            "exchange": rows,
        }
        compiles = sum(1 for s in root.walk()
                       for e in s.events if e.name == "compile")
        assert compiles == 0, root.tree_str()   # warm means warm
        for r in rows:
            report_rows.append(
                f"trace_report,{exchange},{r['stage']},fanin={r['fanin']},"
                f"expected={r['expected_recv_bytes']},"
                f"achieved={r['achieved_recv_bytes']},fill={r['fill']}")
    # ---- Chrome trace: both topologies' traces in one file ----------------
    traces = list(tracer.traces)
    doc = chrome_trace(traces)
    assert doc["traceEvents"], doc
    json.loads(json.dumps(doc))         # valid, serializable JSON
    write_chrome_trace(TRACE_JSON, traces)
    report_rows.append(f"trace_report,json,{os.path.abspath(TRACE_JSON)}")
    report_rows.append(
        "trace_report,tree,flat:\n" + payload["flat"]["tree"])


def run_overhead_gate(report_rows: List[str]) -> None:
    """Tracing off must cost nothing: zero traces recorded, and the warm
    per-query wall time within noise of the traced run."""
    t, m = 8, 256
    x = jnp.asarray(uniform_keys(t * m, seed=11).reshape(t, m))
    spec = sort_query(x, algorithm="smms")
    pool = SubstratePool()
    run_spec(spec, substrate=pool)      # warm

    def _run_with(tracer: Optional[Tracer]):
        engine = QueryEngine(pool=pool, tracer=tracer,
                             result_cache_size=0)
        try:
            return timeit(lambda: engine.run([spec])[0],
                          reps=5, warmup=1)
        finally:
            engine.close()

    off_tracer = Tracer(enabled=False)
    off = _run_with(off_tracer)
    assert off.last_result.trace is None
    assert not off_tracer.traces, "disabled tracer recorded spans"

    on_tracer = Tracer(enabled=True)
    on = _run_with(on_tracer)
    assert on.last_result.trace is not None

    ratio = off.best_s / on.best_s
    report_rows.append(
        f"trace_overhead,off_us={off.best_us:.0f},on_us={on.best_us:.0f},"
        f"off_over_on={ratio:.3f}")
    # off-mode work is a strict subset of on-mode work; 1.25x covers
    # scheduler noise on a shared CI box without masking a real leak
    # (an accidentally-always-on tracer shows up as ratio ~1.0 plus
    # recorded traces, caught by the zero-traces assert above).
    assert ratio <= 1.25, (off.best_us, on.best_us)


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    rows: List[str] = []
    run(rows)
    run_overhead_gate(rows)
    print("\n".join(rows))
