"""Sustained serving traffic: the micro-batched engine vs one-shot calls.

A mixed 200-query trace (sorts + joins, fixed and auto algorithms,
popularity skewed the way serving traffic is — a zipf-weighted draw
from a pool of distinct queries) runs twice with warm caches:

* **baseline** — sequential one-shot ``cluster.sort``/``cluster.join``
  calls, exactly what a client loop without the engine does (the plan
  cache is module-global, so the baseline benefits from it too);
* **engine**  — the same trace through ``QueryEngine``: micro-batching,
  in-flight coalescing, and the shared jit substrate pool.

The acceptance bar asserted here: engine QPS >= 2x baseline QPS, with
plan-cache hit rate and recompile counts recorded in BENCH_serve.json
(recompiles during the measured run must be ZERO — the pool was warmed,
so any compile would be a cache-key instability).

``run_sustained`` is the ROADMAP-4 sustained-load proof: a 100k-query
zipf trace with a 10/30/60 high/normal/low priority mix, offered at 2x
the engine's measured capacity from paced submitter threads.  The gates:
high-priority p99 stays within 3x its uncontended p99, low-priority
traffic is shed (typed errors, bounded queue — never queued unboundedly),
shed rates order by class, and zero recompiles during measurement.
Results land in BENCH_serve.json under ``sustained_load`` plus a
human-readable SERVE_overload.txt latency table (the CI artifact).
"""
from __future__ import annotations

import dataclasses
import itertools
import json
import os
import threading
import time
from typing import Dict, List

import numpy as np
import jax.numpy as jnp

from repro.cluster import SubstratePool
from repro.data import uniform_keys, zipf_tables
from repro.obs import timeit
from repro.serve import (PRIORITY_HIGH, PRIORITY_LOW, PRIORITY_NORMAL,
                         AdmissionError, DeadlineExceededError, QueryEngine,
                         ShedError, join_query, sort_query)
from repro.serve.query import PRIORITY_NAMES, run_spec

_ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir)
BENCH_JSON = os.path.join(_ROOT, "BENCH_serve.json")
OVERLOAD_TXT = os.path.join(_ROOT, "SERVE_overload.txt")

N_QUERIES = 200
SEED = 1234


def _update_bench(payload: dict, key: str = None) -> None:
    """Read-modify-write BENCH_serve.json: ``run`` owns the top-level
    keys, ``run_sustained`` owns the ``sustained_load`` section — each
    mode must survive the other re-running."""
    doc = {}
    if os.path.exists(BENCH_JSON):
        try:
            with open(BENCH_JSON) as f:
                doc = json.load(f)
        except (json.JSONDecodeError, OSError):
            doc = {}
    if key is None:
        doc.update(payload)
    else:
        doc[key] = payload
    with open(BENCH_JSON, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)


def build_query_pool() -> List:
    """~24 distinct queries: three sort shapes x seeds, three join pairs."""
    pool = []
    for t, m in ((8, 256), (8, 512), (4, 256)):
        for seed in range(4):
            x = jnp.asarray(uniform_keys(t * m, seed=97 * seed + t)
                            .reshape(t, m))
            alg = ("smms", "terasort", "auto", "auto")[seed]
            kw = {"seed": seed} if alg == "terasort" else {}
            pool.append(sort_query(x, algorithm=alg, **kw))
    for i, theta in enumerate((1.0, 0.5, -0.5)):
        sk, tk = zipf_tables(600, 600, theta=theta, seed=31 + i, domain=80)
        rows = np.arange(600)
        for alg in ("statjoin", "randjoin", "broadcast", "auto"):
            kw = {"seed": i} if alg == "randjoin" else {}
            pool.append(join_query(sk, rows, tk, rows, t_machines=8,
                                   algorithm=alg, **kw))
    return pool


def build_trace(pool, n=N_QUERIES, seed=SEED) -> List:
    """Zipf-popularity draw: real traffic repeats its hot queries."""
    rng = np.random.default_rng(seed)
    ranks = np.arange(1, len(pool) + 1, dtype=np.float64)
    p = (1.0 / ranks) / (1.0 / ranks).sum()
    return [pool[i] for i in rng.choice(len(pool), size=n, p=p)]


def run_direct(spec):
    """Sequential one-shot baseline: the engine's own spec-unpacking
    helper, without pool or engine."""
    return run_spec(spec)


def run(report_rows: List[str]) -> None:
    pool_specs = build_query_pool()
    trace = build_trace(pool_specs)

    # ---- warm the one-shot path (plan cache) + run its measured trace -----
    warm_results = {s.fingerprint(): run_direct(s) for s in pool_specs}
    dt_base = timeit(lambda: [run_direct(s) for s in trace],
                     reps=1, warmup=0).best_s
    qps_base = len(trace) / dt_base

    # ---- engine constructed AFTER the baseline so its ServeStats deltas
    # (plan-cache hits/misses) cover only traffic the engine served ---------
    sub_pool = SubstratePool()
    engine = QueryEngine(pool=sub_pool, max_batch=32, batch_window_s=0.005)
    engine.run(pool_specs)          # warm the compiled programs
    compiles_after_warm = sub_pool.stats()["compiles"]

    # ---- engine: the same trace, submitted as traffic ---------------------
    eng_res = timeit(lambda: engine.run(trace), reps=1, warmup=0)
    results, dt_engine = eng_res.last_result, eng_res.best_s
    qps_engine = len(trace) / dt_engine
    stats = engine.stats()
    # captured BEFORE the ablation engine touches the same pool, so this
    # really is "compiles during the measured trace"
    recompiles_measured = sub_pool.stats()["compiles"] - compiles_after_warm
    engine.close()

    # ---- ablation: result LRU off (pure batching + program cache) ---------
    engine_nc = QueryEngine(pool=sub_pool, max_batch=32,
                            batch_window_s=0.005, result_cache_size=0)
    nc_res = timeit(lambda: engine_nc.run(trace), reps=1, warmup=0)
    results_nc, dt_nc = nc_res.last_result, nc_res.best_s
    qps_nc = len(trace) / dt_nc
    engine_nc.close()
    assert all(r.ok for r in results_nc)

    assert all(r.ok for r in results), [r.error for r in results if not r.ok]
    # spot-check parity against the warm direct results
    for r in results[:20]:
        want, _ = warm_results[r.spec.fingerprint()]
        got = r.value
        if r.spec.kind == "sort":
            np.testing.assert_array_equal(np.asarray(got[0]),
                                          np.asarray(want[0]))
        else:
            for a, b in zip(got, want):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    speedup = qps_engine / qps_base
    payload = {
        "n_queries": len(trace),
        "distinct_queries": len(pool_specs),
        "baseline_qps": round(qps_base, 3),
        "engine_qps": round(qps_engine, 3),
        "engine_qps_no_result_cache": round(qps_nc, 3),
        "speedup": round(speedup, 3),
        "speedup_no_result_cache": round(qps_nc / qps_base, 3),
        "result_cache_hits": stats.result_cache_hits,
        # percentiles over the measured trace only (engine-lifetime
        # stats would fold the warmup's compile latencies in)
        "p50_latency_s": round(float(np.percentile(
            [r.latency_s for r in results], 50)), 6),
        "p99_latency_s": round(float(np.percentile(
            [r.latency_s for r in results], 99)), 6),
        "coalesced": stats.coalesced,
        "executed": stats.executed,
        "batches": stats.batches,
        "plan_cache_hits": stats.plan_cache_hits,
        "plan_cache_misses": stats.plan_cache_misses,
        "plan_cache_hit_rate": round(stats.plan_cache_hit_rate, 4),
        "recompiles_total": sub_pool.stats()["compiles"],
        "recompiles_during_measurement": int(recompiles_measured),
        "program_cache_hits": sub_pool.stats()["program_cache_hits"],
        "capacity_retries": stats.capacity_retries,
    }
    _update_bench(payload)

    report_rows.append(
        f"serve,trace={len(trace)},baseline_qps={qps_base:.2f},"
        f"engine_qps={qps_engine:.2f},speedup={speedup:.2f}")
    report_rows.append(
        f"serve,coalesced={stats.coalesced},executed={stats.executed},"
        f"plan_hit_rate={stats.plan_cache_hit_rate:.3f},"
        f"recompiles_measured={int(recompiles_measured)}")
    report_rows.append(f"serve,json,{os.path.abspath(BENCH_JSON)}")

    # the acceptance bar: micro-batched serving sustains >= 2x one-shot QPS
    assert speedup >= 2.0, f"engine speedup {speedup:.2f} < 2.0"
    # warm pool means the measured run never recompiled
    assert recompiles_measured == 0, recompiles_measured


# ---------------------------------------------------------------------------
# Sustained load: 100k zipf queries at 2x capacity, shed-by-class gates
# ---------------------------------------------------------------------------

# 10% high / 30% normal / 60% low — the shape of real mixed traffic:
# most requests are best-effort, a thin stripe is interactive.  Lows
# carry a deadline so queue time alone can expire them; highs carry
# none (their SLO is the p99 gate, not a shed).
PRIORITY_MIX = ((PRIORITY_HIGH, 0.10, None),
                (PRIORITY_NORMAL, 0.30, 5.0),
                (PRIORITY_LOW, 0.60, 1.5))


def build_sustained_trace(pool, n, seed=SEED) -> List:
    """Zipf-popularity spec draw x the priority mix."""
    rng = np.random.default_rng(seed)
    base = build_trace(pool, n=n, seed=seed)
    prios = [p for p, _, _ in PRIORITY_MIX]
    weights = [w for _, w, _ in PRIORITY_MIX]
    deadlines = {p: d for p, _, d in PRIORITY_MIX}
    drawn = rng.choice(len(prios), size=n, p=weights)
    return [dataclasses.replace(s, priority=prios[i],
                                deadline_s=deadlines[prios[i]])
            for s, i in zip(base, drawn)]


def _percentiles(latencies: List[float]) -> Dict[str, float]:
    if not latencies:
        return {"p50": 0.0, "p99": 0.0, "p999": 0.0}
    arr = np.asarray(latencies)
    return {"p50": round(float(np.percentile(arr, 50)), 6),
            "p99": round(float(np.percentile(arr, 99)), 6),
            "p999": round(float(np.percentile(arr, 99.9)), 6)}


def run_sustained(report_rows: List[str], n_queries: int = 100_000,
                  overload: float = 2.0, submitters: int = 4) -> None:
    pool_specs = build_query_pool()
    sub_pool = SubstratePool()
    # result LRU OFF: sustained load must stress execution + batching +
    # coalescing, not a dict lookup (with only ~24 distinct queries the
    # LRU would absorb the whole trace and "capacity" would be a
    # memcpy benchmark).  max_batch=16, not 32: a high-priority arrival
    # waits behind at most one in-flight group, so the batch execution
    # time IS the high-p99 floor — halving the batch halves the floor
    # at a modest capacity cost.
    engine = QueryEngine(pool=sub_pool, max_pending=256, max_batch=16,
                         batch_window_s=0.002, result_cache_size=0)
    engine.run(pool_specs)            # warm every compiled program
    compiles_after_warm = sub_pool.stats()["compiles"]

    # ---- uncontended high-priority p99: gentle sequential submits ---------
    uncontended = build_trace(pool_specs, n=min(400, n_queries), seed=77)
    unc_lat = [engine.submit(dataclasses.replace(s,
                                                 priority=PRIORITY_HIGH))
               .result(timeout=120.0).latency_s for s in uncontended]
    p_unc = _percentiles(unc_lat)

    # ---- measured capacity: a blocking all-normal chunk -------------------
    chunk = build_trace(pool_specs, n=min(2000, n_queries), seed=88)
    cap_res = timeit(lambda: engine.run(chunk, timeout=300.0),
                     reps=1, warmup=0)
    assert all(r.ok for r in cap_res.last_result)
    capacity_qps = len(chunk) / cap_res.best_s
    offered_qps = capacity_qps * overload

    # ---- overload phase: paced submitter threads at overload x capacity ---
    trace = build_sustained_trace(pool_specs, n=n_queries)
    tickets: List = [None] * len(trace)
    door_shed = {p: 0 for p, _, _ in PRIORITY_MIX}
    door_lock = threading.Lock()
    idx = itertools.count()
    t_start = time.monotonic()

    def submitter():
        while True:
            i = next(idx)
            if i >= len(trace):
                return
            due = t_start + i / offered_qps
            now = time.monotonic()
            if due > now:
                time.sleep(due - now)
            try:
                tickets[i] = engine.submit(trace[i], block=False)
            except AdmissionError:
                # full of same-or-better class: shed at the door — the
                # bounded queue refusing to grow IS the gate's point
                with door_lock:
                    door_shed[trace[i].priority] += 1

    threads = [threading.Thread(target=submitter, daemon=True)
               for _ in range(submitters)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()

    # ---- collect every outcome (nothing may hang) --------------------------
    per_class: Dict[int, Dict[str, list]] = {
        p: {"latency": [], "shed": 0, "expired": 0, "failed": 0}
        for p, _, _ in PRIORITY_MIX}
    for spec, tk in zip(trace, tickets):
        row = per_class[spec.priority]
        if tk is None:
            row["shed"] += 1          # door rejection
            continue
        try:
            res = tk.result(timeout=300.0)
        except ShedError:
            row["shed"] += 1
            continue
        except DeadlineExceededError:
            row["expired"] += 1
            continue
        if res.ok:
            row["latency"].append(res.latency_s)
        else:
            row["failed"] += 1
    wall = time.monotonic() - t_start
    served_total = sum(len(row["latency"]) for row in per_class.values())
    stats = engine.stats()
    recompiles_measured = sub_pool.stats()["compiles"] - compiles_after_warm
    engine.close()

    classes = {}
    for prio, frac, deadline in PRIORITY_MIX:
        name = PRIORITY_NAMES[prio]
        row = per_class[prio]
        offered = sum(1 for s in trace if s.priority == prio)
        shed_all = row["shed"] + row["expired"]
        classes[name] = {
            "offered": offered,
            "served": len(row["latency"]),
            "shed": row["shed"],
            "expired": row["expired"],
            "failed": row["failed"],
            "shed_rate": round(shed_all / max(offered, 1), 4),
            "deadline_s": deadline,
            **_percentiles(row["latency"]),
        }

    high, normal, low = (classes["high"], classes["normal"],
                         classes["low"])
    payload = {
        "n_queries": len(trace),
        "distinct_queries": len(pool_specs),
        "overload_factor": overload,
        "submitter_threads": submitters,
        "capacity_qps": round(capacity_qps, 2),
        "offered_qps": round(offered_qps, 2),
        "served_qps": round(served_total / wall if wall > 0 else 0.0, 2),
        "wall_s": round(wall, 3),
        "uncontended_high": p_unc,
        "classes": classes,
        "peak_pending": stats.peak_pending,
        "max_pending": 256,
        "recompiles_during_measurement": int(recompiles_measured),
        "high_p99_ratio": round(high["p99"] / max(p_unc["p99"], 1e-9), 3),
    }
    _update_bench(payload, key="sustained_load")

    # ---- the human-readable overload table (CI artifact) -------------------
    lines = [
        f"sustained load: {len(trace)} zipf queries at "
        f"{overload:.1f}x capacity ({offered_qps:.0f} qps offered, "
        f"{capacity_qps:.0f} qps capacity, {submitters} submitters)",
        f"uncontended high-priority: p50={p_unc['p50'] * 1e3:.2f}ms  "
        f"p99={p_unc['p99'] * 1e3:.2f}ms  p999={p_unc['p999'] * 1e3:.2f}ms",
        "",
        f"{'class':>8} {'offered':>8} {'served':>8} {'shed':>7} "
        f"{'expired':>8} {'shed%':>7} {'p50_ms':>9} {'p99_ms':>9} "
        f"{'p999_ms':>9}",
    ]
    for name in ("high", "normal", "low"):
        c = classes[name]
        lines.append(
            f"{name:>8} {c['offered']:>8} {c['served']:>8} {c['shed']:>7} "
            f"{c['expired']:>8} {100 * c['shed_rate']:>6.2f}% "
            f"{c['p50'] * 1e3:>8.2f} {c['p99'] * 1e3:>8.2f} "
            f"{c['p999'] * 1e3:>8.2f}")
    lines += [
        "",
        f"high p99 under overload / uncontended: "
        f"{payload['high_p99_ratio']:.2f}x (gate: <= 3x)",
        f"peak admission queue depth: {stats.peak_pending} "
        f"(bound: 256)",
        f"recompiles during measurement: {int(recompiles_measured)} "
        f"(gate: 0)",
    ]
    with open(OVERLOAD_TXT, "w") as f:
        f.write("\n".join(lines) + "\n")

    report_rows.append(
        f"serve_sustained,n={len(trace)},capacity_qps={capacity_qps:.0f},"
        f"offered_qps={offered_qps:.0f},"
        f"high_p99_ratio={payload['high_p99_ratio']:.2f}")
    report_rows.append(
        f"serve_sustained,shed:high={high['shed'] + high['expired']},"
        f"normal={normal['shed'] + normal['expired']},"
        f"low={low['shed'] + low['expired']},"
        f"recompiles_measured={int(recompiles_measured)}")
    report_rows.append(f"serve_sustained,table,"
                       f"{os.path.abspath(OVERLOAD_TXT)}")

    # ---- the ROADMAP-4 acceptance gates ------------------------------------
    assert high["p99"] <= 3.0 * max(p_unc["p99"], 1e-9), (
        f"high-priority p99 {high['p99']:.4f}s exceeds 3x uncontended "
        f"{p_unc['p99']:.4f}s under {overload:.1f}x overload")
    assert low["shed"] + low["expired"] > 0, \
        "2x overload shed no low-priority traffic — queue grew unboundedly?"
    assert (high["shed_rate"] <= normal["shed_rate"] <= low["shed_rate"]), (
        f"shed rates out of class order: high={high['shed_rate']} "
        f"normal={normal['shed_rate']} low={low['shed_rate']}")
    assert high["failed"] + normal["failed"] + low["failed"] == 0
    assert stats.peak_pending <= 256
    assert recompiles_measured == 0, recompiles_measured


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--sustained", action="store_true",
                    help="run the 100k-query overload mode instead of "
                         "the engine-vs-one-shot comparison")
    ap.add_argument("--n", type=int, default=100_000,
                    help="sustained-mode query count (CI smoke uses 5000)")
    ap.add_argument("--overload", type=float, default=2.0)
    cli = ap.parse_args()
    rows: List[str] = []
    if cli.sustained:
        run_sustained(rows, n_queries=cli.n, overload=cli.overload)
    else:
        run(rows)
    print("\n".join(rows))
