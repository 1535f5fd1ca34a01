"""Quickstart: the paper's algorithms in five minutes.

Everything goes through the cluster front door — one dispatch, one
substrate runtime, one (alpha, k) report format for all four algorithms.

    PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np
import jax.numpy as jnp

from repro import cluster
from repro.data import lidar_like, scalar_skew_tables


def main():
    # ---- 1. SMMS: deterministic balanced distributed sort ------------------
    t, m = 8, 4096
    x = lidar_like(t * m, seed=0).reshape(t, m)   # skewed 'real' data
    (sorted_keys, _), report = cluster.sort(jnp.asarray(x),
                                            algorithm="smms", r=2)
    assert np.all(np.diff(sorted_keys) >= 0)
    print(f"SMMS     : sorted {t*m} keys on {t} machines | "
          f"imbalance {report.imbalance:.3f} (optimal 1.0) | "
          f"alpha={report.alpha}")

    # ---- 2. Terasort baseline: randomized, weaker balance ------------------
    (_, _), rep_ts = cluster.sort(jnp.asarray(x), algorithm="terasort",
                                  seed=0)
    print(f"Terasort : imbalance {rep_ts.imbalance:.3f}  "
          f"(paper: SMMS beats this by design — Thm 1 vs Thm 3)")

    # ---- 3. Skew join: one hot key, three algorithms -----------------------
    n = 4000
    s_keys, t_keys = scalar_skew_tables(n, m_hot=400, n_hot=100, seed=1)
    rows = np.arange(n)

    reports = {}
    for alg in cluster.JOIN_ALGORITHMS:
        _, reports[alg] = cluster.join(s_keys, rows, t_keys, rows,
                                       algorithm=alg, t_machines=8)
    print(f"Skew join imbalance: "
          f"repartition {reports['repartition'].imbalance:.2f}  "
          f"randjoin {reports['randjoin'].imbalance:.2f}  "
          f"statjoin {reports['statjoin'].imbalance:.2f}  "
          f"broadcast {reports['broadcast'].imbalance:.2f}  "
          f"(lower = better, 1.0 ideal)")
    print("Repartition pins the hot key to ONE machine; the others "
          "spread it (Cor 3 / Thm 6 / replication).")

    # ---- 4. Or let the planner decide --------------------------------------
    _, rep = cluster.join(s_keys, rows, t_keys, rows, algorithm="auto",
                          t_machines=8)
    print(f"auto     : planner chose {rep.query_plan.algorithm!r} "
          f"(predicted k={rep.predicted_k:.2f}, "
          f"measured k={rep.k_workload:.2f})")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
