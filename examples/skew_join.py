"""Skew join walkthrough: Zipf tables, every algorithm through the
cluster front door, the paper's Fig 11/13 workload distributions printed
as histograms — then ``algorithm="auto"``: the planner sketches the
tables, scores the candidates with the theorem cost model, and picks.

    PYTHONPATH=src python examples/skew_join.py
"""
import collections

import numpy as np

from repro import cluster
from repro.data import zipf_tables


def bar(w, width=40):
    mx = max(w)
    return "\n".join(
        "  M%-2d |%s %d" % (i, "#" * int(width * v / max(mx, 1)), v)
        for i, v in enumerate(w))


def main():
    n, t = 3000, 8
    for theta in (0.0, 1.0):
        s_keys, t_keys = zipf_tables(n, n, theta=theta, seed=2, domain=150)
        rows = np.arange(n)
        cs = collections.Counter(s_keys.tolist())
        ct = collections.Counter(t_keys.tolist())
        w = sum(cs[k] * ct[k] for k in cs if k in ct)

        print(f"\n=== Zipf theta={theta} "
              f"({'skewed' if theta < 0.5 else 'uniform'}), |result|={w} ===")
        for alg, note in (("repartition", ""), ("randjoin", ""),
                          ("broadcast", ""),
                          ("statjoin", " (Thm 6 bound: 2.0)")):
            _, rep = cluster.join(s_keys, rows, t_keys, rows, algorithm=alg,
                                  t_machines=t)
            print(f"[{alg:11s}]  imbalance {rep.imbalance:.2f}{note}")
            print(bar(rep.workload))

        # ---- the self-driving path: sketch -> cost model -> dispatch ----
        _, rep = cluster.join(s_keys, rows, t_keys, rows, algorithm="auto",
                              t_machines=t)
        print(f"[auto       ]  chose {rep.query_plan.algorithm!r}: "
              f"predicted (alpha={rep.predicted_alpha}, "
              f"k={rep.predicted_k:.2f}) vs measured "
              f"(alpha={rep.alpha}, k={rep.k_workload:.2f})")
        print(rep.query_plan.summary())
        # a repeated query over the same tables hits the plan cache and
        # skips the sketch round entirely
        _, rep2 = cluster.join(s_keys, rows, t_keys, rows, algorithm="auto",
                               t_machines=t)
        print(f"  (second run: cached={rep2.query_plan.cached}, "
              f"sketch rounds={len(rep2.sketch_phases)})")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
