"""The paper's guarantees, as the configurations state them: rounds and
per-machine workload bounds (arXiv:1403.5381, Theorems 1, 3 and 6)."""
from __future__ import annotations


def workload_bound(theorem: str, *, n: int, t: int, r: int = 2,
                   w: int = 0) -> float:
    """Objects one machine may hold at the end, by the named theorem."""
    m = n / t
    if theorem == "smms_theorem1":        # (1 + 2/r + t^2/n) m
        return (1.0 + 2.0 / r + t * t / n) * m
    if theorem == "terasort_theorem3":    # 5m + 1, w.p. >= 1 - 1/n
        return 5.0 * m + 1.0
    if theorem == "statjoin_theorem6":    # 2W/t result pairs
        return 2.0 * w / t
    raise ValueError(f"unknown theorem {theorem!r}")


ROUNDS = {"smms": 3, "terasort": 3, "statjoin": 3}
THEOREM = {"smms": "smms_theorem1", "terasort": "terasort_theorem3",
           "statjoin": "statjoin_theorem6"}
