"""Median over the window's jobs of the algorithm's own k_workload (its
AlphaKReport: the largest machine's records over n/t)."""
import statistics


def read(run):
    ks = [j["k_workload"] for j in run.jobs if j.get("k_workload")]
    return statistics.median(ks) if ks else None
