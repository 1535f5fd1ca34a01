"""Core (alpha, k)-minimal algorithms from the paper, TPU-native.

Everything here is written against ``axis_name`` collectives so the same
per-device body runs under ``shard_map`` (production mesh) and ``vmap``
(t virtual machines in unit tests on one CPU device).
"""
from .alpha_k import (AlphaKReport, PhaseStats, randjoin_k_bound,
                      smms_k_bound, statjoin_k_bound, terasort_k_bound)
from .boundaries import (boundaries_jax, boundaries_oracle,
                         equidepth_samples, interval_pdf)
from .broadcastjoin import broadcast_join
from .exchange import (PAD, ExchangeResult, exchange_sorted_segments,
                       partition_sorted)
from .localjoin import MASKED_KEY, JoinOutput, join_size, local_equijoin
from .randjoin import choose_ab, randjoin, randjoin_shard
from .repartition import repartition_join
from .sampling import algorithm_s, terasort_sample_count
from .smms import SortResult, default_cap_factor, smms_shard, smms_sort
from .statjoin import (JoinStatistics, Rectangle, StatJoinPlan,
                       collect_statistics, plan_statjoin, statjoin)
from .terasort import terasort_shard, terasort_sort

__all__ = [
    "AlphaKReport", "PhaseStats", "smms_k_bound", "terasort_k_bound",
    "statjoin_k_bound", "randjoin_k_bound",
    "boundaries_jax", "boundaries_oracle", "equidepth_samples",
    "interval_pdf", "PAD", "ExchangeResult", "exchange_sorted_segments",
    "partition_sorted", "MASKED_KEY", "JoinOutput", "join_size",
    "local_equijoin", "broadcast_join", "choose_ab", "randjoin",
    "randjoin_shard",
    "repartition_join", "algorithm_s", "terasort_sample_count",
    "SortResult", "default_cap_factor", "smms_shard", "smms_sort",
    "JoinStatistics", "Rectangle", "StatJoinPlan", "collect_statistics",
    "plan_statjoin", "statjoin", "terasort_shard", "terasort_sort",
]
