"""Unified cluster substrate for the paper's (alpha, k) algorithms.

One runtime, two executors, uniform accounting:

* :mod:`substrate`   — ``VmapSubstrate`` (t virtual machines) and
  ``ShardMapSubstrate`` (real mesh) behind one ``run(shard_fn, *args)``.
* :mod:`collectives` — instrumented ``all_gather`` / ``all_to_all`` /
  ``ragged_all_to_all`` / ``psum`` recording per-device traffic inside
  the jitted program; assembles AlphaKReport automatically.
* :mod:`capacity`    — theorem-derived static receive capacities and the
  retry-on-overflow loop.
* :mod:`api`         — ``cluster.sort`` / ``cluster.join`` dispatch over
  all the algorithms (SMMS, Terasort+AlgS, RandJoin, StatJoin, the
  broadcast small-table join) plus the repartition baseline — and
  ``algorithm="auto"``, which hands the choice to the sketch-driven
  planner in :mod:`repro.planner`.
"""
from .api import (AUTO, JOIN_ALGORITHMS, MOE_DISPATCH_MODES,
                  SORT_ALGORITHMS, join, moe_dispatch, sort)
from .capacity import CapacityOverflowError, CapacityPolicy, run_with_capacity
from .collectives import CollectiveTape
from .substrate import (ShardMapSubstrate, Substrate, SubstratePool,
                        VmapSubstrate, default_pool, default_substrate,
                        recommend_pool_size, reset_default_pool)

__all__ = [
    "sort", "join", "moe_dispatch",
    "SORT_ALGORITHMS", "JOIN_ALGORITHMS", "MOE_DISPATCH_MODES", "AUTO",
    "CapacityPolicy", "CapacityOverflowError", "run_with_capacity",
    "CollectiveTape",
    "Substrate", "VmapSubstrate", "ShardMapSubstrate", "SubstratePool",
    "default_substrate", "default_pool", "reset_default_pool",
    "recommend_pool_size",
]
