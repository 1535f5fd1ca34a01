"""Distributed sort on a real device mesh — the production path.

The identical per-device body the unit tests run on vmap virtual
machines executes here on a ShardMapSubstrate over every available
device, with the (alpha, k) report assembled from the instrumented
collectives either way.

    PYTHONPATH=src python examples/sort_cluster.py
"""
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import numpy as np
import jax
import jax.numpy as jnp

from repro import cluster
from repro.cluster import ShardMapSubstrate
from repro.core.alpha_k import smms_workload_bound
from repro.data import lidar_like


def main():
    t = len(jax.devices())
    m, r = 1 << 14, 2
    x = lidar_like(t * m, seed=3).reshape(t, m)

    substrate = ShardMapSubstrate(("machines", t))
    (keys, _), report = cluster.sort(jnp.asarray(x), algorithm="smms", r=r,
                                     substrate=substrate)
    assert np.all(np.diff(keys) >= 0) and len(keys) == t * m
    counts = report.workload
    bound = smms_workload_bound(t * m, t, r)
    print(f"devices={t}  n={t*m}  max-load={int(counts.max())}  "
          f"mean={counts.mean():.0f}  Thm1-bound={bound:.0f}")
    print(f"imbalance {report.imbalance:.3f} — SMMS on a real mesh, zero "
          f"drops at the Theorem-1 static capacity "
          f"(cap_factor={report.cap_factor:.3f}, "
          f"{report.capacity_attempts} attempt(s))")
    for p in report.phases:
        print(f"  phase {p.name:22s} max sent {int(np.max(p.sent)):6d}  "
              f"max received {int(np.max(p.received)):6d}")

    # --- the same sort through the Pallas kernel layer -------------------
    # kernel_backend="pallas" routes the Round-1 bitonic sort, the
    # branch-free searchsorted partition, and the Round-3 merge kernel; the
    # output is bitwise identical to the jnp reference path.  (The kernel
    # mode follows the platform: the Pallas interpreter on CPU, Mosaic-
    # compiled kernels on a TPU, for the identical calls.)
    mk = 1 << 10
    xk = jnp.asarray(lidar_like(t * mk, seed=3).reshape(t, mk))
    (keys_ref, _), _ = cluster.sort(xk, algorithm="smms", r=r,
                                    substrate=ShardMapSubstrate(("machines", t)),
                                    kernel_backend="reference")
    (keys_ker, _), rep_k = cluster.sort(xk, algorithm="smms", r=r,
                                        substrate=ShardMapSubstrate(("machines", t)),
                                        kernel_backend="pallas")
    assert np.array_equal(np.asarray(keys_ref), np.asarray(keys_ker))
    print(f"kernel_backend='pallas' (n={t*mk}): bitwise-identical output, "
          f"imbalance {rep_k.imbalance:.3f}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
