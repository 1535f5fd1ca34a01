#!/usr/bin/env python3
"""Bring-up smoke run of the sort/join front door on a TPU chip.

    python chip_smoke.py [--seed N]        # one chip: bulk + serve phases
    python chip_smoke.py --chips 4         # four chips: the mesh phase only

One process, every input generated on the device from ``--seed``.  The
phases drive the entry points a user calls and check every answer:

* bulk  — ``cluster.sort`` (SMMS and Terasort, uniform and Zipf-skewed
  keys) over 2^27 (float32 key, int32 payload) records on t = 16 virtual
  machines of the default substrate pool, and a ``cluster.join``
  StatJoin foreign-key join (|S| = 2^24 rows whose keys follow a Zipf
  law with exponent 1.1 over T's 2^22 unique keys).  Checked against
  numpy on the host: keys sorted, payload a permutation consistent with
  the keys; every S row joined exactly once, to the T row with its key.
* serve — a ``QueryEngine`` on the Pallas kernel backend answers mixed
  sort and join queries at kernel-eligible shapes (t = 8, 2^13 to 2^16
  keys per machine); each answer must equal, bit for bit, the same
  query run with the path pinned to the reference
  (``ops.DEFAULT_BACKEND``), and no eligible operand may take the
  reference path.
* mesh (``--chips 4``) — ``cluster.sort(algorithm="smms")`` with the
  flat and the staged 2x2 exchange (uniform keys, with ties) and
  ``cluster.join(algorithm="statjoin")`` on a ``ShardMapSubstrate``
  over the four chips, each compared bit for bit with
  ``VmapSubstrate(4)`` on one chip.  The five runs go on concurrent
  threads, so their compiles overlap.  The sorts' compiled programs
  must hold an all-to-all; StatJoin routes on the host, so its program
  has no exchange and only its device placement is checked.

Earlier lines report the devices, the compile-cache directory, per-phase
wall time, compile count, peak device bytes, each report's (alpha, k)
and the kernel dispatch counts by (op, path).  The last line is one JSON
object: ``{"ok": true, "device": {...}}`` on a TPU when every phase
passed.  Off TPU the same phases run at a tiny size as a rehearsal and
the script exits 1 without claiming success.  Any failed check raises
and exits nonzero.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

# (bulk, serve, mesh) sizes on a TPU and in the off-TPU rehearsal
SIZES = {
    True: dict(bulk_t=16, bulk_n=1 << 27, join_s=1 << 24, join_t=1 << 22,
               serve_t=8, serve_m=(1 << 13, 1 << 16), serve_join_m=1 << 13,
               mesh_n=1 << 27, mesh_join_s=1 << 24, mesh_join_t=1 << 22),
    False: dict(bulk_t=16, bulk_n=1 << 12, join_s=1 << 11, join_t=1 << 9,
                serve_t=8, serve_m=(1 << 7, 1 << 9), serve_join_m=1 << 6,
                mesh_n=1 << 12, mesh_join_s=1 << 10, mesh_join_t=1 << 8),
}
SORT_ZIPF_EXPONENT = 0.8   # hottest key ~0.6% of records: under 1/t
JOIN_ZIPF_EXPONENT = 1.1
SERVE_QUERIES = 16


class CheckFailed(AssertionError):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise CheckFailed(what)


def log(*parts) -> None:
    print(*parts, flush=True)


# ---------------------------------------------------------------------------
# data, generated on the device
# ---------------------------------------------------------------------------

def power_law_ranks(key, n: int, domain: int, exponent: float):
    """n ranks in [0, domain) with P(rank r) ~ (r + 1)^-exponent: the
    inverse CDF of the continuous power law on [1, domain + 1), floored."""
    import jax
    import jax.numpy as jnp
    u = jax.random.uniform(key, (n,), jnp.float32)
    e = 1.0 - exponent
    top = jnp.float32(domain + 1) ** e
    x = ((top - 1.0) * u + 1.0) ** (1.0 / e)
    return jnp.clip(jnp.floor(x).astype(jnp.int32) - 1, 0, domain - 1)


def sort_input(key, t: int, n: int, skewed: bool):
    """(keys (t, m) float32, payload (t, m) int32 = record ids)."""
    import jax
    import jax.numpy as jnp
    if skewed:
        keys = power_law_ranks(key, n, 1 << 24, SORT_ZIPF_EXPONENT
                               ).astype(jnp.float32)
    else:   # the paper's random data sets: uniform keys in [1, 12e6)
        keys = jax.random.uniform(key, (n,), jnp.float32, 1.0, 12e6)
    payload = jnp.arange(n, dtype=jnp.int32)
    return keys.reshape(t, -1), payload.reshape(t, -1)


def fk_join_input(key, n_s: int, n_t: int):
    """S foreign keys into T's unique keys, Zipf-skewed; rows = ids."""
    import jax
    import jax.numpy as jnp
    kt, ks = jax.random.split(key)
    t_keys = jax.random.permutation(kt, n_t).astype(jnp.int32)
    s_keys = t_keys[power_law_ranks(ks, n_s, n_t, JOIN_ZIPF_EXPONENT)]
    return (s_keys, jnp.arange(n_s, dtype=jnp.int32),
            t_keys, jnp.arange(n_t, dtype=jnp.int32))


# ---------------------------------------------------------------------------
# checks against numpy on the host
# ---------------------------------------------------------------------------

def check_sort(name, keys_in, out_keys, out_vals) -> None:
    """Keys ascending, payload a permutation of the record ids, and each
    key the input key of its record: together, exactly the sorted input."""
    import numpy as np
    keys_in = np.asarray(keys_in).reshape(-1)
    n = keys_in.shape[0]
    check(out_keys.shape == (n,) and out_vals.shape == (n,),
          f"{name}: output length {out_keys.shape} != {n}")
    check(bool(np.all(out_keys[1:] >= out_keys[:-1])),
          f"{name}: keys not ascending")
    seen = np.zeros(n, bool)
    seen[out_vals] = True
    check(seen.all(), f"{name}: payload is not a permutation")
    check(np.array_equal(keys_in[out_vals], out_keys),
          f"{name}: payload inconsistent with the keys")


def check_fk_join(name, out, s_keys, t_keys) -> None:
    import numpy as np
    s_keys, t_keys = np.asarray(s_keys), np.asarray(t_keys)
    valid = np.asarray(out.valid).reshape(-1)
    s_rows = np.asarray(out.s_rows).reshape(-1)[valid]
    t_rows = np.asarray(out.t_rows).reshape(-1)[valid]
    check(int(np.asarray(out.dropped).max()) == 0, f"{name}: dropped pairs")
    check(s_rows.shape[0] == s_keys.shape[0],
          f"{name}: {s_rows.shape[0]} pairs, want |S| = {s_keys.shape[0]}")
    seen = np.zeros(s_keys.shape[0], bool)
    seen[s_rows] = True
    check(seen.all(), f"{name}: an S row is missing from the result")
    check(np.array_equal(s_keys[s_rows], t_keys[t_rows]),
          f"{name}: a pair joins rows with different keys")


def same_bits(a, b) -> bool:
    import numpy as np
    a, b = np.asarray(a), np.asarray(b)
    if a.dtype != b.dtype or a.shape != b.shape:
        return False
    if a.dtype.kind == "f":
        a, b = a.view(np.uint32), b.view(np.uint32)
    return np.array_equal(a, b)


def same_answer(kind: str, a, b) -> bool:
    """Sort answers are (keys, values-or-None); joins are JoinOutputs."""
    if kind == "sort":
        (ka, va), (kb, vb) = a, b
        return same_bits(ka, kb) and (va is None) == (vb is None) and (
            va is None or same_bits(va, vb))
    return all(same_bits(x, y) for x, y in zip(a, b))


# ---------------------------------------------------------------------------
# phase bookkeeping
# ---------------------------------------------------------------------------

class Phase:
    """Wall time, compile count, peak bytes and dispatch counts of a phase."""

    compiles = 0
    cache_hits = 0
    compile_s = 0.0

    @classmethod
    def install_listeners(cls):
        from jax import monitoring

        def on_duration(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                cls.compiles += 1
                cls.compile_s += secs

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                cls.cache_hits += 1

        monitoring.register_event_duration_secs_listener(on_duration)
        monitoring.register_event_listener(on_event)

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        from repro.kernels import ops
        ops.reset_dispatch_counts()
        self.t0 = time.perf_counter()
        self.c0 = (Phase.compiles, Phase.cache_hits, Phase.compile_s)
        log(f"== phase {self.name}")
        return self

    def __exit__(self, exc_type, *_):
        import jax
        from repro.kernels import ops
        wall = time.perf_counter() - self.t0
        compiles = Phase.compiles - self.c0[0]
        hits = Phase.cache_hits - self.c0[1]
        comp_s = Phase.compile_s - self.c0[2]
        stats = jax.devices()[0].memory_stats() or {}
        log(f"phase {self.name}: {'FAILED' if exc_type else 'passed'} "
            f"wall_s={wall:.3f} compiles={compiles} "
            f"(persistent-cache hits {hits}, {comp_s:.3f}s compiling) "
            f"peak_bytes_in_use={stats.get('peak_bytes_in_use', 'n/a')}")
        log(f"phase {self.name} dispatch counts: "
            + json.dumps({f"{op}/{path}": c for (op, path), c
                          in sorted(ops.DISPATCH_COUNTS.items())}))
        return False


def report_line(name: str, report, seconds: float,
                compile_s: float = None) -> None:
    comp = "" if compile_s is None else f" ({compile_s:.3f}s compiling)"
    log(f"  {name}: {seconds:.3f}s{comp} alpha={report.alpha} "
        f"k_workload={report.k_workload:.4f} "
        f"k_network={report.k_network:.4f} "
        f"capacity_attempts={getattr(report, 'capacity_attempts', 1)}")


class Step:
    """Wall and compile seconds of one call inside a phase."""

    def __enter__(self):
        self.t0, self.c0 = time.perf_counter(), Phase.compile_s
        return self

    def __exit__(self, *_):
        self.wall = time.perf_counter() - self.t0
        self.compile_s = Phase.compile_s - self.c0
        return False


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def bulk_phase(sizes, seed: int) -> None:
    import jax
    from repro import cluster
    from repro.cluster import CapacityPolicy
    t, n = sizes["bulk_t"], sizes["bulk_n"]
    root = jax.random.key(seed)
    # Terasort's theorem capacity (5m + 1 receive slots per machine)
    # does not fit 2^27 records in 16 GB; its sampled boundaries stay
    # within 2m at this t, with gentle growth if a draw overflows.
    tera_policy = CapacityPolicy(base_factor=2.0, slack=1.0, growth=1.25,
                                 max_retries=2)
    with Phase("bulk"):
        for i, (skewed, algorithm) in enumerate(
                [(s, a) for s in (False, True)
                 for a in ("smms", "terasort")]):
            name = (f"sort {algorithm} {'zipf' if skewed else 'uniform'} "
                    f"n={n} t={t}")
            x, v = sort_input(jax.random.fold_in(root, i), t, n, skewed)
            with Step() as st:
                (keys, vals), rep = cluster.sort(
                    x, values=v, algorithm=algorithm,
                    policy=tera_policy if algorithm == "terasort" else None)
            report_line(name, rep, st.wall, st.compile_s)
            check_sort(name, x, keys, vals)
            del x, v, keys, vals
        s_keys, s_rows, t_keys, t_rows = fk_join_input(
            jax.random.fold_in(root, 99), sizes["join_s"], sizes["join_t"])
        name = (f"join statjoin |S|={sizes['join_s']} "
                f"|T|={sizes['join_t']} t={t}")
        with Step() as st:
            out, rep = cluster.join(s_keys, s_rows, t_keys, t_rows,
                                    algorithm="statjoin", t_machines=t)
        report_line(name, rep, st.wall, st.compile_s)
        check_fk_join(name, out, s_keys, t_keys)


def serve_specs(sizes, seed: int):
    import jax
    from repro.serve.query import join_query, sort_query
    t = sizes["serve_t"]
    root = jax.random.key(seed + 1)
    specs = []
    for i in range(SERVE_QUERIES):
        key = jax.random.fold_in(root, i)
        if i % 4 == 3:                               # a quarter are joins
            algorithm = ("statjoin", "randjoin")[(i // 4) % 2]
            # RandJoin replicates fragments (capacity 4x its share), so
            # it gets the smaller table to stay kernel-eligible
            mj = sizes["serve_join_m"] * (2 if algorithm == "statjoin" else 1)
            arrays = fk_join_input(key, t * mj, t * mj // 4)
            specs.append(join_query(*arrays, t_machines=t, tag=f"q{i}",
                                    algorithm=algorithm))
        else:
            m = sizes["serve_m"][i % 2]
            x, v = sort_input(key, t, t * m, skewed=bool(i % 3))
            specs.append(sort_query(
                x, values=v if i % 5 else None, tag=f"q{i}",
                algorithm=("smms", "terasort")[(i // 2) % 2]))
    return specs


def serve_phase(sizes, seed: int) -> None:
    from repro.kernels import ops
    from repro.serve.query import QueryEngine, run_spec
    specs = serve_specs(sizes, seed)
    pinned = ops.DEFAULT_BACKEND
    ops.DEFAULT_BACKEND = "pallas"
    try:
        with Phase("serve"):
            with QueryEngine(result_cache_size=0,
                             max_pending=len(specs)) as engine:
                t0 = time.perf_counter()
                results = engine.run(specs, timeout=1800)
                log(f"  engine served {len(results)} queries in "
                    f"{time.perf_counter() - t0:.3f}s")
            pallas_counts = dict(ops.DISPATCH_COUNTS)
            for r in results:
                check(r.ok, f"{r.spec.tag}: {r.error}")
                report_line(f"{r.spec.tag} {r.spec.kind} {r.algorithm}",
                            r.report, r.exec_s)
            fallbacks = {k: c for k, c in pallas_counts.items()
                         if k[1] == "reference"}
            check(not fallbacks, f"eligible operands took the reference "
                  f"path: {fallbacks}")
            check(any(p in ("pallas", "radix") for _, p in pallas_counts),
                  "no kernel dispatched")
        ops.DEFAULT_BACKEND = "reference"
        with Phase("serve-reference"):
            for r in results:
                want, _ = run_spec(r.spec)
                check(same_answer(r.spec.kind, r.value, want),
                      f"{r.spec.tag}: pallas answer != reference answer")
            log(f"  {len(results)} answers bitwise equal to the reference")
    finally:
        ops.DEFAULT_BACKEND = pinned


def mesh_phase(sizes, seed: int) -> None:
    import jax
    import numpy as np
    from repro import cluster
    from repro.cluster import ShardMapSubstrate, VmapSubstrate
    from repro.launch.mesh import STAGED_AXIS_NAMES

    class Recording(ShardMapSubstrate):
        """Keeps its last run's inputs and outputs for the checks."""

        def run(self, shard_fn, *args, donate_argnums=()):
            out, tape = super().run(shard_fn, *args,
                                    donate_argnums=donate_argnums)
            self.last = (list(self._compiled.values())[-1][0], args, out)
            return out, tape

    def check_spread(name, sub, want_all_to_all: bool) -> None:
        fn, args, out = sub.last
        devs = set()
        for leaf in jax.tree.leaves(out):
            devs |= set(leaf.sharding.device_set)
        check(len(devs) == 4, f"{name}: outputs on {len(devs)} device(s)")
        hlo = fn.lower(*args).compile().as_text()
        found = "all-to-all" in hlo
        log(f"  {name}: outputs on {len(devs)} devices, all-to-all in "
            f"compiled HLO: {found}")
        if want_all_to_all:
            check(found, f"{name}: no all-to-all in the compiled program")

    check(len(jax.devices()) == 4, f"--chips 4 needs 4 devices, found "
          f"{len(jax.devices())}")
    n = sizes["mesh_n"]
    root = jax.random.key(seed + 2)
    vmap_sort = "sort smms VmapSubstrate(4) one chip"
    vmap_join = "join statjoin VmapSubstrate(4) one chip"
    mesh_join = "join statjoin ShardMapSubstrate(4)"
    with Phase("mesh"):
        # uniform float32 keys: ~11 copies of each value at 2^27, so the
        # payload comparison also checks the order of tied keys
        x, v = sort_input(jax.random.fold_in(root, 0), 4, n, skewed=False)
        arrays = [np.asarray(a) for a in fk_join_input(
            jax.random.fold_in(root, 1), sizes["mesh_join_s"],
            sizes["mesh_join_t"])]
        meshes = {"flat": Recording(4),
                  "staged": Recording((STAGED_AXIS_NAMES[0], 2),
                                      (STAGED_AXIS_NAMES[1], 2)),
                  "join": Recording(4)}
        sort_name = {e: f"sort smms {e} ShardMapSubstrate{meshes[e].shape}"
                     for e in ("flat", "staged")}

        def sort_on(sub, exchange="flat"):
            return lambda: cluster.sort(x, values=v, algorithm="smms",
                                        substrate=sub, exchange=exchange)

        def join_on(sub):
            return lambda: cluster.join(*arrays, algorithm="statjoin",
                                        t_machines=4, substrate=sub,
                                        donate=False)

        legs = {vmap_sort: sort_on(VmapSubstrate(4, jit=True)),
                sort_name["flat"]: sort_on(meshes["flat"]),
                sort_name["staged"]: sort_on(meshes["staged"], "staged"),
                vmap_join: join_on(VmapSubstrate(4, jit=True)),
                mesh_join: join_on(meshes["join"])}

        def timed(fn):
            t0 = time.perf_counter()
            return fn(), time.perf_counter() - t0

        # compiling dominates at this size and XLA compiles each program
        # on its own thread, so the legs run concurrently
        done = {}
        with ThreadPoolExecutor(len(legs)) as pool:
            futures = {name: pool.submit(timed, fn)
                       for name, fn in legs.items()}
            for name, future in futures.items():
                done[name], wall = future.result()
                report_line(f"{name} (concurrent)", done[name][1], wall)

        (rk, rv), _ = done[vmap_sort]
        check_sort("vmap reference sort", x, rk, rv)
        for exchange in ("flat", "staged"):
            name = sort_name[exchange]
            (k, vv), rep = done[name]
            check(rep.exchange_topology == exchange,
                  f"{name}: ran {rep.exchange_topology}")
            check(same_bits(k, rk) and same_bits(vv, rv),
                  f"{name}: differs from VmapSubstrate(4)")
            check_spread(name, meshes[exchange], want_all_to_all=True)
        check_fk_join(mesh_join, done[mesh_join][0], arrays[0], arrays[2])
        check(all(same_bits(a, b) for a, b in
                  zip(done[vmap_join][0], done[mesh_join][0])),
              f"{mesh_join} differs from VmapSubstrate(4)")
        # StatJoin routes tuples on the host (the paper's planner runs in
        # the map setup), so its device program holds no exchange
        check_spread(mesh_join, meshes["join"], want_all_to_all=False)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip mesh phase")
    args = ap.parse_args(argv)

    from repro.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    import jax

    devices = jax.devices()
    log(f"jax.devices(): {devices}")
    log(f"compile cache: {cache_dir}")
    on_tpu = devices[0].platform == "tpu"
    sizes = SIZES[on_tpu]
    Phase.install_listeners()
    if args.chips == 4:
        mesh_phase(sizes, args.seed)
    else:
        bulk_phase(sizes, args.seed)
        serve_phase(sizes, args.seed)
    if not on_tpu:
        log(f"every phase passed at rehearsal size on "
            f"{devices[0].platform}; no TPU, so no result")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
