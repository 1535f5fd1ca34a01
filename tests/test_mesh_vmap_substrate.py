"""The front door lays its machines over every chip JAX sees.

With several devices the default pool builds a MeshVmapSubstrate:
shard_map over the chips, vmap over each chip's machines.  Its answers
and (alpha, k) reports must be bitwise those of ``VmapSubstrate(t)``.
The multi-device cases run in ONE subprocess with four forced host
devices (device-count overrides never happen in this process); each
test reads its case from that run.  The one-device cases run here.
"""
import functools
import json
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from repro import cluster
from repro.cluster import SubstratePool, VmapSubstrate
from repro.cluster.substrate import _stable_fn_key
from repro.kernels import ops

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import functools, json
import numpy as np
import jax
import jax.numpy as jnp

from repro import cluster
from repro.cluster import (MeshVmapSubstrate, SubstratePool, VmapSubstrate,
                           default_pool)
from repro.core.smms import smms_shard
from repro.data import zipf_tables
from repro.obs import Tracer
from repro.serve.query import QueryEngine, run_spec, sort_query

assert len(jax.devices()) == 4
out = {}
vmap_pool = SubstratePool(lambda *axes: VmapSubstrate(*axes, jit=True))


def sort_input(t, m=256, seed=0):
    rng = np.random.default_rng(seed + t)
    x = rng.permutation(t * m).astype(np.float32).reshape(t, m)
    return jnp.asarray(x), jnp.asarray(np.arange(t * m, dtype=np.int32)
                                       .reshape(t, m))


def same(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return a.dtype == b.dtype and a.shape == b.shape and bool(
        np.array_equal(a.view(np.uint8), b.view(np.uint8)))


def report_same(r1, r2):
    return (r1.alpha == r2.alpha and r1.k_workload == r2.k_workload
            and r1.k_network == r2.k_network
            and same(r1.workload, r2.workload))


for algo in ("smms", "terasort"):
    for t in (16, 8):
        x, v = sort_input(t)
        kw = {"seed": 3} if algo == "terasort" else {}
        (k1, v1), r1 = cluster.sort(x, values=v, algorithm=algo, **kw)
        (k2, v2), r2 = cluster.sort(x, values=v, algorithm=algo,
                                    substrate=vmap_pool, **kw)
        sub = default_pool()(t)
        flat = np.asarray(x).reshape(-1)
        out[f"{algo}_t{t}"] = {
            "substrate": type(sub).__name__,
            "layout": [sub.chips, sub.per_chip],
            "sorted": bool(np.array_equal(k1, np.sort(flat))),
            "carries_key": bool(
                np.array_equal(np.sort(v1), np.arange(flat.size))
                and np.array_equal(flat[v1], k1)),
            "keys_bitwise": same(k1, k2), "payload_bitwise": same(v1, v2),
            "report_bitwise": report_same(r1, r2), "alpha": r1.alpha}

# ---- StatJoin through the default pool ------------------------------------
ns = 400
s_keys, t_keys = zipf_tables(ns, ns, theta=0.3, seed=1)
rows = np.arange(ns)
o1, r1 = cluster.join(s_keys, rows, t_keys, rows, algorithm="statjoin",
                      t_machines=8)
o2, r2 = cluster.join(s_keys, rows, t_keys, rows, algorithm="statjoin",
                      t_machines=8, substrate=vmap_pool)
valid = np.asarray(o1.valid).reshape(-1)
got = set(zip(np.asarray(o1.s_rows).reshape(-1)[valid].tolist(),
              np.asarray(o1.t_rows).reshape(-1)[valid].tolist()))
want = {(i, j) for i, a in enumerate(s_keys) for j, b in enumerate(t_keys)
        if a == b}
out["statjoin"] = {
    "substrate": type(default_pool()(8)).__name__,
    "pairs": got == want,
    "bitwise": all(same(getattr(o1, f), getattr(o2, f))
                   for f in o1._fields),
    "report_bitwise": report_same(r1, r2)}

# ---- two-axis specs split their leading axis the same way -----------------
x, v = sort_input(16)
(ks, vs), rs = cluster.sort(x, values=v, algorithm="smms", exchange="staged")
(kf, vf), rf = cluster.sort(x, values=v, algorithm="smms")
(kv, vv), rv = cluster.sort(x, values=v, algorithm="smms", exchange="staged",
                            substrate=vmap_pool)
staged = default_pool()(("i1", 4), ("i2", 4))
o1, r1 = cluster.join(s_keys, rows, t_keys, rows, algorithm="randjoin",
                      t_machines=8, ab=(4, 2))
o2, r2 = cluster.join(s_keys, rows, t_keys, rows, algorithm="randjoin",
                      t_machines=8, ab=(4, 2), substrate=vmap_pool)
out["two_axis"] = {
    "staged": [type(staged).__name__, staged.chips, rs.exchange_topology],
    "staged_bitwise": same(ks, kv) and same(vs, vv) and same(ks, kf)
    and report_same(rs, rv),
    "randjoin": type(default_pool()(("a", 4), ("b", 2))).__name__,
    "randjoin_bitwise": all(same(getattr(o1, f), getattr(o2, f))
                            for f in o1._fields) and report_same(r1, r2)}

# ---- one serving query through the engine's own pool ----------------------
x, v = sort_input(8, seed=7)
spec = sort_query(x, values=v, algorithm="smms")
pool = SubstratePool()
with QueryEngine(pool=pool) as eng:
    res = eng.run([spec])[0]
(wk, wv), wrep = run_spec(spec, substrate=vmap_pool)
gk, gv = res.value
out["query"] = {
    "ok": bool(res.ok),
    "substrates": sorted({type(s).__name__ for s in pool.substrates()}),
    "keys_bitwise": same(gk, wk), "payload_bitwise": same(gv, wv),
    "report_bitwise": report_same(res.report, wrep)}

# ---- t = 6 on 4 devices: the divisor rule ----------------------------------
x, v = sort_input(6)
(k6, v6), r6 = cluster.sort(x, values=v, algorithm="smms")
sub6 = default_pool()(6)
out["t6"] = {"substrate": type(sub6).__name__,
             "layout": [sub6.chips, sub6.per_chip],
             "sorted": bool(np.array_equal(
                 k6, np.sort(np.asarray(x).reshape(-1))))}

# ---- where the outputs live, the program's all-to-all, the spans ----------
t = 16
x, _ = sort_input(t)
sub = MeshVmapSubstrate(("i", t), chips=4)
body = functools.partial(smms_shard, axis_name=sub.axis_name, t=t, r=2)
tracer = Tracer(enabled=True)
with tracer.trace("unplaced") as root:
    res, tape = sub.run(body, x)
runs = root.find("substrate.run")
placed = jax.device_put(x, sub.sharding)
with tracer.trace("placed") as root2:
    sub.run(body, placed)
(fn, _), = sub._compiled.values()
hlo = fn.lower(placed).compile().as_text()
out["layout"] = {
    "axis_name": list(sub.axis_name),
    "devices": len(res.keys.devices()),
    "all_to_all": "all-to-all" in hlo,
    "place_spans": [len(root.find("substrate.place")),
                    len(root2.find("substrate.place"))],
    "run_attrs": {k: runs[0].attrs[k] for k in ("chips",
                                                 "machines_per_chip")},
    "compiles": sub.stats_snapshot().get("compiles", 0)}
print("RESULTS " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def four_devices():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULTS ")][-1]
    return json.loads(line[len("RESULTS "):])


@pytest.mark.parametrize("case", ["smms_t16", "smms_t8", "terasort_t16",
                                  "terasort_t8"])
def test_sort_over_four_chips_matches_vmap(four_devices, case):
    got = four_devices[case]
    t = int(case.split("_t")[1])
    assert got["substrate"] == "MeshVmapSubstrate"
    assert got["layout"] == [4, t // 4]
    assert got["sorted"] and got["carries_key"]
    assert got["keys_bitwise"] and got["payload_bitwise"]
    assert got["report_bitwise"] and got["alpha"] == 3


def test_statjoin_over_four_chips_matches_vmap(four_devices):
    got = four_devices["statjoin"]
    assert got["substrate"] == "MeshVmapSubstrate"
    assert got["pairs"] and got["bitwise"] and got["report_bitwise"]


def test_two_axis_specs_split_their_leading_axis(four_devices):
    got = four_devices["two_axis"]
    assert got["staged"] == ["MeshVmapSubstrate", 4, "staged"]
    assert got["staged_bitwise"]
    assert got["randjoin"] == "MeshVmapSubstrate"
    assert got["randjoin_bitwise"]


def test_serving_query_through_engine_pool(four_devices):
    got = four_devices["query"]
    assert got["ok"] and got["substrates"] == ["MeshVmapSubstrate"]
    assert got["keys_bitwise"] and got["payload_bitwise"]
    assert got["report_bitwise"]


def test_six_machines_on_four_devices_use_two_chips(four_devices):
    got = four_devices["t6"]
    assert got["substrate"] == "MeshVmapSubstrate"
    assert got["layout"] == [2, 3]
    assert got["sorted"]


def test_outputs_on_four_devices_and_program_has_all_to_all(four_devices):
    got = four_devices["layout"]
    assert got["axis_name"] == ["chip", "i"]
    assert got["devices"] == 4
    assert got["all_to_all"]
    assert got["run_attrs"] == {"chips": 4, "machines_per_chip": 4}
    assert got["compiles"] == 1          # the placed call reused it


def test_placement_span_only_for_unplaced_input(four_devices):
    assert four_devices["layout"]["place_spans"] == [1, 0]


def test_one_device_pool_is_the_vmap_substrate():
    pool = SubstratePool()
    sub = pool(8)
    assert type(sub) is VmapSubstrate and sub._jit
    assert sub.axis_name == "i"
    x = jnp.asarray(np.random.default_rng(0).permutation(8 * 64)
                    .astype(np.float32).reshape(8, 64))
    cluster.sort(x, algorithm="smms", substrate=pool)
    (key,) = sub._compiled
    from repro.core.smms import default_cap_factor, smms_shard
    body = functools.partial(
        smms_shard, axis_name="i", t=8, r=2,
        cap_factor=float(default_cap_factor(8 * 64, 8, 2)),
        backend="static")
    assert key == (_stable_fn_key(body), ops.program_key(), (),
                   (((8, 64), "float32"),))
