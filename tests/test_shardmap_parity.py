"""The same per-device bodies must run under shard_map on a real mesh.

Unit tests emulate machines with vmap; production uses shard_map.  This
test launches a subprocess with XLA_FLAGS forcing 8 host devices (per the
dry-run rules, device-count overrides never happen in THIS process) and
drives everything through the cluster substrate: SMMS / Terasort /
RandJoin / StatJoin on a ShardMapSubstrate, checked for exact parity
(sorted output, join pairs, AlphaKReport k's) against the VmapSubstrate
run of the identical input.  The ragged exchange backend is checked at
the lowering level.
"""
import os
import subprocess
import sys

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np
import jax
import jax.numpy as jnp

from repro import cluster
from repro.cluster import ShardMapSubstrate, VmapSubstrate
from repro.launch.mesh import make_mesh
from repro.data import uniform_keys, zipf_tables

t, m = 8, 512
assert len(jax.devices()) == 8
x = jnp.asarray(uniform_keys(t * m, seed=42).reshape(t, m))

# ---- SMMS: vmap vs shard_map parity (output AND instrumented report) ------
(kv, _), rep_v = cluster.sort(x, algorithm="smms", substrate=VmapSubstrate(t))
(ks, _), rep_s = cluster.sort(x, algorithm="smms",
                              substrate=ShardMapSubstrate(t))
np.testing.assert_array_equal(np.asarray(kv), np.asarray(ks))
np.testing.assert_array_equal(np.sort(np.asarray(x).reshape(-1)), ks)
assert rep_v.k_workload == rep_s.k_workload, (rep_v.summary(), rep_s.summary())
assert rep_v.k_network == rep_s.k_network
assert rep_v.alpha == rep_s.alpha == 3
print("SMMS substrate parity OK:", rep_s.summary())

# ---- Terasort -------------------------------------------------------------
(kv, _), rep_v = cluster.sort(x, algorithm="terasort", seed=0,
                              substrate=VmapSubstrate(t))
(ks, _), rep_s = cluster.sort(x, algorithm="terasort", seed=0,
                              substrate=ShardMapSubstrate(t))
np.testing.assert_array_equal(np.asarray(kv), np.asarray(ks))
assert rep_v.k_workload == rep_s.k_workload
print("Terasort substrate parity OK:", rep_s.summary())

# ---- staged exchange on a real (i1, i2) = (4, 2) mesh ---------------------
(kf, _), rep_f = cluster.sort(x, algorithm="smms",
                              substrate=ShardMapSubstrate(t))
(kv2, _), rep_v2 = cluster.sort(x, algorithm="smms", exchange="staged",
                                substrate=VmapSubstrate(("i1", 4), ("i2", 2)))
(ks2, _), rep_s2 = cluster.sort(x, algorithm="smms", exchange="staged",
                                substrate=ShardMapSubstrate(("i1", 4),
                                                            ("i2", 2)))
np.testing.assert_array_equal(np.asarray(kv2), np.asarray(ks2))
np.testing.assert_array_equal(np.asarray(kf), np.asarray(ks2))
assert rep_s2.exchange_topology == "staged"
assert rep_v2.k_workload == rep_s2.k_workload == rep_f.k_workload
assert rep_v2.k_network == rep_s2.k_network
assert rep_v2.alpha == rep_s2.alpha == 4
print("SMMS staged-exchange mesh parity OK:", rep_s2.summary())

# ---- ragged backend: lowers (execution is TPU-only) -----------------------
from jax.sharding import PartitionSpec as P
from repro.core.smms import smms_shard
mesh = make_mesh((t,), ("i",))
def body(xl):
    res = smms_shard(xl[0], axis_name="i", t=t, r=2, backend="ragged")
    return res.keys[None]
txt = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P("i"),),
                            out_specs=P("i"), check_vma=False)
              ).lower(x).as_text()
assert "ragged" in txt, "expected ragged-all-to-all in lowered HLO"
print("ragged backend lowers OK (execution is TPU-only)")

# ---- RandJoin on a real 2D (a, b) mesh ------------------------------------
a, b = 2, 4
ns = 160
s_keys, t_keys = zipf_tables(ns, ns, theta=0.2, seed=1)
rows = np.arange(ns)
def oracle(sk, tk):
    out = set(); byk = {}
    for j, k in enumerate(tk): byk.setdefault(int(k), []).append(j)
    for i, k in enumerate(sk):
        for j in byk.get(int(k), ()): out.add((i, j))
    return out
def pairs(out):
    v = np.asarray(out.valid).reshape(-1)
    return set(zip(np.asarray(out.s_rows).reshape(-1)[v].tolist(),
                   np.asarray(out.t_rows).reshape(-1)[v].tolist()))
want = oracle(s_keys, t_keys)
out, rep = cluster.join(s_keys, rows, t_keys, rows, algorithm="randjoin",
                        t_machines=a * b, ab=(a, b),
                        substrate=ShardMapSubstrate(("a", a), ("b", b)))
assert pairs(out) == want, (len(pairs(out)), len(want))
assert int(np.asarray(out.dropped).max()) == 0
assert rep.alpha == 1
print("RandJoin 2D-mesh OK:", rep.summary())

# ---- StatJoin on the mesh -------------------------------------------------
out, rep = cluster.join(s_keys, rows, t_keys, rows, algorithm="statjoin",
                        t_machines=t, substrate=ShardMapSubstrate(t))
assert pairs(out) == want
assert rep.alpha == 3
print("StatJoin mesh OK:", rep.summary())
print("ALL_SHARD_MAP_PARITY_OK")
"""


def test_shardmap_parity():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr
    assert "ALL_SHARD_MAP_PARITY_OK" in proc.stdout
