"""Radix-sort kernel family vs bitonic vs jnp — bitwise, adversarial.

The radix path promises MORE than the bitonic one: bitwise parity with
``jnp.sort`` / stable ``jnp.argsort`` for *every* bit pattern — negative
ints at the int32 extremes, +-inf, NaNs of either sign and any payload,
-0.0, denormals — because the key bijection plus the equivalence-class
canonicalization reproduce XLA's comparator exactly (see
repro.kernels.radix).  These tests drive that contract through the raw
kernel, the ops dispatch layer (both kernel families forced in turn,
both dispatch backends), and the cluster front door end-to-end.

Float comparisons are on *bit views* (uint32/uint16), not values — NaN
!= NaN would otherwise vacuously pass the rows that matter most.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from _prop import given, settings, st

from repro.kernels import ops
from repro.kernels.radix import (DEFAULT_RADIX_BITS, bits_to_key, key_bits,
                                 key_to_bits, radix_sort)

N_CASES = 8


def _bits_view(a: np.ndarray) -> np.ndarray:
    """Bit-pattern view for exact comparison (floats: NaN-safe)."""
    if a.dtype == np.float32:
        return a.view(np.uint32)
    if a.dtype.itemsize == 2:          # bfloat16 (ml_dtypes)
        return a.view(np.uint16)
    return a


def adversarial_keys(dtype, case: int, n: int, seed: int) -> np.ndarray:
    """One of N_CASES key vectors designed to break radix sorts."""
    rng = np.random.default_rng(seed)
    case = case % N_CASES
    if dtype == np.int32:
        if case == 0:                               # full-range incl. extremes
            x = rng.integers(-2**31, 2**31, size=n,
                             dtype=np.int64).astype(np.int32)
            x[rng.integers(0, n, size=max(1, n // 8))] = np.int32(-2**31)
            x[rng.integers(0, n, size=max(1, n // 8))] = np.int32(2**31 - 1)
            return x
        if case == 1:                               # negative-heavy duplicates
            return rng.choice(np.int32([-7, -1, 0, 3]), size=n)
        if case == 2:
            return np.full(n, np.int32(-42))        # all equal, negative
        if case == 3:                               # presorted
            return np.sort(rng.integers(-1000, 1000, size=n).astype(np.int32))
        if case == 4:                               # reverse sorted
            return np.sort(rng.integers(-1000, 1000,
                                        size=n).astype(np.int32))[::-1].copy()
        if case == 5:                               # one digit varies (LSD)
            return (rng.integers(0, 16, size=n) - 8).astype(np.int32)
        if case == 6:                               # high digits only
            return (rng.integers(-8, 8, size=n).astype(np.int32) << 28)
        return rng.integers(-5, 5, size=n).astype(np.int32)
    # float32 / bfloat16: build f32 then cast (adversarial values survive)
    if case == 0:
        x = rng.normal(size=n).astype(np.float32)
    elif case == 1:                                 # heavy duplicates
        x = rng.choice(np.float32([-1.5, 0.0, 2.25]), size=n)
    elif case == 2:
        x = np.full(n, np.float32(-3.75))           # all equal, negative
    elif case == 3:
        x = np.sort(rng.normal(size=n)).astype(np.float32)
    elif case == 4:
        x = np.sort(rng.normal(size=n))[::-1].astype(np.float32).copy()
    elif case == 5:                                 # +-inf sentinels mixed in
        x = rng.normal(size=n).astype(np.float32)
        x[rng.integers(0, n, size=max(1, n // 8))] = np.inf
        x[rng.integers(0, n, size=max(1, n // 8))] = -np.inf
    elif case == 6:                                 # NaNs both signs + zeros
        x = rng.normal(size=n).astype(np.float32)
        x[rng.integers(0, n, size=max(1, n // 8))] = np.nan
        x[rng.integers(0, n, size=max(1, n // 8))] = -np.nan
        x[rng.integers(0, n, size=max(1, n // 8))] = -0.0
        x[rng.integers(0, n, size=max(1, n // 8))] = 0.0
    else:                                           # raw bit soup: every class
        x = rng.integers(0, 2**32, size=n,
                         dtype=np.uint64).astype(np.uint32).view(np.float32)
    if dtype == jnp.bfloat16:
        return np.asarray(jnp.asarray(x).astype(jnp.bfloat16))
    return x


DTYPES = [np.int32, np.float32, jnp.bfloat16]
DTYPE_IDS = ["int32", "float32", "bfloat16"]


# ---------------------------------------------------------------------------
# raw kernel vs jnp oracle: sort AND stable-argsort parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", DTYPES, ids=DTYPE_IDS)
@pytest.mark.parametrize("case", range(N_CASES))
@pytest.mark.parametrize("rows,n", [(1, 7), (3, 100), (4, 257), (2, 1024)])
def test_radix_vs_jnp_adversarial(dtype, case, rows, n):
    x = jnp.asarray(np.stack([adversarial_keys(dtype, case, n, seed=case
                                               * 31 + r) for r in
                              range(rows)]))
    got, order = radix_sort(x)
    np.testing.assert_array_equal(
        _bits_view(np.asarray(got)),
        _bits_view(np.asarray(jnp.sort(x, axis=-1))))
    np.testing.assert_array_equal(
        np.asarray(order), np.asarray(jnp.argsort(x, axis=-1, stable=True)))


@pytest.mark.parametrize("dtype", DTYPES, ids=DTYPE_IDS)
def test_radix_vs_bitonic(dtype):
    """The two kernel families agree bitwise (NaN-free inputs: bitonic's
    contract excludes NaN, radix's does not)."""
    from repro.kernels.bitonic import bitonic_sort
    x = jnp.asarray(np.stack([adversarial_keys(dtype, c, 200, seed=c)
                              for c in (0, 1, 3, 4, 5)]))
    if dtype != np.int32:
        x = jnp.where(jnp.isnan(x), jnp.zeros_like(x), x)
    got, _ = radix_sort(x)
    if dtype == np.int32:
        ref = jnp.sort(x, axis=-1)  # bitonic sorts float/bf16 keys only
    else:
        ref = bitonic_sort(x)
    np.testing.assert_array_equal(_bits_view(np.asarray(got)),
                                  _bits_view(np.asarray(ref)))


@settings(max_examples=15, deadline=None)
@given(st.integers(1, 5), st.integers(1, 300), st.integers(0, 2**31 - 1))
def test_property_radix_float32(rows, n, seed):
    raw = np.random.default_rng(seed).integers(
        0, 2**32, size=(rows, n), dtype=np.uint64).astype(np.uint32)
    x = jnp.asarray(raw.view(np.float32))   # every IEEE class, raw bits
    got, order = radix_sort(x)
    np.testing.assert_array_equal(
        _bits_view(np.asarray(got)),
        _bits_view(np.asarray(jnp.sort(x, axis=-1))))
    np.testing.assert_array_equal(
        np.asarray(order), np.asarray(jnp.argsort(x, axis=-1, stable=True)))


def test_radix_block_rows_pad():
    """Row counts that don't divide block_rows pad internally and the pad
    rows never leak into the output."""
    x = jnp.asarray(np.random.default_rng(0).normal(
        size=(5, 65)).astype(np.float32))
    got, order = radix_sort(x, block_rows=4)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(jnp.sort(x, axis=-1)))
    assert got.shape == x.shape and order.shape == x.shape


# ---------------------------------------------------------------------------
# key bijections: round-trip + order preservation
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", DTYPES, ids=DTYPE_IDS)
def test_key_bits_roundtrip(dtype):
    """bits_to_key(key_to_bits(x)) is the identity on BIT PATTERNS —
    NaN payloads and -0.0 included."""
    rng = np.random.default_rng(7)
    kb = key_bits(jnp.dtype(np.dtype(dtype) if dtype != jnp.bfloat16
                            else jnp.bfloat16))
    if dtype == np.int32:
        x = jnp.asarray(rng.integers(0, 2**32, size=2048,
                                     dtype=np.uint64).astype(
                                         np.uint32).view(np.int32))
    elif dtype == np.float32:
        x = jnp.asarray(rng.integers(0, 2**32, size=2048,
                                     dtype=np.uint64).astype(
                                         np.uint32).view(np.float32))
    else:
        import ml_dtypes
        x = jnp.asarray(rng.integers(0, 2**16, size=2048,
                                     dtype=np.uint64).astype(
                                         np.uint16).view(ml_dtypes.bfloat16))
    bits = key_to_bits(x)
    assert bits.dtype == jnp.uint32
    assert int(jnp.max(bits)) < (1 << kb)
    back = bits_to_key(bits, x.dtype)
    np.testing.assert_array_equal(_bits_view(np.asarray(back)),
                                  _bits_view(np.asarray(x)))


@pytest.mark.parametrize("dtype", [np.int32, np.float32], ids=["int32",
                                                               "float32"])
def test_key_bits_monotone(dtype):
    """Unsigned bit order == key order on comparable keys.  The raw
    bijection is monotone only OUTSIDE XLA's equivalence classes
    (-0.0==+0.0, flushed denormals) — those are canonicalized later by
    _sort_ready_bits, so this test uses class-free keys."""
    rng = np.random.default_rng(11)
    if dtype == np.int32:
        x = rng.integers(-2**31, 2**31, size=512,
                         dtype=np.int64).astype(np.int32)
    else:
        x = rng.normal(size=512).astype(np.float32) * 1e10
        x[:6] = [np.inf, -np.inf, 0.0, 3.5, -3.5, 1.0]
    xs = np.unique(np.sort(x, kind="stable"))
    bits = np.asarray(key_to_bits(jnp.asarray(xs))).astype(np.uint64)
    assert (np.diff(bits.astype(np.int64)) >= 0).all(), (
        "bijected bits must be monotone in key order")


def test_key_bits_rejects_unsupported():
    with pytest.raises(TypeError):
        key_bits(jnp.float64)
    with pytest.raises(TypeError):
        key_to_bits(jnp.zeros((4,), jnp.float16))


# ---------------------------------------------------------------------------
# ops dispatch: forced families, kv carry, both backends
# ---------------------------------------------------------------------------

def test_sort_dispatch_forced_radix_ticks():
    x = jnp.asarray(np.random.default_rng(1).normal(
        size=(4, 300)).astype(np.float32))
    ops.reset_dispatch_counts()
    with ops.force_sort_kernel("radix"):
        got = ops.sort(x, backend="pallas")
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(jnp.sort(x, axis=-1)))
    assert ops.DISPATCH_COUNTS.get(("sort", "radix")) == 1
    # reference backend never routes to a kernel family
    ops.reset_dispatch_counts()
    ref = ops.sort(x, backend="reference")
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))
    assert ops.DISPATCH_COUNTS.get(("sort", "reference")) == 1
    assert ("sort", "radix") not in ops.DISPATCH_COUNTS


def test_sort_kv_radix_carries_values_stably():
    """Duplicate keys: the payload must ride the STABLE permutation —
    radix carries values through one gather of the argsort order."""
    rng = np.random.default_rng(2)
    keys = jnp.asarray(rng.choice(np.float32([-2.0, 0.5, 7.0]), size=500))
    values = jnp.arange(500, dtype=jnp.int32)
    with ops.force_sort_kernel("radix"):
        ks, vs = ops.sort_kv(keys, values, backend="pallas")
    order = np.asarray(jnp.argsort(keys, stable=True))
    np.testing.assert_array_equal(np.asarray(ks), np.asarray(keys)[order])
    np.testing.assert_array_equal(np.asarray(vs), np.asarray(values)[order])
    assert ops.DISPATCH_COUNTS.get(("sort_kv", "radix"), 0) >= 1


@pytest.mark.parametrize("family", ["bitonic", "radix"])
def test_sort_partition_families_agree(family):
    """sort_partition / sort_partition_kv under each forced family match
    the reference backend bitwise (radix has no fused radix+search
    kernel: the dispatcher splits into sort + searchsorted)."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=600).astype(np.float32))
    interior = jnp.asarray(np.sort(rng.normal(size=7).astype(np.float32)))
    values = jnp.arange(600, dtype=jnp.int32)
    ref_sp = ops.sort_partition(x, interior, backend="reference")
    ref_spkv = ops.sort_partition_kv(x, values, interior,
                                     backend="reference")
    with ops.force_sort_kernel(family):
        sp = ops.sort_partition(x, interior, backend="pallas")
        spkv = ops.sort_partition_kv(x, values, interior, backend="pallas")
    for got, ref in list(zip(sp, ref_sp)) + list(zip(spkv, ref_spkv)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_sort_kernel_choice_cost_model(monkeypatch):
    """The pallas sort family is the bitonic network on every platform
    and at every width; only force_sort_kernel picks radix, and the
    force is scoped to its context."""
    for plat in ("cpu", "tpu"):
        monkeypatch.setattr(ops, "platform", lambda: plat)
        assert ops.sort_kernel_choice() == "bitonic"
        with ops.force_sort_kernel("radix"):
            assert ops.sort_kernel_choice() == "radix"
            with ops.force_sort_kernel(None):
                assert ops.sort_kernel_choice() == "bitonic"
            assert ops.sort_kernel_choice() == "radix"
        assert ops.sort_kernel_choice() == "bitonic"
    with pytest.raises(ValueError):
        with ops.force_sort_kernel("quantum"):
            pass


# ---------------------------------------------------------------------------
# end-to-end: the cluster front door under the forced radix family
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("algorithm,budget_key", [("smms", "smms_radix"),
                                                  ("terasort",
                                                   "terasort_radix")])
def test_cluster_sort_forced_radix_parity(algorithm, budget_key,
                                          kernel_path):
    from benchmarks.bench_sort import DISPATCH_BUDGET, KERNEL_PATHS
    from repro import cluster
    from repro.cluster.substrate import reset_default_pool
    from repro.data import uniform_keys

    t, m = 4, 256
    x = jnp.asarray(uniform_keys(t * m, seed=21).reshape(t, m))
    reset_default_pool()
    kernel_path("reference")
    (ref_keys, _), _ = cluster.sort(x, algorithm=algorithm)
    reset_default_pool()
    ops.reset_dispatch_counts()
    kernel_path("pallas")
    with ops.force_sort_kernel("radix"):
        (keys, _), rep = cluster.sort(x, algorithm=algorithm)
    np.testing.assert_array_equal(np.asarray(keys), np.asarray(ref_keys))
    radix_ticks = sum(c for (op, path), c in ops.DISPATCH_COUNTS.items()
                      if path == "radix")
    assert radix_ticks >= 1, dict(ops.DISPATCH_COUNTS)
    kernel_ticks = sum(c for (op, path), c in ops.DISPATCH_COUNTS.items()
                       if path in KERNEL_PATHS)
    assert 0 < kernel_ticks <= DISPATCH_BUDGET[budget_key], (
        budget_key, dict(ops.DISPATCH_COUNTS))
    reset_default_pool()
