"""Differential harness: ops.* kernel path vs jnp oracles, bitwise.

Every op in the dispatch layer (repro.kernels.ops) promises that the
Pallas path is *bitwise identical* to the reference path.  These tests
drive both through adversarial inputs — duplicates, all-equal, presorted,
reverse-sorted, +-inf sentinels, non-power-of-two lengths, int32 and
float32 keys — via the _prop shim so they run with or without hypothesis.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from _prop import given, settings, st

from repro.kernels import ops

N_CASES = 7


def adversarial_f32(case: int, n: int, seed: int) -> np.ndarray:
    """One of N_CASES float32 key vectors designed to break sorts."""
    rng = np.random.default_rng(seed)
    case = case % N_CASES
    if case == 0:
        return rng.normal(size=n).astype(np.float32)
    if case == 1:                                   # heavy duplicates
        return rng.choice(np.float32([-1.5, 0.0, 2.25]), size=n)
    if case == 2:                                   # all equal
        return np.full(n, 3.75, np.float32)
    if case == 3:                                   # presorted
        return np.sort(rng.normal(size=n)).astype(np.float32)
    if case == 4:                                   # reverse sorted
        return np.sort(rng.normal(size=n))[::-1].astype(np.float32)
    if case == 5:                                   # +-inf sentinels mixed in
        x = rng.normal(size=n).astype(np.float32)
        x[rng.integers(0, n, size=max(1, n // 8))] = np.inf
        x[rng.integers(0, n, size=max(1, n // 8))] = -np.inf
        return x
    x = rng.normal(size=n).astype(np.float32)       # near-sorted with swaps
    x.sort()
    for _ in range(max(1, n // 16)):
        i, j = rng.integers(0, n, size=2)
        x[i], x[j] = x[j], x[i]
    return x


def adversarial_i32(case: int, n: int, seed: int) -> np.ndarray:
    """int32 variant, including iinfo.max (the MASKED_KEY sentinel)."""
    rng = np.random.default_rng(seed)
    case = case % N_CASES
    big = np.iinfo(np.int32).max
    if case == 0:
        return rng.integers(-1000, 1000, size=n).astype(np.int32)
    if case == 1:
        return rng.choice(np.int32([-7, 0, 3]), size=n)
    if case == 2:
        return np.full(n, 42, np.int32)
    if case == 3:
        return np.sort(rng.integers(-50, 50, size=n)).astype(np.int32)
    if case == 4:
        return np.sort(rng.integers(-50, 50, size=n))[::-1].astype(np.int32)
    if case == 5:                                   # sentinel collisions
        x = rng.integers(-10, 10, size=n).astype(np.int32)
        x[rng.integers(0, n, size=max(1, n // 4))] = big
        return x
    x = rng.integers(-5, 5, size=n).astype(np.int32)
    x[0] = np.iinfo(np.int32).min
    x[-1] = big
    return x


# ---------------------------------------------------------------------------
# ops.sort
# ---------------------------------------------------------------------------

@settings(max_examples=14, deadline=None)
@given(st.integers(0, N_CASES - 1), st.integers(1, 300),
       st.integers(0, 2**31 - 1))
def test_sort_differential_f32(case, n, seed):
    x = adversarial_f32(case, n, seed)
    got = ops.sort(jnp.asarray(x), backend="pallas")
    np.testing.assert_array_equal(np.asarray(got), np.sort(x))


@settings(max_examples=14, deadline=None)
@given(st.integers(0, N_CASES - 1), st.integers(1, 300),
       st.integers(0, 2**31 - 1))
def test_sort_differential_i32(case, n, seed):
    x = adversarial_i32(case, n, seed)
    got = ops.sort(jnp.asarray(x), backend="pallas")
    np.testing.assert_array_equal(np.asarray(got), np.sort(x))


def test_sort_2d_rows():
    x = np.stack([adversarial_f32(c, 100, c) for c in range(N_CASES)])
    got = ops.sort(jnp.asarray(x), backend="pallas")
    np.testing.assert_array_equal(np.asarray(got), np.sort(x, axis=-1))


def test_sort_nan_keys_never_corrupt_neighbours():
    """NaN keys are outside the bitwise-parity contract (jnp.sort moves
    them last; a comparison network cannot order them), but they must
    not destroy other keys: the kernel returns a permutation of the
    input — regression for min/max compare-exchange propagating one NaN
    over the whole row."""
    rng = np.random.default_rng(21)
    x = rng.normal(size=50).astype(np.float32)
    x[7] = np.nan
    x[23] = np.nan
    got = np.asarray(ops.sort(jnp.asarray(x), backend="pallas"))
    assert np.isnan(got).sum() == 2
    np.testing.assert_array_equal(np.sort(got[~np.isnan(got)]),
                                  np.sort(x[~np.isnan(x)]))


# ---------------------------------------------------------------------------
# ops.sort_kv — stability under key ties is the contract
# ---------------------------------------------------------------------------

@settings(max_examples=14, deadline=None)
@given(st.integers(0, N_CASES - 1), st.integers(1, 300),
       st.integers(0, 2**31 - 1))
def test_sort_kv_differential_stable(case, n, seed):
    keys = adversarial_i32(case, n, seed)
    vals = np.arange(n, dtype=np.int32)              # distinct: detects order
    gk, gv = ops.sort_kv(jnp.asarray(keys), jnp.asarray(vals),
                         backend="pallas")
    order = np.argsort(keys, kind="stable")
    np.testing.assert_array_equal(np.asarray(gk), keys[order])
    np.testing.assert_array_equal(np.asarray(gv), vals[order])


def test_sort_kv_float_keys_payload_matrix():
    """Trailing payload dims ride along; ties keep input order."""
    rng = np.random.default_rng(5)
    keys = rng.choice(np.float32([0.0, 1.0, np.inf]), size=65)
    vals = rng.normal(size=(65, 3)).astype(np.float32)
    gk, gv = ops.sort_kv(jnp.asarray(keys), jnp.asarray(vals),
                         backend="pallas")
    order = np.argsort(keys, kind="stable")
    np.testing.assert_array_equal(np.asarray(gk), keys[order])
    np.testing.assert_array_equal(np.asarray(gv), vals[order])


# ---------------------------------------------------------------------------
# ops.searchsorted
# ---------------------------------------------------------------------------

@settings(max_examples=14, deadline=None)
@given(st.integers(0, N_CASES - 1), st.integers(1, 200),
       st.integers(1, 200), st.integers(0, 2**31 - 1))
def test_searchsorted_differential(case, na, nq, seed):
    a = np.sort(adversarial_i32(case, na, seed))
    q = adversarial_i32((case + 3) % N_CASES, nq, seed + 1)
    for side in ("left", "right"):
        got = ops.searchsorted(jnp.asarray(a), jnp.asarray(q), side=side,
                               backend="pallas")
        np.testing.assert_array_equal(np.asarray(got),
                                      np.searchsorted(a, q, side=side))


def test_searchsorted_float_inf_queries():
    a = np.sort(adversarial_f32(5, 120, 7))          # contains +-inf
    q = np.float32([-np.inf, np.inf, 0.0, a[3], a[60]])
    for side in ("left", "right"):
        got = ops.searchsorted(jnp.asarray(a), jnp.asarray(q), side=side,
                               backend="pallas")
        np.testing.assert_array_equal(np.asarray(got),
                                      np.searchsorted(a, q, side=side))


# ---------------------------------------------------------------------------
# ops.merge_sorted_rows / _kv — the Round-3 receive-side merge
# ---------------------------------------------------------------------------

@settings(max_examples=14, deadline=None)
@given(st.integers(1, 9), st.integers(1, 60), st.integers(0, 2**31 - 1))
def test_merge_sorted_rows_differential(t, c, seed):
    rng = np.random.default_rng(seed)
    x = np.sort(rng.normal(size=(t, c)).astype(np.float32), axis=1)
    # inf tails, as the sentinel-padded exchange buffer has
    for i in range(t):
        x[i, rng.integers(0, c + 1):] = np.inf
    got = ops.merge_sorted_rows(jnp.asarray(x), backend="pallas")
    np.testing.assert_array_equal(np.asarray(got), np.sort(x.reshape(-1)))


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 8), st.integers(1, 40), st.integers(0, 2**31 - 1))
def test_merge_sorted_rows_kv_stable(t, c, seed):
    rng = np.random.default_rng(seed)
    keys = np.sort(rng.integers(0, 5, size=(t, c)), axis=1).astype(np.int32)
    vals = np.arange(t * c, dtype=np.int32).reshape(t, c)
    gk, gv = ops.merge_sorted_rows_kv(jnp.asarray(keys), jnp.asarray(vals),
                                      backend="pallas")
    order = np.argsort(keys.reshape(-1), kind="stable")
    np.testing.assert_array_equal(np.asarray(gk), keys.reshape(-1)[order])
    np.testing.assert_array_equal(np.asarray(gv), vals.reshape(-1)[order])


# ---------------------------------------------------------------------------
# reference path: the payload rides through one stable XLA sort
# ---------------------------------------------------------------------------

def _tie_keys(dtype, shape, seed):
    """Heavy ties, -0.0 beside +0.0, +-inf, and a PAD (sentinel) tail on
    every row of the last axis."""
    rng = np.random.default_rng(seed)
    if dtype == np.float32:
        pool = np.float32([-np.inf, -1.5, -0.0, 0.0, 2.25, np.inf])
        pad = np.float32(np.inf)
    else:
        pool = np.int32([np.iinfo(np.int32).min, -7, 0, 3,
                         np.iinfo(np.int32).max])
        pad = np.iinfo(np.int32).max
    k = rng.choice(pool, size=shape).astype(dtype)
    k[..., -(shape[-1] // 5):] = pad
    return k


def _payload(kind, shape, seed):
    rng = np.random.default_rng(seed)
    n = int(np.prod(shape))
    ids = rng.permutation(n).astype(np.int32).reshape(shape)
    f = rng.normal(size=shape).astype(np.float32)
    f.reshape(-1)[::7] = -0.0
    if kind == "i32":
        return ids
    if kind == "f32":
        return f
    if kind == "tuple":
        return (f, ids)
    return rng.normal(size=shape + (3,)).astype(np.float32)   # "rows3"


def _bits(a):
    a = np.asarray(a)
    return a.view(np.uint32) if a.dtype == np.float32 else a


def _assert_bitwise(got, want):
    np.testing.assert_array_equal(_bits(got), _bits(want))


def _oracle_ref_kv(keys, leaf):
    """``kernels.ref.sort_kv_ref`` applied to one payload leaf (a trailing
    payload dim goes column by column)."""
    from repro.kernels import ref
    if leaf.ndim == keys.ndim:
        return ref.sort_kv_ref(jnp.asarray(keys), jnp.asarray(leaf))[1]
    cols = [ref.sort_kv_ref(jnp.asarray(keys), jnp.asarray(leaf[..., j]))[1]
            for j in range(leaf.shape[-1])]
    return np.stack([np.asarray(c) for c in cols], axis=-1)


@pytest.mark.parametrize("op", ["sort_kv", "sort_kv_rows",
                                "sort_partition_kv", "merge_sorted_rows_kv"])
@pytest.mark.parametrize("payload", ["i32", "f32", "tuple", "rows3"])
@pytest.mark.parametrize("key_dtype", [np.float32, np.int32])
def test_reference_payload_sort_bitwise(op, payload, key_dtype):
    """Each reference payload sort equals numpy's stable argsort + gather
    and ``ref.sort_kv_ref`` bit for bit, whichever form the payload
    takes, and ticks the path its payload's shape picks."""
    shape = {"sort_kv": (257,), "sort_kv_rows": (3, 129),
             "sort_partition_kv": (257,),
             "merge_sorted_rows_kv": (5, 41)}[op]
    keys = _tie_keys(key_dtype, shape, seed=11)
    if op == "merge_sorted_rows_kv":
        keys = np.sort(keys, axis=-1, kind="stable")
    vals = _payload(payload, shape, seed=12)
    leaves = list(vals) if isinstance(vals, tuple) else [vals]
    jvals = jax.tree.map(jnp.asarray, vals)
    ops.reset_dispatch_counts()
    if op == "merge_sorted_rows_kv":
        gk, gv = ops.merge_sorted_rows_kv(jnp.asarray(keys), jvals,
                                          backend="reference")
        keys = keys.reshape(-1)
        leaves = [v.reshape(keys.shape[0], *v.shape[2:]) for v in leaves]
    elif op == "sort_partition_kv":
        interior = np.sort(_tie_keys(key_dtype, (4,), seed=13))
        gk, gv, starts, lens = ops.sort_partition_kv(
            jnp.asarray(keys), jvals, jnp.asarray(interior),
            backend="reference")
        cuts = np.searchsorted(np.sort(keys, kind="stable"), interior)
        np.testing.assert_array_equal(np.asarray(starts),
                                      np.concatenate([[0], cuts]))
        np.testing.assert_array_equal(
            np.asarray(lens), np.diff(np.concatenate([[0], cuts,
                                                      [keys.size]])))
    else:
        gk, gv = ops.sort_kv(jnp.asarray(keys), jvals, backend="reference")
    got = list(gv) if isinstance(gv, tuple) else [gv]
    order = np.argsort(keys, axis=-1, kind="stable")
    _assert_bitwise(gk, np.take_along_axis(keys, order, axis=-1))
    for g, v in zip(got, leaves):
        idx = order.reshape(order.shape + (1,) * (v.ndim - keys.ndim))
        want = np.take_along_axis(v, idx, axis=keys.ndim - 1)
        _assert_bitwise(g, want)
        _assert_bitwise(g, _oracle_ref_kv(keys, v))
    path = "reference_gather" if payload == "rows3" else "reference"
    tick_op = "sort_kv" if op == "sort_kv_rows" else op
    assert dict(ops.DISPATCH_COUNTS) == {(tick_op, path): 1}


@pytest.mark.parametrize("op", ["sort_kv", "merge_sorted_rows_kv"])
@pytest.mark.parametrize("payload,gathers", [("i32", 0), ("tuple", 0),
                                             ("rows3", 1)])
def test_reference_payload_sort_lowering(op, payload, gathers):
    """The reference payload sort lowers to exactly one ``sort``; only a
    payload with trailing dims adds a gather, one, for that leaf."""
    shape = (4, 16) if op == "merge_sorted_rows_kv" else (64,)
    keys = jnp.zeros(shape, jnp.float32)
    vals = jax.tree.map(jnp.asarray, _payload(payload, shape, seed=3))
    fn = getattr(ops, op)
    ops.reset_dispatch_counts()
    text = jax.jit(lambda k, v: fn(k, v, backend="reference")).lower(
        keys, vals).as_text()
    assert text.count('"stablehlo.sort"(') == 1
    assert text.count('"stablehlo.gather"(') == gathers
    path = "reference_gather" if gathers else "reference"
    assert dict(ops.DISPATCH_COUNTS) == {(op, path): 1}


# ---------------------------------------------------------------------------
# dispatch mechanics: fallback, counters, backend resolution
# ---------------------------------------------------------------------------

def test_unsupported_shapes_fall_back_to_reference():
    ops.reset_dispatch_counts()
    x3 = jnp.zeros((2, 3, 4), jnp.float32)           # >2D: no kernel
    ops.sort(x3, backend="pallas")
    xu = jnp.zeros((8,), jnp.uint8)                  # exotic dtype: no kernel
    ops.sort(xu, backend="pallas")
    xl = jnp.zeros((ops.MAX_KERNEL_LANES + 1,), jnp.float32)  # too long
    ops.sort(xl, backend="pallas")
    assert ops.DISPATCH_COUNTS[("sort", "reference")] == 3
    assert ops.DISPATCH_COUNTS[("sort", "pallas")] == 0


def test_dispatch_counts_tick_per_path():
    ops.reset_dispatch_counts()
    x = jnp.asarray(np.float32([3, 1, 2]))
    ops.sort(x, backend="pallas")
    ops.sort(x, backend="reference")
    assert ops.DISPATCH_COUNTS[("sort", "pallas")] == 1
    assert ops.DISPATCH_COUNTS[("sort", "reference")] == 1


def test_unknown_backend_rejected():
    with pytest.raises(ValueError, match="unknown kernel backend"):
        ops.sort(jnp.zeros((4,), jnp.float32), backend="bogus")


def test_default_backend_env_resolution(monkeypatch):
    monkeypatch.setattr(ops, "DEFAULT_BACKEND", "pallas")
    assert ops.resolve_backend(None) == "pallas"
    assert ops.resolve_backend("reference") == "reference"
    monkeypatch.setattr(ops, "DEFAULT_BACKEND", "reference")
    assert ops.resolve_backend(None) == "reference"
