"""Each reference at a tiny size: exact answers pass, wrong ones count."""
import numpy as np
import pytest

from bench.reference.bounds import workload_bound
from bench.reference.join import compare_join, control_join, join_reference
from bench.reference.sort import compare_sort, control_sort, sort_reference


def test_sort_reference_is_ascending_and_complete():
    keys = np.random.default_rng(0).uniform(1, 12e6, 1000).astype(np.float32)
    ref = sort_reference(keys)
    assert np.all(ref[1:] >= ref[:-1]) and np.array_equal(np.sort(keys), ref)


@pytest.mark.parametrize("ties", [False, True])
def test_compare_sort_counts(ties):
    rng = np.random.default_rng(1)
    keys = (rng.integers(0, 20, 512) if ties
            else rng.permutation(512)).astype(np.float32)
    ids = np.arange(512, dtype=np.int32)
    order = np.argsort(keys, kind="stable")
    assert compare_sort(keys, keys[order], ids[order]) == {
        "keys_wrong": 0, "payload_wrong": 0}
    # ties leave the payload order free: any order within a tie passes
    shuffled = order.copy()
    for k in np.unique(keys):
        pos = np.flatnonzero(keys[order] == k)
        shuffled[pos] = rng.permutation(order[pos])
    assert compare_sort(keys, keys[order], ids[shuffled])["payload_wrong"] == 0
    assert compare_sort(keys, keys, ids)["keys_wrong"] > 0
    assert compare_sort(keys, keys[order][:256], ids[order][:256]) == {
        "keys_wrong": 256, "payload_wrong": 512}
    swapped = ids[order].copy()
    swapped[[0, -1]] = swapped[[-1, 0]]
    assert compare_sort(keys, keys[order], swapped)["payload_wrong"] == 2
    assert compare_sort(keys, keys[order], None)["payload_wrong"] == 0


def test_sort_control_is_refused():
    keys = np.random.default_rng(2).uniform(1, 12e6, 4096).astype(np.float32)
    ids = np.arange(keys.size, dtype=np.int32)
    out = compare_sort(keys, *control_sort(keys, ids))
    assert out["keys_wrong"] > 0 and out["payload_wrong"] > 0


def test_join_reference_pairs_each_row_with_equal_keys():
    rng = np.random.default_rng(3)
    t_keys = rng.integers(0, 50, 60).astype(np.int32)   # repeats too
    s_keys = rng.integers(0, 60, 200).astype(np.int32)
    pairs = join_reference(s_keys, t_keys)
    want = {(i, j) for i in range(200) for j in range(60)
            if s_keys[i] == t_keys[j]}
    assert {(int(p >> 32), int(p & 0xFFFFFFFF)) for p in pairs} == want
    assert pairs.size == len(want)


def test_compare_join_counts():
    rng = np.random.default_rng(4)
    t_keys = rng.permutation(100).astype(np.int32)
    s_keys = t_keys[rng.integers(0, 100, 400)]
    ref = join_reference(s_keys, t_keys)
    s, t = ref >> 32, ref & 0xFFFFFFFF
    assert compare_join(ref, s, t) == {"pairs_missing": 0, "pairs_wrong": 0}
    assert compare_join(ref, s[:300], t[:300]) == {"pairs_missing": 100,
                                                  "pairs_wrong": 0}
    assert compare_join(ref, np.r_[s, s[:2]], np.r_[t, t[:2]]) == {
        "pairs_missing": 0, "pairs_wrong": 2}
    bad = t.copy()
    bad[0] = (bad[0] + 1) % 100
    assert compare_join(ref, s, bad) == {"pairs_missing": 1, "pairs_wrong": 1}


def test_join_control_is_refused_where_int16_keys_collide():
    # int16 keys collide once T holds more than 2^16 distinct keys
    rng = np.random.default_rng(5)
    t_keys = rng.permutation(1 << 17).astype(np.int32)
    s_keys = t_keys[rng.integers(0, 1 << 17, 1 << 18)]
    out = compare_join(join_reference(s_keys, t_keys),
                       *control_join(s_keys, t_keys))
    assert out["pairs_wrong"] > 0


def test_workload_bounds():
    assert workload_bound("smms_theorem1", n=1 << 20, t=16, r=2) == \
        pytest.approx((2.0 + 256 / (1 << 20)) * (1 << 16))
    assert workload_bound("terasort_theorem3", n=160, t=16) == 51.0
    assert workload_bound("statjoin_theorem6", n=0, t=16, w=800) == 100.0
    with pytest.raises(ValueError):
        workload_bound("nope", n=1, t=1)
