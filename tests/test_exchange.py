"""Round-3 exchange machinery: partition, static capacity, drop counting."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from _prop import given, settings, st

from repro.core.exchange import (PAD, build_send_buffer,
                                 exchange_sorted_segments, partition_sorted)


def test_partition_sorted_boundaries_go_right():
    x = jnp.asarray([1.0, 2.0, 2.0, 3.0, 5.0])
    starts, lens = partition_sorted(x, jnp.asarray([2.0, 4.0]))
    # bucket [b_k, b_{k+1}): keys == 2.0 belong to bucket 1
    np.testing.assert_array_equal(starts, [0, 1, 4])
    np.testing.assert_array_equal(lens, [1, 3, 1])


def test_build_send_buffer_pads_and_counts_drops():
    x = jnp.asarray([1.0, 2.0, 3.0, 4.0])
    starts = jnp.asarray([0, 3])
    lens = jnp.asarray([3, 1])
    keys, _, dropped = build_send_buffer(x, starts, lens, cap_per_pair=2)
    assert keys.shape == (2, 2)
    np.testing.assert_array_equal(np.asarray(keys)[0], [1.0, 2.0])  # 3.0 dropped
    np.testing.assert_array_equal(np.asarray(keys)[1], [4.0, np.inf])
    assert int(dropped) == 1


def _gather_reference(x_sorted, starts, lens, cap_per_pair, values=None,
                      pad_key=PAD, valid_len=None):
    """The element-gather formula over a (t, C) index grid, which
    ``build_send_buffer``'s row copies must reproduce bit for bit."""
    t = starts.shape[0]
    m = valid_len if valid_len is not None else x_sorted.shape[0]
    cols = jnp.arange(cap_per_pair)
    idx = starts[:, None] + cols[None, :]
    valid = cols[None, :] < lens[:, None]
    safe = jnp.clip(idx, 0, m - 1)
    keys = jnp.where(valid, x_sorted[safe],
                     jnp.asarray(pad_key, x_sorted.dtype))

    def pack(v):
        vals_g = v[safe]
        mask = valid.reshape(t, cap_per_pair, *([1] * (v.ndim - 1)))
        return jnp.where(mask, vals_g, jnp.zeros_like(vals_g))

    vals = jax.tree.map(pack, values) if values is not None else None
    return keys, vals, jnp.sum(jnp.maximum(lens - cap_per_pair, 0))


def _sorted_keys(rng, n, dtype=np.float32):
    if np.issubdtype(dtype, np.integer):
        return np.sort(rng.integers(-1000, 1000, n)).astype(dtype)
    return np.sort(rng.normal(size=n)).astype(dtype)


def _segments(cuts, m):
    starts = np.asarray([0] + list(cuts), np.int32)
    ends = np.asarray(list(cuts) + [m], np.int32)
    return jnp.asarray(starts), jnp.asarray(ends - starts)


def _case(name, rng):
    """Inputs of one differential case: the arrays, the pair capacity
    ``cap``, and ``valid_len`` where the keys carry a padded tail."""
    if name == "drops_empty_start_at_m":
        m = 12
        starts, lens = _segments([5, 5, 10, 12], m)  # lens 5, 0, 5, 2, 0
        return dict(x=jnp.asarray(_sorted_keys(rng, m)), starts=starts,
                    lens=lens, cap=4, values=jnp.arange(m, dtype=jnp.int32))
    if name in ("pow2_tail", "traced_valid_len"):
        m, padded = 11, 16
        keys = np.full(padded, np.inf, np.float32)
        keys[:m] = _sorted_keys(rng, m)
        vals = np.zeros(padded, np.int32)
        vals[:m] = rng.permutation(m)
        interior = jnp.asarray(np.quantile(keys[:m], [0.3, 0.6]),
                               jnp.float32)
        starts, lens = partition_sorted(jnp.asarray(keys), interior,
                                        valid_len=m)
        return dict(x=jnp.asarray(keys), starts=starts, lens=lens, cap=12,
                    values=jnp.asarray(vals), valid_len=m,
                    interior=interior)
    if name == "tuple_payload":
        m = 40
        starts, lens = _segments(sorted(rng.integers(0, m + 1, 3)), m)
        return dict(x=jnp.asarray(_sorted_keys(rng, m)), starts=starts,
                    lens=lens, cap=11,
                    values=(jnp.asarray(rng.normal(size=m), jnp.float32),
                            jnp.asarray(rng.permutation(m), jnp.int32)))
    if name == "row_payload":
        m = 40
        starts, lens = _segments(sorted(rng.integers(0, m + 1, 4)), m)
        return dict(x=jnp.asarray(_sorted_keys(rng, m)), starts=starts,
                    lens=lens, cap=9,
                    values=jnp.asarray(rng.integers(0, 1 << 20, (m, 2)),
                                       jnp.int32))
    if name == "int_keys":
        m = 33
        starts, lens = _segments(sorted(rng.integers(0, m + 1, 2)), m)
        return dict(x=jnp.asarray(_sorted_keys(rng, m, np.int32)),
                    starts=starts, lens=lens, cap=13,
                    values=jnp.asarray(rng.normal(size=(m, 3)), jnp.float32),
                    pad_key=int(np.iinfo(np.int32).max))
    raise KeyError(name)


def _assert_bitwise(got, want):
    got, want = jax.tree.leaves(got), jax.tree.leaves(want)
    assert len(got) == len(want)
    for g, w in zip(got, want):
        g, w = np.asarray(g), np.asarray(w)
        assert g.dtype == w.dtype and g.shape == w.shape
        assert g.tobytes() == w.tobytes()


@pytest.mark.parametrize("name", [
    "drops_empty_start_at_m", "pow2_tail", "traced_valid_len",
    "tuple_payload", "row_payload", "int_keys", "vmap_machines"])
def test_build_send_buffer_matches_element_gather(name, rng):
    """Row copies give the element gather's tiles, values and drop count
    bit for bit: drops, empty segments, a start at m, a padded tail
    (static or traced valid length), payload tuples and rows, integer
    keys, and a batch of machines under ``vmap``."""
    if name == "vmap_machines":
        t, m, cap = 4, 64, 12                       # tight: some pairs drop
        x = jnp.asarray(np.sort(rng.normal(size=(t, m)), axis=1),
                        jnp.float32)
        vals = jnp.asarray(rng.permutation(t * m).reshape(t, m), jnp.int32)
        interior = jnp.asarray([-0.7, 0.0, 0.4], jnp.float32)

        def body(fn, xl, vl):
            starts, lens = partition_sorted(xl, interior)
            return fn(xl, starts, lens, cap, vl)

        got = jax.vmap(lambda a, b: body(build_send_buffer, a, b))(x, vals)
        want = jax.vmap(lambda a, b: body(_gather_reference, a, b))(x, vals)
        assert int(got[2].sum()) > 0
        _assert_bitwise(got, want)
        return
    c = _case(name, rng)
    if name == "traced_valid_len":              # the staged exchange's hop 2
        @jax.jit
        def both(xl, vl, n):
            starts, lens = partition_sorted(xl, c["interior"], valid_len=n)
            return (build_send_buffer(xl, starts, lens, c["cap"], vl),
                    _gather_reference(xl, starts, lens, c["cap"], vl,
                                      valid_len=n))

        _assert_bitwise(*both(c["x"], c["values"], jnp.int32(c["valid_len"])))
        return
    pad_key = c.get("pad_key", PAD)
    got = build_send_buffer(c["x"], c["starts"], c["lens"], c["cap"],
                            c["values"], pad_key=pad_key)
    want = _gather_reference(c["x"], c["starts"], c["lens"], c["cap"],
                             c["values"], pad_key=pad_key,
                             valid_len=c.get("valid_len"))
    _assert_bitwise(got, want)


def test_exchange_roundtrip_under_vmap(rng):
    t, m = 4, 64
    x = np.sort(rng.normal(size=(t, m)).astype(np.float32), axis=1)
    interior = jnp.asarray(np.quantile(x.reshape(-1), [0.25, 0.5, 0.75]),
                           jnp.float32)

    def body(xl):
        r = exchange_sorted_segments(xl, interior, axis_name="i", t=t,
                                     cap_factor=2.0)
        return r.keys, r.count, r.dropped

    keys, counts, dropped = jax.vmap(body, axis_name="i")(jnp.asarray(x))
    assert int(dropped[0]) == 0
    got = np.concatenate([np.asarray(keys)[i, :counts[i]]
                          for i in range(t)])
    np.testing.assert_array_equal(np.sort(x.reshape(-1)), got)


@settings(max_examples=15, deadline=None)
@given(st.integers(2, 6), st.integers(8, 100), st.integers(0, 2**31 - 1))
def test_property_exchange_conserves_or_drops(t, m, seed):
    """Every key either arrives or is counted as dropped — none vanish."""
    rng = np.random.default_rng(seed)
    x = np.sort(rng.normal(size=(t, m)).astype(np.float32), axis=1)
    interior = jnp.sort(jax.random.normal(jax.random.key(seed), (t - 1,)))

    def body(xl):
        r = exchange_sorted_segments(xl, interior, axis_name="i", t=t,
                                     cap_factor=0.8)  # deliberately tight
        return r.count, r.dropped

    counts, dropped = jax.vmap(body, axis_name="i")(jnp.asarray(x))
    assert int(counts.sum()) + int(dropped[0]) == t * m


# ---------------------------------------------------------------------------
# ragged backend: values routing (regression — values used to be silently
# dropped)
# ---------------------------------------------------------------------------

def test_ragged_backend_does_not_silently_drop_values(rng):
    """backend='ragged' must route the values through the exchange."""
    t, m = 4, 32
    x = jnp.sort(jnp.asarray(rng.normal(size=m), jnp.float32))
    vals = jnp.arange(m, dtype=jnp.int32)
    interior = jnp.asarray([-0.5, 0.0, 0.5], jnp.float32)

    def body(xl, vl):
        r = exchange_sorted_segments(xl, interior, axis_name="i", t=t,
                                     cap_factor=float(t), values=vl,
                                     backend="ragged")
        return r.keys, r.values

    # the lowered program must carry TWO ragged exchanges (keys + values)
    # with the same size vectors
    from jax.sharding import PartitionSpec as P
    if len(jax.devices()) < t:
        pytest.skip("needs >= t devices for shard_map lowering")
    mesh = jax.make_mesh((t,), ("i",))
    fn = jax.jit(jax.shard_map(
        lambda xl, vl: body(xl[0], vl[0]),
        mesh=mesh, in_specs=(P("i"), P("i")), out_specs=P("i")))
    txt = fn.lower(jnp.tile(x, (t, 1)), jnp.tile(vals, (t, 1))).as_text()
    assert txt.count("ragged-all-to-all") >= 2, txt


def test_unknown_backend_rejected(rng):
    x = jnp.sort(jnp.asarray(rng.normal(size=8), jnp.float32))
    with pytest.raises(ValueError, match="unknown exchange backend"):
        jax.vmap(lambda xl: exchange_sorted_segments(
            xl, jnp.asarray([0.0]), axis_name="i", t=2, cap_factor=2.0,
            backend="bogus"), axis_name="i")(jnp.tile(x, (2, 1)))
