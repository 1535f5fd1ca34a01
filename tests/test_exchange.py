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
