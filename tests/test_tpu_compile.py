"""Ahead-of-time compiles of the main-path kernels for a v5e chip.

Interpret mode runs the kernel bodies through XLA on the CPU and cannot
see what Mosaic refuses (unsupported shape casts, in-kernel gathers,
block shapes off the (8, 128) tiling).  These tests compile every
kernel the cluster front door dispatches, with ``interpret=False``, at
the largest shapes ``ops.kernel_eligible`` admits, for a described
(not attached) v5e device, and check that the program holds the Mosaic
kernel.  Nothing runs: results are the differential suites' business.

The topology is described inside a fixture, never at import: only one
process may load the TPU compiler library at a time.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import bitonic, bucketize, fused, ops

LANES = ops.MAX_KERNEL_LANES
T = 16                          # machines in the bulk cell: t - 1 = 15 bounds


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


@pytest.mark.parametrize("rows,n,dtype", [
    (8, 4096, jnp.float32),
    (1, LANES, jnp.float32),
    (8, LANES, jnp.float32),
    (1, LANES, jnp.int32),
    (1, LANES, jnp.bfloat16),
    (3, 100, jnp.float32),
])
def test_bitonic_sort_compiles(one_chip, rows, n, dtype):
    _compile(one_chip, lambda x: bitonic.bitonic_sort(
        x, block_rows=min(8, rows), interpret=False), ((rows, n), dtype))


@pytest.mark.parametrize("n,dtype", [(8192, jnp.float32),
                                     (LANES, jnp.float32),
                                     (LANES, jnp.int32)])
def test_bitonic_sort_kv_compiles(one_chip, n, dtype):
    _compile(one_chip, lambda k, v: bitonic.bitonic_sort_kv(
        k, v, block_rows=1, interpret=False),
        ((1, n), dtype), ((1, n), jnp.int32))


@pytest.mark.parametrize("n,nq,dtype", [
    (LANES, 4096, jnp.float32),
    (LANES, LANES, jnp.int32),
    (LANES, T - 1, jnp.float32),
    (4096, 4096, jnp.bfloat16),
])
@pytest.mark.parametrize("side", ["left", "right"])
def test_searchsorted_compiles(one_chip, n, nq, dtype, side):
    _compile(one_chip, lambda a, q: bucketize.searchsorted(
        a, q, side=side, interpret=False), ((n,), dtype), ((nq,), dtype))


@pytest.mark.parametrize("t", [T, 1 << 12])
def test_bucketize_histogram_compiles(one_chip, t):
    _compile(one_chip, lambda k, b: bucketize.bucketize_histogram(
        k, b, t, interpret=False),
        ((LANES,), jnp.float32), ((t - 1,), jnp.float32))


@pytest.mark.parametrize("m,dtype", [(8192, jnp.float32),
                                     (LANES, jnp.float32),
                                     (8192, jnp.bfloat16)])
def test_sort_partition_compiles(one_chip, m, dtype):
    _compile(one_chip, lambda x, q: fused.sort_partition(
        x, q, interpret=False), ((m,), dtype), ((T - 1,), dtype))


@pytest.mark.parametrize("m", [8192, LANES])
def test_sort_partition_kv_compiles(one_chip, m):
    _compile(one_chip, lambda x, q: fused.sort_partition_kv(
        x, q, interpret=False), ((m,), jnp.int32), ((T - 1,), jnp.int32))


@pytest.mark.parametrize("t,c", [(16, 4096), (8, 2)])
def test_merge_sorted_rows_compiles(one_chip, t, c):
    assert ops._merge_fits_one_tile(t, c)
    _compile(one_chip, lambda x: bitonic.merge_sorted_rows(
        x, interpret=False), ((t, c), jnp.float32))
    _compile(one_chip, lambda x: bitonic.merge_sorted_rows_argsort(
        x, interpret=False), ((t, c), jnp.float32))


@pytest.mark.parametrize("t,c,bound_block", [
    (16, 4096, None),
    (16, 1 << 14, ops.RANK_MERGE_BOUND_BLOCK),
    (2, LANES, ops.RANK_MERGE_BOUND_BLOCK),
    (512, 256, None),
])
def test_merge_ranks_compiles(one_chip, t, c, bound_block):
    _compile(one_chip, lambda k, i: fused.merge_ranks(
        k, i, bound_block=bound_block, interpret=False),
        ((t, c), jnp.float32), ((t, c), jnp.int32))


def test_sort_kernel_choice_never_radix_on_tpu(monkeypatch):
    """The radix kernel's scatter/gather have no Mosaic lowering, so on
    TPU the pallas sort path is the bitonic network, and eligible wide
    rows tick a bitonic ("pallas") dispatch, never a radix one."""
    monkeypatch.setattr(ops, "platform", lambda: "tpu")
    assert not ops.interpret_mode()
    assert ops.platform_backend() == "pallas"
    assert ops.sort_kernel_choice() == "bitonic"
    ops.reset_dispatch_counts()
    for x in (jnp.zeros((4, 1 << 14), jnp.float32),
              jnp.zeros((1, LANES), jnp.int32),
              jnp.zeros((4, 1 << 13), jnp.bfloat16)):
        jax.eval_shape(lambda y: ops.sort(y), x)
    assert ops.DISPATCH_COUNTS == {("sort", "pallas"): 3}


def test_machines_over_four_chips_compile(topo, monkeypatch):
    """The front door's four-chip program (t = 64 as 16 machines on each
    chip of a 2x2 host, SMMS with payload, kernels on) compiles for the
    chip, with the Round-3 exchange as cross-chip all-to-all ops."""
    import functools

    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.cluster.substrate import MeshVmapSubstrate
    from repro.core.smms import _smms_shard_kv, default_cap_factor

    monkeypatch.setattr(ops, "platform", lambda: "tpu")
    t, n = 64, 1 << 18
    sub = MeshVmapSubstrate(("i", t), chips=4, devices=topo.devices)
    body = functools.partial(
        _smms_shard_kv, axis_name=sub.axis_name, t=t, r=2,
        cap_factor=float(default_cap_factor(n, t, 2)), backend="static")
    sharded = NamedSharding(sub.mesh, P("chip"))
    args = [jax.ShapeDtypeStruct((t, n // t), d, sharding=sharded)
            for d in (jnp.float32, jnp.int32)]
    fn, _ = sub._build(body, 2, ())
    text = fn.lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    assert text.count(" all-to-all(") == 2         # keys and payload


@pytest.mark.parametrize("op,shape", [
    ("sort_kv", (16, 1 << 22)),                 # Round 1, 16 machines
    ("merge_sorted_rows_kv", (16, 16, 550_504)),  # the receive merge
])
def test_reference_payload_sorts_compile_without_gather(one_chip, op, shape):
    """At the one-chip sort cell's shapes (rows past every kernel's lane
    bound, under ``vmap`` over the machines) the reference payload sort
    compiles to the chip's sort with the payload as an operand: no
    gather of keys or payload is left."""
    fn = getattr(ops, op)
    args = [jax.ShapeDtypeStruct(shape, d, sharding=one_chip)
            for d in (jnp.float32, jnp.int32)]
    body = jax.vmap(lambda k, v: fn(k, v, backend="reference"))
    text = jax.jit(body).lower(*args).compile().as_text()
    assert " sort(" in text
    assert " gather(" not in text


def test_send_buffer_compiles_without_gather(one_chip):
    """At the one-chip sort cell's shapes (16 machines of 2^22 keys and
    ids under ``vmap``, 550,504 slots a pair) the exchange packs its send
    tiles with row copies: no gather of keys or ids is left."""
    from repro.core.exchange import build_send_buffer

    t, cap = 16, 550_504
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in (((t, 1 << 22), jnp.float32),
                         ((t, 1 << 22), jnp.int32),
                         ((t, t), jnp.int32), ((t, t), jnp.int32))]
    body = jax.vmap(lambda k, v, starts, lens: build_send_buffer(
        k, starts, lens, cap, v))
    text = jax.jit(body).lower(*args).compile().as_text()
    assert " gather(" not in text
