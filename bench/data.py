"""Input generators: every record the benchmark feeds the system, made on
the device from the run's seed.

The same seed gives the same inputs.  Seeds may exceed 32 bits, so
:func:`root_key` folds the high word in instead of letting
``jax.random.key`` drop it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def root_key(seed: int):
    """A PRNG key for any non-negative seed up to 64 bits."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def power_law_ranks(key, n: int, domain: int, exponent: float):
    """n ranks in [0, domain) with P(rank r) ~ (r + 1)^-exponent: the
    inverse CDF of the continuous power law on [1, domain + 1), floored."""
    u = jax.random.uniform(key, (n,), jnp.float32)
    e = 1.0 - exponent
    top = jnp.float32(domain + 1) ** e
    x = ((top - 1.0) * u + 1.0) ** (1.0 / e)
    return jnp.clip(jnp.floor(x).astype(jnp.int32) - 1, 0, domain - 1)


@functools.partial(jax.jit, static_argnames=("t", "n", "dist"))
def sort_input(key, *, t: int, n: int, dist: tuple):
    """(keys (t, n/t) float32, payload (t, n/t) int32 record ids).

    ``dist`` is ``("uniform", low, high)`` (the paper's random data) or
    ``("zipf", exponent, ranks)`` (power-law ranks as float keys).
    """
    if dist[0] == "zipf":
        keys = power_law_ranks(key, n, int(dist[2]), float(dist[1])
                               ).astype(jnp.float32)
    elif dist[0] == "uniform":
        keys = jax.random.uniform(key, (n,), jnp.float32, float(dist[1]),
                                  float(dist[2]))
    else:
        raise ValueError(f"unknown key distribution {dist!r}")
    payload = jnp.arange(n, dtype=jnp.int32)
    return keys.reshape(t, -1), payload.reshape(t, -1)


@functools.partial(jax.jit, static_argnames=("n_s", "n_t", "exponent"))
def fk_join_input(key, base_key, *, n_s: int, n_t: int, exponent: float):
    """S foreign keys into T's unique keys, Zipf-skewed; rows are ids.

    ``base_key`` draws the keys and how many S rows each T key has (the
    join's statistics); ``key`` draws the row order of both tables.  So
    every ``key`` gives the same statistics in a different order.
    Returns (s_keys, s_rows, t_keys, t_rows), all int32."""
    kt, ks = jax.random.split(base_key)
    t_keys = jax.random.permutation(kt, n_t).astype(jnp.int32)
    s_keys = t_keys[power_law_ranks(ks, n_s, n_t, exponent)]
    s_order, t_order = jax.random.split(key)
    s_keys = s_keys[jax.random.permutation(s_order, n_s)]
    t_keys = t_keys[jax.random.permutation(t_order, n_t)]
    return (s_keys, jnp.arange(n_s, dtype=jnp.int32),
            t_keys, jnp.arange(n_t, dtype=jnp.int32))
