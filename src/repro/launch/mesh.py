"""Production mesh builders + the staged-exchange shard factorization.

Importing this module never touches jax device state — meshes are built
inside functions only (the dry-run sets XLA_FLAGS before any jax import).
Every mesh goes through :func:`make_mesh`, so all axes are ``Auto``.

``factor_shards`` / ``staged_axes`` / ``make_staged_mesh`` support the
two-level (AMS-style) exchange: the shard axis t is factored into
t = t1 * t2 sub-axes so one t-way all_to_all becomes two ~sqrt(t)-way
exchanges.  Only balanced power-of-two factorizations are produced;
anything else falls back to the flat topology with a warning (never an
exception) — the staged path is an optimization, not a requirement.
"""
from __future__ import annotations

import warnings
from typing import Optional, Tuple

STAGED_AXIS_NAMES = ("i1", "i2")


def make_mesh(shape, names, devices=None):
    """``jax.make_mesh`` with every axis ``AxisType.Auto`` — the
    explicit-collectives behaviour the substrate's shard_map bodies
    are written for."""
    import jax
    from jax.sharding import AxisType

    kw = {} if devices is None else {"devices": devices}
    return jax.make_mesh(tuple(shape), tuple(names),
                         axis_types=(AxisType.Auto,) * len(names), **kw)


def factor_shards(t: int, *, warn: bool = False
                  ) -> Optional[Tuple[int, int]]:
    """Balanced two-level factorization t = t1 * t2 (t1 >= t2 >= 2).

    Returns ``None`` when no balanced power-of-two factorization exists
    (t < 4, or t not a power of two) — the caller falls back to the flat
    exchange.  ``warn=True`` announces that fallback (user-facing call
    sites pass it; probing call sites like the planner stay silent).
    """
    t = int(t)
    if t < 4 or (t & (t - 1)) != 0:
        if warn:
            warnings.warn(
                f"t={t} has no balanced power-of-two factorization; "
                "falling back to the flat (single-stage) exchange",
                stacklevel=2)
        return None
    k = t.bit_length() - 1
    return (1 << (k - k // 2), 1 << (k // 2))


def staged_axes(t: int, names: Tuple[str, str] = STAGED_AXIS_NAMES,
                *, warn: bool = False):
    """Axis spec ``((name1, t1), (name2, t2))`` for a staged substrate,
    or ``None`` when t does not factor (see :func:`factor_shards`)."""
    fs = factor_shards(t, warn=warn)
    if fs is None:
        return None
    return ((names[0], fs[0]), (names[1], fs[1]))


def make_staged_mesh(t: int, names: Tuple[str, str] = STAGED_AXIS_NAMES):
    """2-level device mesh for the staged exchange (needs t devices).

    Non-factorable t degrades to a flat 1-axis mesh (with a warning)
    instead of raising — same contract as the exchange itself.
    """
    fs = factor_shards(t, warn=True)
    if fs is None:
        return make_mesh((int(t),), (names[0],))
    return make_mesh(fs, names)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; multi_pod adds a 2-pod 'pod' axis.

    'pod'   — pure data parallelism (slow inter-pod links: gradient
              all-reduce only, optionally int8-compressed),
    'data'  — batch + FSDP,
    'model' — TP / EP / sequence-sharded KV.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(t: int = 8):
    """Small mesh over however many (host) devices exist — examples/tests."""
    import jax

    n = len(jax.devices())
    t = min(t, n)
    data = max(1, t // 2) if t > 1 else 1
    model = t // data
    return make_mesh((data, model), ("data", "model"))
