"""Shared fixtures: cross-test isolation + deterministic randomness.

Isolation: every module-level counter/cache the serving stack shares —
kernel dispatch counts, the planner's plan cache + stats, the serve
counters — is reset around every test, so no test can pass (or fail) on
another test's traffic.

Seed hygiene: all test randomness routes through the ``rng`` fixture,
seeded from a stable hash of the test's node id XOR ``REPRO_TEST_SEED``
(default pinned).  Run-to-run the data is identical; across tests the
streams are independent; flipping the env var reseeds the whole suite
deliberately.  Global ``random``/``np.random`` state is also pinned per
test, and hypothesis (when installed) is forced onto a deterministic
``ci`` profile so property tests draw the same examples on every CI run.
"""
import hashlib
import os
import random

import numpy as np
import pytest

from repro.kernels import ops

TEST_SEED = int(os.environ.get("REPRO_TEST_SEED", "20260730"))

try:  # deterministic hypothesis profile for CI (optional dependency)
    from hypothesis import settings as _hyp_settings

    _hyp_settings.register_profile(
        "ci", derandomize=True, deadline=None,
        max_examples=int(os.environ.get("REPRO_HYP_EXAMPLES", "20")))
    _hyp_settings.load_profile(os.environ.get("HYPOTHESIS_PROFILE", "ci"))
except ImportError:  # tests/_prop.py shim is deterministic already
    pass


@pytest.fixture(autouse=True)
def _reset_shared_counters():
    """Module-global counters/caches must never leak between tests.

    DISPATCH_COUNTS ticks at trace time, the plan cache keys on content
    (a repeated fixture table would hit a stale plan and skip the
    sketch), and SERVE_COUNTERS ticks on every engine submit — without
    this reset a test asserting any of them could pass on another
    test's traffic.  The shared default substrate pool (the fused
    front-door executor) is dropped too: its compiled-program and
    compile counters would otherwise let a compile-count assertion pass
    (or a dispatch-count assertion fail) on another test's warm cache.
    Reset before AND after: before isolates this test, after leaves
    nothing behind for non-pytest callers.
    """
    from repro import obs
    from repro.cluster import reset_default_pool
    from repro.planner import clear_plan_cache
    from repro.serve.query import reset_serve_counters

    def _reset_all():
        ops.reset_dispatch_counts()
        clear_plan_cache()
        reset_serve_counters()
        reset_default_pool()
        # observability globals: the process-wide metrics registry (the
        # kernel dispatch counters tick into it) and the default tracer
        # (tests that enable tracing must not leak spans — or an
        # enabled tracer — into the next test)
        obs.reset_registry()
        obs.set_tracer(obs.Tracer(enabled=False))

    _reset_all()
    yield
    _reset_all()


@pytest.fixture(autouse=True)
def _pin_global_rngs():
    """Anything that (accidentally) uses global randomness is pinned."""
    random.seed(TEST_SEED)
    np.random.seed(TEST_SEED % (2**32))
    yield


@pytest.fixture
def kernel_path(monkeypatch):
    """Pin the kernel path for this test: ``kernel_path("pallas")`` or
    ``kernel_path("reference")`` sets ``ops.DEFAULT_BACKEND``, the one
    process-wide pin ``ops`` honours.  Call it again to flip the path
    mid-test; the default comes back when the test ends."""
    def pin(path):
        monkeypatch.setattr(ops, "DEFAULT_BACKEND", path)
    return pin


@pytest.fixture
def rng(request) -> np.random.Generator:
    """Per-test deterministic generator — the one seeded randomness door.

    Seeded from blake2b(node id) ^ REPRO_TEST_SEED: stable run-to-run,
    independent across tests, and reseedable suite-wide via the env var.
    """
    digest = hashlib.blake2b(request.node.nodeid.encode(),
                             digest_size=8).digest()
    return np.random.default_rng(int.from_bytes(digest, "big") ^ TEST_SEED)
