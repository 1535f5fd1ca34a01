"""Static receive capacities from theorem bounds + retry-on-overflow.

XLA buffers are compile-time static; the repo's central hardware
adaptation is that each algorithm's (alpha, k) theorem *is* the buffer
size: Theorem 1 (SMMS), Theorem 3 (Terasort) and Theorem 6 (StatJoin)
bound per-machine receive totals, so ``ceil(bound * slack)`` slots are
provably (or w.h.p.) enough.  Randomized bounds can still fail — with
probability <= 1/n for Terasort — and adversarial initial placements can
exceed a *per-pair* static capacity even when the total is fine; both
are detected by the exchange's dropped-object counters.  The recovery
is the classic capacity-factor loop: re-run the (pure, deterministic)
program with a geometrically larger factor.  :class:`CapacityPolicy`
packages the theorem-derived base factor and the retry schedule;
:func:`run_with_capacity` is the loop itself.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Iterator, Tuple

from ..obs import trace as obs_trace

__all__ = ["CapacityPolicy", "CapacityOverflowError", "run_with_capacity"]


class CapacityOverflowError(RuntimeError):
    """Raised when the retry schedule is exhausted and objects still drop."""


@dataclasses.dataclass(frozen=True)
class CapacityPolicy:
    """Receive-capacity schedule: theorem-derived base, geometric growth.

    base_factor — capacity as a multiple of m = n/t (the perfectly
    balanced share); the per-algorithm constructors derive it from the
    paper's workload theorems.
    """

    base_factor: float
    slack: float = 1.05
    growth: float = 2.0
    max_retries: int = 3

    def factors(self) -> Iterator[float]:
        f = self.base_factor * self.slack
        for _ in range(self.max_retries + 1):
            yield f
            f *= self.growth

    @property
    def first_factor(self) -> float:
        return self.base_factor * self.slack

    # ---- theorem-derived constructors ---------------------------------
    @classmethod
    def fixed(cls, factor: float, **kw) -> "CapacityPolicy":
        """A caller-chosen factor: no slack and no silent growth.

        An explicit cap_factor pins the static buffer size (that is the
        point of the parameter on a TPU), so overflow raises
        CapacityOverflowError instead of re-running with a buffer up to
        8x what the caller asked for.  Pass max_retries explicitly to
        opt back into the growth schedule.
        """
        kw.setdefault("slack", 1.0)
        kw.setdefault("max_retries", 0)
        return cls(base_factor=float(factor), **kw)

    @classmethod
    def smms(cls, n: int, t: int, r: int, **kw) -> "CapacityPolicy":
        """Theorem 1: round-3 receive total <= (1 + 2/r + t^2/n) m."""
        return cls(base_factor=1.0 + 2.0 / r + t**2 / n, **kw)

    @classmethod
    def terasort(cls, n: int, t: int, **kw) -> "CapacityPolicy":
        """Theorem 3: |S_i| <= 5m + 1 w.p. >= 1 - 1/n."""
        m = max(1, n // t)
        return cls(base_factor=5.0 + 1.0 / m, **kw)

    @classmethod
    def statjoin(cls, **kw) -> "CapacityPolicy":
        """Theorem 6: per-machine join output <= 2 W/t, deterministic."""
        return cls(base_factor=2.0, **kw)

    @classmethod
    def randjoin(cls, **kw) -> "CapacityPolicy":
        """Cor. 3: per-machine output < 2 MN/t w.p. >= 1 - 1.2e-9."""
        return cls(base_factor=2.0, **kw)

    @classmethod
    def moe_dispatch(cls, **kw) -> "CapacityPolicy":
        """Theorem 6 applied to expert routing: the StatJoin slot plan
        splits a hot expert's tokens evenly over its replicas, so no slot
        receives more than 2 * T * K / n_slots assignments — the MoE
        capacity factor is the paper's deterministic join bound, not a
        hand-tuned constant."""
        return cls(base_factor=2.0, **kw)


def run_with_capacity(attempt: Callable[[float], Tuple[object, int]],
                      policy: CapacityPolicy) -> Tuple[object, float, int]:
    """Run ``attempt(cap_factor) -> (result, dropped)`` until nothing drops.

    Returns ``(result, cap_factor_used, attempts)``.  Raises
    :class:`CapacityOverflowError` when the schedule is exhausted with
    drops remaining (the last result is attached as ``.last_result``).
    """
    attempts = 0
    result, dropped, factor = None, 0, policy.first_factor
    for factor in policy.factors():
        attempts += 1
        if attempts > 1:    # an actual retry (the first try is not one)
            obs_trace.event("capacity_retry", attempt=attempts,
                            cap_factor=float(factor),
                            dropped=int(dropped))
        with obs_trace.span("capacity.attempt", attempt=attempts,
                            cap_factor=float(factor)):
            result, dropped = attempt(factor)
        if int(dropped) == 0:
            return result, factor, attempts
    err = CapacityOverflowError(
        f"{int(dropped)} objects still dropped after {attempts} attempts "
        f"(last cap_factor={factor:.3f})")
    err.last_result = result
    raise err
