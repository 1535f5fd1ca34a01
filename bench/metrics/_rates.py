"""Shared arithmetic of the metric readers."""
from __future__ import annotations


def rate(jobs) -> float | None:
    """All units of the window's whole jobs over all their time."""
    if not jobs:
        return None
    return sum(j["units"] for j in jobs) / sum(j["seconds"] for j in jobs)


def share(part: float, whole: float) -> float | None:
    """part / whole in percent; None where there is nothing to divide."""
    if whole is None or whole <= 0 or part is None:
        return None
    return 100.0 * part / whole


def idle_share(view) -> float | None:
    """Percent of the traced window in which no device op ran."""
    if view is None or view.window_s <= 0 or not view.ops:
        return None
    return share(view.window_s - view.busy_s, view.window_s)
