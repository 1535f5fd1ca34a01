"""The Round-3 all_to_all of SMMS against the chips' interconnect.

A dense all_to_all ships whole tiles, padding and all: machine i sends
tile row k, ``cap_pair`` records of 8 bytes, to machine k.  With t
machines over ``chips`` chips, v = t / chips per chip, each chip's v
machines send ``t - v`` tiles each to machines on other chips, so a chip
puts ``v (t - v) cap_pair 8`` bytes on the interconnect per job.  The
tiles its own machines address to each other stay in its memory.

``cap_pair`` is what ``core/exchange.py`` sizes from the capacity
factor: ``ceil(int(factor m) / t)`` with m = n / t.  The factor is
Theorem 1's bound, ``1 + 2/r + t^2/n``, times the capacity policy's
first-attempt slack of 1.05; a job that retried with a larger factor
sent larger tiles, so the roofline reads None for a window with one.

The least time is those bytes over the chip's whole ICI peak
(``ici_bits_per_s`` in ``bench/peaks.json``, all of its links and both
directions), so the share is a lower bound of the outbound links' use
and cannot pass 100%.
"""
from __future__ import annotations

from bench.metrics._rates import share
from bench.metrics._spans import span_count_per_job

RECORD_BYTES = 8              # float32 key + int32 record id
SLACK = 1.05                  # CapacityPolicy's first-attempt slack


def theorem1_factor(n: int, t: int, r: int = 2) -> float:
    """SMMS's first capacity factor: (1 + 2/r + t^2/n) with the slack."""
    return (1.0 + 2.0 / r + t * t / n) * SLACK


def cap_pair(n: int, t: int, factor: float) -> int:
    """Records per (sender, receiver) tile of the static exchange."""
    m = n // t
    return -(-int(factor * m) // t)


def round3_bytes_per_chip(t: int, chips: int, records: int,
                          factor: float) -> int:
    """Bytes one chip's machines send to other chips in one job."""
    v = t // chips
    return v * (t - v) * cap_pair(records, t, factor) * RECORD_BYTES


def is_all_to_all(op: dict) -> bool:
    return "all-to-all" in op["opcode"] or "all_to_all" in op["name"]


def peak_bytes_per_s() -> float:
    """The ICI peak of the device this process runs on, in bytes/s."""
    import jax

    from bench.registry import Benchmark
    kind = jax.devices()[0].device_kind
    return Benchmark().peaks(kind)["ici_bits_per_s"] / 8.0


def roofline(run) -> float | None:
    """Percent of the all_to_all ops' device time, per chip, that the
    interconnect's peak needs for the cell's bytes; None where the
    trace holds no all_to_all or a job retried its capacity."""
    view = run.trace
    if view is None or not run.jobs:
        return None
    attempts = span_count_per_job(run, "capacity.attempt")
    if attempts is None or attempts > 1.0:
        return None
    seconds = view.op_seconds(is_all_to_all)
    if seconds <= 0:
        return None
    cfg = run.config
    t, n = int(cfg["t"]), int(cfg["records_per_chip"])
    chips = int(run.cell["chips"])
    moved = round3_bytes_per_chip(t, chips, n, theorem1_factor(
        n, t, int(cfg["r"]))) * len(run.jobs)
    return share(moved / peak_bytes_per_s(), seconds)
