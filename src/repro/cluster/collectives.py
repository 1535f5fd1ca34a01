"""Instrumented collectives — the (alpha, k) accounting layer.

The paper judges every algorithm with one yardstick: alpha synchronized
rounds, per-machine workload and network both within a factor k of
perfect balance.  Instead of each algorithm hand-assembling its
``PhaseStats``, the substrate threads a :class:`CollectiveTape` through
the per-device body: every collective goes through the tape, which
records per-device sent/received object counts *inside the jitted
program* (they are ordinary traced scalars that flow out as extra
outputs of the vmap/shard_map program).  After execution the tape is
bound to the concrete (t,)-shaped counters and can assemble the
:class:`~repro.core.alpha_k.AlphaKReport` directly.

Accounting conventions (matching the paper's object counting):

* ``all_gather``   — sent = objects this device contributes, received =
  total objects gathered (``psum`` of the contributions).
* ``all_to_all``   — sent = objects leaving this device (caller-supplied,
  since only it knows which rows are self-addressed), received = valid
  objects in the landed buffer (sentinel-padding aware via ``pad``).
* ``ragged_all_to_all`` — exact sizes are part of the op; received =
  sum of the receive-size vector.
* ``psum`` of O(1) control scalars (overflow counters etc.) is *not*
  counted: the paper counts objects, and constant-size control messages
  vanish in the N/t normalization.

Phases are declared with ``tape.phase(name)``; alpha = number of
declared phases, and every record merges into the innermost active
phase.  A phase with no traffic (e.g. SMMS's replicated Round-2
boundary computation) still counts toward alpha — that is the paper's
definition of a synchronized round.
"""
from __future__ import annotations

import contextlib
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


# NOTE: repro.core.alpha_k is imported lazily in phases()/report() — the
# algorithm modules under repro.core import this module at load time, and
# importing any repro.core submodule here would close the cycle.

__all__ = ["CollectiveTape"]


def _flat(axis_name):
    """A reduction's axis names with nested pairs flattened: a
    MeshVmapSubstrate names its leading axis ``(chip, machine)``, so a
    reduction over ``(leading, second)`` arrives as a nested tuple."""
    if not isinstance(axis_name, tuple):
        return axis_name
    return tuple(n for name in axis_name
                 for n in (_flat(name) if isinstance(name, tuple)
                           else (name,)))


def _all_gather(x, axis_name, *, tiled: bool):
    """``lax.all_gather``, also over a ``(chip, machine)`` pair, which
    vmap cannot gather at once: the innermost axis first, so the rows
    come in linear machine order."""
    if not isinstance(axis_name, tuple):
        return lax.all_gather(x, axis_name, tiled=tiled)
    out = x
    for name in reversed(axis_name):
        out = lax.all_gather(out, name)
    out = out.reshape((-1,) + jnp.shape(x))
    return out.reshape((-1,) + jnp.shape(x)[1:]) if tiled else out


def _leading_count(x) -> int:
    """Default object count of an operand: its leading-axis length."""
    shape = jnp.shape(x)
    return int(shape[0]) if shape else 1


class CollectiveTape:
    """Records per-device collective traffic during one traced execution.

    Lifecycle: the substrate calls :meth:`reset` at trace time, the body
    records through the instrumented collectives, the substrate returns
    :meth:`traced` as program outputs and calls :meth:`bind` on the
    concrete results.  :meth:`report` then builds the AlphaKReport.
    """

    def __init__(self) -> None:
        self.reset()

    # ------------------------------------------------------------------
    # trace-side API
    # ------------------------------------------------------------------
    def reset(self) -> None:
        self._phase_order: List[str] = []
        self._entry_phase: List[str] = []   # static: phase of each record
        self._traced: List = []             # traced (sent, received) pairs
        self._current: Optional[str] = None
        self._bound: Optional[List] = None

    @contextlib.contextmanager
    def phase(self, name: str):
        """Declare a synchronized round; records inside merge into it."""
        if name not in self._phase_order:
            self._phase_order.append(name)
        prev, self._current = self._current, name
        try:
            yield self
        finally:
            self._current = prev

    def record(self, sent, received, *, phase: Optional[str] = None) -> None:
        """Record one traffic entry (traced or static scalars)."""
        name = phase if phase is not None else self._current
        if name is None:
            name = "(untagged)"
        if name not in self._phase_order:
            self._phase_order.append(name)
        self._entry_phase.append(name)
        self._traced.append((jnp.asarray(sent, jnp.float32),
                             jnp.asarray(received, jnp.float32)))

    # ---- instrumented collectives ------------------------------------
    def all_gather(self, x, axis_name: str, *, count=None, tiled: bool = False,
                   track: bool = True):
        out = _all_gather(x, axis_name, tiled=tiled)
        if track:
            c = jnp.asarray(count if count is not None else _leading_count(x))
            self.record(sent=c, received=lax.psum(c, axis_name))
        return out

    def all_to_all(self, x, axis_name: str, *, split_axis: int = 0,
                   concat_axis: int = 0, sent=None, pad=None,
                   received=None, track: bool = True):
        """Dense exchange; ``pad`` makes the received count sentinel-aware.

        ``sent`` defaults to every element of ``x`` (the whole buffer
        leaves conceptually; pass the exact off-device count when known).
        ``received`` overrides the landed count for buffers with no
        sentinel structure (e.g. the MoE return exchange, whose tiles
        are dense payload rows — only the caller knows how many carry
        real objects); it wins over ``pad``.
        """
        out = lax.all_to_all(x, axis_name, split_axis=split_axis,
                             concat_axis=concat_axis, tiled=False)
        if track:
            s = jnp.asarray(sent if sent is not None else int(np.prod(jnp.shape(x))))
            if received is not None:
                r = jnp.asarray(received)
            elif pad is not None:
                r = jnp.sum(out < jnp.asarray(pad, out.dtype))
            else:
                r = jnp.asarray(int(np.prod(jnp.shape(out))))
            self.record(sent=s, received=r)
        return out

    def all_gather_multi(self, x, axis_names, *, count=None,
                         track: bool = True):
        """Ordered nested gather over factored sub-axes.

        The result's leading dims are the axis sizes, outermost first
        (``axis_names=("i1", "i2")`` over a (c,)-operand yields
        (t1, t2, c) in global machine order g = i1*t2 + i2 — reshape to
        (t, c) reproduces the flat gather bitwise).  Each hop's traffic
        is recorded separately: the relayed copies genuinely transit the
        network twice, and k_network must see that.
        """
        c = jnp.asarray(count if count is not None else _leading_count(x))
        out = x
        for name in reversed(tuple(axis_names)):
            out = self.all_gather(out, name, count=c, track=track)
            c = c * lax.psum(1, name)
        return out

    def staged_all_to_all(self, keys_buf, axis_names, *, values_buf=None,
                          sent=None, pad=None, restage=None, chunks: int = 1,
                          chunk_fn=None, phase_prefix: str = "shuffle"):
        """Two-hop exchange over factored sub-axes (the AMS-style staging).

        Stage 1 is one all_to_all over ``axis_names[0]``: row g of
        ``keys_buf`` is addressed to machine *group* g.  Between the
        hops, ``restage(landed_keys, landed_values)`` maps the stage-1
        landing to ``(buf2, vals2, sent2)`` with ``buf2`` rows addressed
        to the final machines along ``axis_names[1]`` — the compacted
        exchange passes its merge + re-partition here.  Without a
        ``restage``, a pure relay runs: ``keys_buf`` must then be
        (t1, t2, C) with block [g, d2] addressed to machine (g, d2), and
        the stage-2 landing, reassembled source-major, is bitwise equal
        to the flat t-way all_to_all of the same buffer.  ``values_buf``
        (one array or a tuple of arrays) rides beside the keys, untracked.

        Stage 2 is issued in ``chunks`` column slices; ``chunk_fn(keys,
        values)`` runs on each landed chunk *between* the chunked
        collectives — on an async runtime chunk i's merge overlaps chunk
        i+1's exchange (double-buffering).  ``chunks`` must divide the
        stage-2 row length.

        Each stage records into its own phase (``"<prefix> s1"`` /
        ``"<prefix> s2"``) so alpha counts the extra synchronization and
        k_network's per-phase max sees each stage's true peak — exactly
        the accounting the flat exchange gets for its single phase.
        Returns ``(chunk_outputs, sent_stage2)``.
        """
        a1, a2 = axis_names
        with self.phase(f"{phase_prefix} s1"):
            rk = self.all_to_all(keys_buf, a1, sent=sent, pad=pad)
            rv = (self.all_to_all(values_buf, a1, track=False)
                  if values_buf is not None else None)
        if restage is not None:
            buf2, vals2, sent2 = restage(rk, rv)
        else:
            if rk.ndim < 2:
                raise ValueError("relay staging needs a (t1, t2, ...) "
                                 "buffer; pass restage= for other layouts")
            swap = lambda y: jnp.reshape(jnp.swapaxes(y, 0, 1),
                                         (y.shape[1], -1))
            buf2 = swap(rk)
            vals2 = jax.tree.map(swap, rv) if rv is not None else None
            if pad is not None:
                vrow = jnp.sum(
                    (buf2 < jnp.asarray(pad, buf2.dtype)).reshape(
                        buf2.shape[0], -1), axis=1)
                sent2 = jnp.sum(vrow) - vrow[lax.axis_index(a2)]
            else:
                sent2 = jnp.asarray(
                    (buf2.shape[0] - 1) * int(np.prod(buf2.shape[1:])))
        chunks = max(1, int(chunks))
        width = buf2.shape[1]
        if width % chunks != 0:
            raise ValueError(f"chunks={chunks} must divide the stage-2 "
                             f"row length {width}")
        cc = width // chunks
        outs = []
        with self.phase(f"{phase_prefix} s2"):
            for j in range(chunks):
                ck = lax.slice_in_dim(buf2, j * cc, (j + 1) * cc, axis=1)
                cv = (jax.tree.map(lambda v: lax.slice_in_dim(
                          v, j * cc, (j + 1) * cc, axis=1), vals2)
                      if vals2 is not None else None)
                s = sent2 if j == 0 else jnp.zeros((), jnp.int32)
                ok = self.all_to_all(ck, a2, sent=s, pad=pad)
                ov = (self.all_to_all(cv, a2, track=False)
                      if cv is not None else None)
                outs.append(chunk_fn(ok, ov) if chunk_fn is not None
                            else (ok, ov))
        return outs, sent2

    def ragged_all_to_all(self, operand, output, input_offsets, send_sizes,
                          output_offsets, recv_sizes, *, axis_name: str,
                          sent=None, track: bool = True):
        out = lax.ragged_all_to_all(
            operand, output, input_offsets, send_sizes, output_offsets,
            recv_sizes, axis_name=axis_name)
        if track:
            s = jnp.asarray(sent if sent is not None else jnp.sum(send_sizes))
            self.record(sent=s, received=jnp.sum(recv_sizes))
        return out

    def psum(self, x, axis_name: str, *, count=None):
        """Reduction; O(1) control scalars are untracked by default."""
        out = lax.psum(x, _flat(axis_name))
        if count is not None:
            c = jnp.asarray(count)
            self.record(sent=c, received=c)
        return out

    # ------------------------------------------------------------------
    # host-side API
    # ------------------------------------------------------------------
    def traced(self):
        """The in-program counters, to be returned as program outputs."""
        return tuple(self._traced)

    def bind(self, frames: Sequence) -> None:
        """Attach concrete (t,)-shaped counters from the executed program."""
        frames = list(frames)
        assert len(frames) == len(self._entry_phase), (
            f"tape recorded {len(self._entry_phase)} entries but got "
            f"{len(frames)} frames back")
        self._bound = [(np.asarray(s).reshape(-1), np.asarray(r).reshape(-1))
                       for (s, r) in frames]

    @property
    def is_bound(self) -> bool:
        return self._bound is not None

    def bound_snapshot(self, frames: Sequence) -> "CollectiveTape":
        """A private tape bound to ``frames`` with this tape's static phase
        metadata.

        Compiled-program caches keep ONE tape per cached program (its
        phase layout was fixed at trace time); binding concrete counters
        onto that shared tape would let a later run clobber an earlier
        run's numbers between ``run()`` and ``report()``.  Each execution
        therefore gets its own bound snapshot — the shared tape is only
        ever mutated at trace time, under the substrate's lock.
        """
        snap = CollectiveTape()
        snap._phase_order = list(self._phase_order)
        snap._entry_phase = list(self._entry_phase)
        snap.bind(frames)
        return snap

    def phases(self, t: int):
        """Merge bound entries into one PhaseStats per declared phase."""
        from repro.core.alpha_k import PhaseStats
        assert self._bound is not None, "tape not bound — run it first"
        sent: Dict[str, np.ndarray] = {p: np.zeros(t) for p in self._phase_order}
        recv: Dict[str, np.ndarray] = {p: np.zeros(t) for p in self._phase_order}
        for name, (s, r) in zip(self._entry_phase, self._bound):
            sent[name] = sent[name] + np.broadcast_to(s, (t,))
            recv[name] = recv[name] + np.broadcast_to(r, (t,))
        return [PhaseStats(p, sent[p], recv[p]) for p in self._phase_order]

    def report(self, *, algorithm: str, t: int, n_in: int, n_out: int,
               workload):
        from repro.core.alpha_k import AlphaKReport
        return AlphaKReport(algorithm=algorithm, t=t, n_in=n_in, n_out=n_out,
                            workload=np.asarray(workload).reshape(-1),
                            phases=self.phases(t))
