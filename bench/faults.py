"""Planted faults and the lower-precision control, for proving that the
check refuses what it should.

``install(name)`` replaces the front door (``repro.cluster.sort`` and
``repro.cluster.join``) with a broken twin.  Every driver reaches the
system through that attribute, so the rest of a run is unchanged.  The benchmark's own runs never install one; the
control runs on the chip with ``bench/run.py --fault control``, and
``bench/tests/test_faults.py`` drives each fault at a small size.

* ``control``     the plain reference in the system's place, one
  precision down (bfloat16 sort keys, int16 join keys), with the (alpha,
  k) report of the system's own run on the same inputs, so that only
  the comparison of answers can refuse it;
* ``unchanged``   the job returns its input state: records unsorted, no
  join pairs;
* ``half``        half of the machines' rows left out;
* ``no_exchange`` every machine sorts or joins only its own rows;
* ``altered``     one key or one row id of the answer altered where it
  is produced.
"""
from __future__ import annotations

import types

import numpy as np

from bench.reference.join import control_join, join_reference
from bench.reference.sort import control_sort

FAULTS = ("control", "unchanged", "half", "no_exchange", "altered")


def _host(a):
    return None if a is None else np.asarray(a)


def _sorted_rows(x, v):
    """Each row sorted on its own (a machine that never exchanged)."""
    order = np.argsort(x, axis=1, kind="stable")
    keys = np.take_along_axis(x, order, axis=1).reshape(-1)
    vals = None if v is None else \
        np.take_along_axis(v, order, axis=1).reshape(-1)
    return keys, vals


def _pairs(s_rows, t_rows):
    s_rows, t_rows = np.asarray(s_rows), np.asarray(t_rows)
    return types.SimpleNamespace(
        s_rows=s_rows, t_rows=t_rows, valid=np.ones(s_rows.shape, bool),
        dropped=np.zeros(1, np.int32))


def sort_fault(name, real):
    def broken(x, *, values=None, **kw):
        if name == "half":
            t = np.shape(x)[0]
            half = slice(0, max(1, t // 2))
            return real(x[half], values=None if values is None
                        else values[half], **kw)
        if name == "altered":
            (keys, vals), report = real(x, values=values, **kw)
            keys = np.array(keys, copy=True)
            keys[keys.size // 2] = np.nextafter(keys[keys.size // 2],
                                                np.float32(np.inf))
            return (keys, vals), report
        xh, vh = _host(x), _host(values)
        if name == "control":
            _, report = real(x, values=values, **kw)
            return control_sort(xh, vh), report
        if name == "unchanged":
            return (xh.reshape(-1), None if vh is None
                    else vh.reshape(-1)), None
        if name == "no_exchange":
            return _sorted_rows(xh, vh), None
        raise ValueError(name)
    return broken


def join_fault(name, real):
    def broken(s_keys, s_rows, t_keys, t_rows, **kw):
        if name == "half":
            half = np.shape(s_keys)[0] // 2
            return real(s_keys[:half], s_rows[:half], t_keys, t_rows, **kw)
        if name == "altered":
            out, report = real(s_keys, s_rows, t_keys, t_rows, **kw)
            valid = np.asarray(out.valid).reshape(-1).astype(bool)
            s_rows_o = np.asarray(out.s_rows).reshape(-1)[valid]
            t_rows_o = np.array(np.asarray(out.t_rows).reshape(-1)[valid])
            t_rows_o[t_rows_o.size // 2] += 1
            return _pairs(s_rows_o, t_rows_o), report
        sk, sr, tk, tr = (np.asarray(a) for a in (s_keys, s_rows, t_keys,
                                                  t_rows))
        if name == "control":
            _, report = real(s_keys, s_rows, t_keys, t_rows, **kw)
            s_idx, t_idx = control_join(sk, tk)
            return _pairs(sr[s_idx], tr[t_idx]), report
        if name == "unchanged":
            return _pairs(np.zeros(0, np.int32), np.zeros(0, np.int32)), None
        if name == "no_exchange":
            t = int(kw["t_machines"])
            got_s, got_t = [], []
            for s_part, t_part in zip(np.array_split(np.arange(sk.size), t),
                                      np.array_split(np.arange(tk.size), t)):
                pairs = join_reference(sk[s_part], tk[t_part])
                got_s.append(sr[s_part][pairs >> 32])
                got_t.append(tr[t_part][pairs & 0xFFFFFFFF])
            return _pairs(np.concatenate(got_s), np.concatenate(got_t)), None
        raise ValueError(name)
    return broken


def install(name: str) -> None:
    """Break the front door for the rest of this process."""
    if name not in FAULTS:
        raise ValueError(f"unknown fault {name!r}; expected one of {FAULTS}")
    from repro import cluster
    cluster.sort = sort_fault(name, cluster.sort)
    cluster.join = join_fault(name, cluster.join)
