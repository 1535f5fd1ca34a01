"""Host spans around the program's layers, for traced runs only.

``bench/spans.json`` names, for each span, a function of the program as
``module:attribute`` (``Class.method`` for a method).  ``install()``
wraps each in a ``jax.profiler.TraceAnnotation`` of that name, where its
callers look it up, so the profiler's trace shows what the host was
doing in each idle gap of the device.  Runs with ``--trace 0`` install
nothing.
"""
from __future__ import annotations

import functools
import importlib

from bench.registry import BENCH_DIR, load_json


def _wrap(fn, span: str):
    import jax

    @functools.wraps(fn)
    def traced(*args, **kw):
        with jax.profiler.TraceAnnotation(span):
            return fn(*args, **kw)
    traced.__bench_span__ = span
    return traced


def install(path=BENCH_DIR / "spans.json") -> None:
    """Wrap every function the file names (once per process)."""
    for span, target in load_json(path)["spans"].items():
        module, attr = target.split(":")
        owner = importlib.import_module(module)
        *outer, name = attr.split(".")
        for part in outer:
            owner = getattr(owner, part)
        fn = getattr(owner, name)
        if getattr(fn, "__bench_span__", None) is None:
            setattr(owner, name, _wrap(fn, span))
