"""Program spans on the JAX profiler's clock.

Every span is a ``jax.profiler.TraceAnnotation`` named ``earl.<name>``:
under an active profiler session (``jax.profiler.trace``) it lands in
the profiler's own trace beside the device ops, with its keyword stats;
without one it costs about a microsecond. There is no switch: the
profiler session is the switch.

``to_host`` is the one device-to-host read of the step's path: each read
is one ``earl.sync`` span, so the spans count the host's syncs and their
durations are the time it waited. ``watch_gc`` brackets Python's garbage
collections in ``earl.gc`` spans.
"""
from __future__ import annotations

import gc

import jax
import numpy as np

PREFIX = "earl."


def span(name: str, **stats):
    """A context manager spanning ``earl.<name>``, with numeric or string
    ``stats`` attached to the event."""
    return jax.profiler.TraceAnnotation(PREFIX + name, **stats)


def to_host(x, what: str, dtype=None) -> np.ndarray:
    """``np.asarray(x, dtype)`` inside an ``earl.sync`` span tagged
    ``what``."""
    with span("sync", what=what):
        return np.asarray(x, dtype)


_open_gc = []


def _on_gc(phase, info):
    if phase == "start":
        s = span("gc", generation=info["generation"])
        s.__enter__()
        _open_gc.append(s)
    elif _open_gc:
        _open_gc.pop().__exit__(None, None, None)


def watch_gc() -> None:
    """Span every garbage collection from its start to its stop (once per
    process; later calls do nothing)."""
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)
