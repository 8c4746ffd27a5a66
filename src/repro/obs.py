"""Program spans and counters on the served path, recorded only while a
JAX profiler trace is being collected (``jax.profiler.trace``,
``start_trace`` or the profiler server).

:func:`span` then enters a ``jax.profiler.TraceAnnotation``, so the span
lands on the trace's clock beside the device's operations, and appends
``(name, t0, t1, parent, root)`` on ``time.perf_counter()`` to an
in-memory registry: ``parent`` is the enclosing span's index and ``root``
the outermost one's, shared by every span of one served batch.
:func:`count` appends ``(name, t, n, span)`` under the innermost open
span; :func:`to_host` counts ``host_sync`` and ``host_sync_bytes`` per
device→host read; JAX's jaxpr-trace and backend-compile events count as
``compile`` (2 for a fresh ``jit``).  With no trace running each call
costs one ``is_enabled()`` check and allocates nothing.  Nothing here
waits on the device.  Readers take the registry through :func:`snapshot`.
"""
from __future__ import annotations

import functools
import threading
import time

import jax
import numpy as np

__all__ = ["span", "count", "to_host", "snapshot", "clear", "MAX_RECORDS"]

#: Records kept per kind (spans, counts); a capture that outgrows it keeps
#: its first records and counts the rest as ``dropped``.
MAX_RECORDS = 1 << 18

_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/backend_compile_duration")

_recording = jax.profiler.TraceAnnotation.is_enabled
_lock = threading.Lock()
_local = threading.local()
_spans: list = []        # [name, t0, t1 (None while open), parent, root]
_counts: list = []       # (name, t, n, innermost open span)
_dropped = 0


def _open() -> list:
    """This thread's open spans, innermost last (registry indices)."""
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


def _decorate(name: str, fn):
    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        with span(name):
            return fn(*args, **kwargs)
    return wrapped


class _Null:
    """What :func:`span` returns while nothing records: one per name."""

    __slots__ = ("name",)

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def __call__(self, fn):
        return _decorate(self.name, fn)


class _Span(_Null):
    __slots__ = ("_ann", "_rec")

    def __enter__(self):
        global _dropped
        self._ann = jax.profiler.TraceAnnotation(self.name)
        self._ann.__enter__()
        stack = _open()
        parent = stack[-1] if stack else None
        with _lock:
            if len(_spans) < MAX_RECORDS:
                i = len(_spans)
                self._rec = [self.name, time.perf_counter(), None, parent,
                             stack[0] if stack else i]
                _spans.append(self._rec)
            else:
                i, self._rec = None, None
                _dropped += 1
        stack.append(i)
        return self

    def __exit__(self, *exc):
        if self._rec is not None:
            self._rec[2] = time.perf_counter()
        _open().pop()
        self._ann.__exit__(*exc)
        return False


_nulls = {}


def span(name: str):
    """A program span named ``name``: a context manager, or a decorator
    that opens one around each call."""
    if _recording():
        return _Span(name)
    null = _nulls.get(name)
    if null is None:
        null = _nulls[name] = _Null(name)
    return null


def _record(name: str, n) -> None:
    global _dropped
    stack = _open()
    with _lock:
        if len(_counts) < MAX_RECORDS:
            _counts.append((name, time.perf_counter(), n,
                            stack[-1] if stack else None))
        else:
            _dropped += 1


def count(name: str, n=1) -> None:
    """Add ``n`` to counter ``name`` under the innermost open span."""
    if _recording():
        _record(name, n)


def to_host(x, dtype=None) -> np.ndarray:
    """``np.asarray(x, dtype)``, counting the read when ``x`` is a device
    array: ``host_sync`` 1 and ``host_sync_bytes`` its size."""
    if _recording() and isinstance(x, jax.Array):
        _record("host_sync", 1)
        _record("host_sync_bytes", int(x.nbytes))
    return np.asarray(x, dtype)


def _on_duration(event: str, duration: float, **kwargs) -> None:
    if event in _COMPILE_EVENTS and _recording():
        _record("compile", 1)


jax.monitoring.register_event_duration_secs_listener(_on_duration)


def snapshot() -> dict:
    """The registry: ``spans`` as ``(name, t0, t1, parent, root)`` tuples
    (``t1`` None while open), ``counts`` as ``(name, t, n, span)``, and
    how many records the cap ``dropped``."""
    with _lock:
        return {"spans": [tuple(s) for s in _spans], "counts": list(_counts),
                "dropped": _dropped}


def clear() -> None:
    """Empty the registry.  Call it between captures, with no span open."""
    global _dropped
    with _lock:
        _spans.clear()
        _counts.clear()
        _dropped = 0
