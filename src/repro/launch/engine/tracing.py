"""Spans of the serving engine's host code, kept in memory while recording.

Off by default. ``span(name)`` then reads one module flag and returns a
shared no-op context, which allocates nothing and yields ``None``::

    with tracing.span("scheduler.fetch") as sp:
        if sp:                       # recording: attach attributes
            sp.set(what="tokens")
        toks = np.asarray(device_toks)

``record()`` turns recording on for the body of a ``with`` and yields
the list the finished spans are appended to. A ``Span`` holds its
``name``, ``start`` and ``end`` (``time.monotonic``, the clock of the
request handles' stamps), the enclosing span on the same thread
(``parent``), the ``thread`` and its ``attrs``. Spans that act on
requests carry their uids, so the spans of one request share its
identifier. While recording, each span also enters
``jax.profiler.TraceAnnotation(name)``: under a profiler trace the spans
land in the trace, on the device trace's clock.

Spans live in host code only, never inside a jitted function. Parents
are tracked per thread, since a ``ReplicaSet`` may step its replicas on
worker threads.
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Optional

import jax

_sink: Optional[list] = None      # the recording's list; None when off
_local = threading.local()        # per-thread stack of open spans


class _Noop:
    """The shared context ``span()`` returns while recording is off."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NOOP = _Noop()


class Span:
    """One timed stretch of host code (see the module docstring)."""

    __slots__ = ("name", "start", "end", "parent", "thread", "attrs",
                 "_note", "_sink")

    def __init__(self, name: str, sink: list):
        self.name = name
        self.start = self.end = None
        self.parent: Optional[Span] = None
        self.thread = threading.get_ident()
        self.attrs: dict = {}
        self._note = jax.profiler.TraceAnnotation(name)
        self._sink = sink

    def set(self, **attrs):
        """Attach attributes (request uids, sizes, kinds)."""
        self.attrs.update(attrs)

    def __enter__(self) -> "Span":
        stack = _local.__dict__.setdefault("stack", [])
        self.parent = stack[-1] if stack else None
        stack.append(self)
        self._note.__enter__()
        self.start = time.monotonic()
        return self

    def __exit__(self, *exc):
        self.end = time.monotonic()
        self._note.__exit__(*exc)
        _local.stack.pop()
        self._sink.append(self)
        return False

    def __repr__(self):
        return (f"Span({self.name!r}, {self.start}, {self.end}, "
                f"{self.attrs})")


def span(name: str):
    """A context for one span named ``name``: a recorded ``Span`` while
    ``record()`` is on, else the shared no-op, which yields ``None``."""
    if _sink is None:
        return _NOOP
    return Span(name, _sink)


@contextlib.contextmanager
def record():
    """Record every span closed in the body, on every thread; yields the
    list they are appended to, in the order they close."""
    global _sink
    if _sink is not None:
        raise RuntimeError("tracing.record() is already on")
    spans: list[Span] = []
    _sink = spans
    try:
        yield spans
    finally:
        _sink = None
