"""Spans and counters of the program, for whoever profiles it.

Spans record only while a JAX profile records (``jax.profiler.trace``
or ``start_trace``): the static ``TraceAnnotation.is_enabled()`` is the
whole check, so with no profile a span costs one call and returns a
shared null context.  While a profile records, ``span(name, **attrs)``
enters a ``jax.profiler.TraceAnnotation`` — the span shows in the
profile's host plane beside the device events — and keeps a
:class:`Span` in a bounded in-memory buffer, timed with
``time.perf_counter_ns()``.  Spans nest per thread: each carries its
parent's id, and every span of one call shares the ``call_id`` of the
outermost.

Counters (``count(name, key, n)``) are always on and cumulative over
the process: one locked dict update each.  ``spans()`` and
``counters()`` read both; ``reset()`` clears both.

The op registry (``repro.core.dispatch``) records, per eager call:

  * span ``repro.dispatch`` (attrs ``op``, ``method``, ``engine``,
    ``n``, ``bytes``) around routing and running the call, with child
    span ``repro.engine`` (attr ``engine``) around the engine run;
  * counters ``dispatch.calls`` and ``dispatch.bytes`` keyed
    ``(op, engine)``, the engine that served the call.

A call traced into a jitted function records neither, and the
autotuner's candidate runs (``dispatch.execute``) are not counted.
``resolve_method`` counts ``dispatch.fallbacks`` keyed
``(op, requested, served)`` each time it substitutes its fallback: a
jitted caller once per trace.

The paged KV store (``repro.models.kv_cache``) counts
``store.state_bytes`` keyed by leaf path: the bytes of each eager copy
of a dense per-slot leaf (recurrent state, cross-attention memory).
"""

from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import NamedTuple, Optional

import jax

MAX_SPANS = 1 << 16     # the oldest spans go first beyond this

_recording = jax.profiler.TraceAnnotation.is_enabled
_clock = time.perf_counter_ns


class Span(NamedTuple):
    id: int
    parent_id: Optional[int]
    call_id: int
    name: str
    t0_ns: int
    t1_ns: int
    attrs: dict


class _NullSpan:
    """What ``span`` returns while no profile records."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> None:
        pass

    def set(self, **attrs) -> None:
        pass


_NULL = _NullSpan()
_buffer: collections.deque = collections.deque(maxlen=MAX_SPANS)
_ids = itertools.count(1)
_stack = threading.local()
_counters: dict = {}
_lock = threading.Lock()


class _Recording:
    __slots__ = ("name", "attrs", "id", "parent_id", "call_id", "t0",
                 "_ann")

    def __init__(self, name: str, attrs: dict):
        self.name, self.attrs = name, attrs

    def set(self, **attrs) -> None:
        """Add attributes known only once the span is open."""
        self.attrs.update(attrs)
        self._ann.set_metadata(**attrs)

    def __enter__(self):
        stack = _stack.__dict__.setdefault("open", [])
        parent = stack[-1] if stack else None
        self.id = next(_ids)
        self.parent_id = parent.id if parent else None
        self.call_id = parent.call_id if parent else self.id
        stack.append(self)
        self.t0 = _clock()
        self._ann = jax.profiler.TraceAnnotation(self.name, **self.attrs)
        self._ann.__enter__()
        return self

    def __exit__(self, *exc) -> None:
        self._ann.__exit__(*exc)
        t1 = _clock()
        _stack.open.pop()
        _buffer.append(Span(self.id, self.parent_id, self.call_id,
                            self.name, self.t0, t1, self.attrs))


def span(name: str, **attrs):
    """A context manager timing ``name`` while a JAX profile records,
    and doing nothing otherwise.  What it yields has ``set(**attrs)``
    for attributes known only inside the span."""
    if not _recording():
        return _NULL
    return _Recording(name, attrs)


def count(name: str, key, n: int = 1) -> None:
    """Add ``n`` to counter ``name`` under ``key``."""
    with _lock:
        c = _counters.setdefault(name, {})
        c[key] = c.get(key, 0) + n


def spans() -> list:
    """The recorded spans, in the order they closed."""
    return list(_buffer)


def counters() -> dict:
    """``{name: {key: total}}``, a copy."""
    with _lock:
        return {name: dict(c) for name, c in _counters.items()}


def reset() -> None:
    """Forget every recorded span and counter."""
    with _lock:
        _counters.clear()
        _buffer.clear()
