"""Spans of the store's read path: host time per layer, on the profiler's clock.

Off by default; :func:`enable` switches them. While on, each
``span(name, **attrs)`` block

* enters ``jax.profiler.TraceAnnotation(name, **attrs)``, so a profiler
  trace shows it on the same clock as the device's ``XLA Ops``;
* adds its time to an in-memory table per name: ``count``, ``total_s``
  and ``self_s``, the duration less the part covered by child spans on
  the same thread.

:func:`snapshot` reads the table (``store.io_stats()["spans"]``) and
:func:`reset` clears it.

Every span carries ``read=<id>``. :func:`new_read` gives each
``TensorRef.read_device`` call a fresh id on the reading thread; work handed
to the fetch and decode pools takes the id along as an argument and adopts
it there with :func:`in_read` (a context variable does not cross a thread
pool).

A span marks work a thread does; time a thread spends blocked is a
counter of :class:`~repro.lake.io.ReadStats`, never a span.

Off, :func:`span` returns one shared no-op context: it allocates nothing and
never imports jax. This module imports nothing from the rest of the package.
"""

from __future__ import annotations

import contextvars
import itertools
import threading
import time
from contextlib import nullcontext
from typing import Any, Dict, List, Optional

_OFF = nullcontext()
_on = False
_annotation: Any = None  # jax.profiler.TraceAnnotation, bound by enable()
_lock = threading.Lock()
_table: Dict[str, List[float]] = {}  # name -> [count, total_s, self_s]
_stacks = threading.local()  # .open: child seconds of each open span
_read: contextvars.ContextVar[Optional[int]] = contextvars.ContextVar(
    "repro_lake_read", default=None)
_read_ids = itertools.count(1)


def enable(on: bool = True) -> None:
    """Turn spans on (importing ``jax.profiler``) or off."""
    global _on, _annotation
    if on and _annotation is None:
        from jax.profiler import TraceAnnotation
        _annotation = TraceAnnotation
    _on = bool(on)


def snapshot() -> Dict[str, Dict[str, float]]:
    """``{name: {"count", "total_s", "self_s"}}`` of every span so far."""
    with _lock:
        return {name: {"count": int(c), "total_s": t, "self_s": s}
                for name, (c, t, s) in _table.items()}


def reset() -> None:
    """Clear the table."""
    with _lock:
        _table.clear()


def current_read() -> Optional[int]:
    """The read id of this thread's spans, or None outside a read."""
    return _read.get()


class _Timer:
    """Times its block with one ``perf_counter`` pair: ``.seconds``."""

    __slots__ = ("seconds", "_t0")

    def __enter__(self) -> "_Timer":
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc: Any) -> None:
        self.seconds = time.perf_counter() - self._t0


class _Span(_Timer):
    __slots__ = ("name", "_ann")

    def __init__(self, name: str, read: Optional[int],
                 attrs: Dict[str, Any]):
        if read is None:
            read = _read.get()
        if read is not None:
            attrs["read"] = read
        self.name = name
        self._ann = _annotation(name, **attrs)

    def __enter__(self) -> "_Span":
        self._ann.__enter__()
        _open().append(0.0)
        return super().__enter__()

    def __exit__(self, *exc: Any) -> None:
        super().__exit__(*exc)
        d = self.seconds
        opened = _open()
        children = opened.pop()
        if opened:
            opened[-1] += d
        with _lock:
            row = _table.setdefault(self.name, [0, 0.0, 0.0])
            row[0] += 1
            row[1] += d
            row[2] += d - children
        self._ann.__exit__(*exc)


def _open() -> List[float]:
    stack = getattr(_stacks, "open", None)
    if stack is None:
        stack = _stacks.open = []
    return stack


def span(name: str, read: Optional[int] = None, **attrs: Any) -> Any:
    """A span named ``name`` around the block; the shared no-op when off.

    ``read`` defaults to the thread's current read id (:func:`new_read`,
    :func:`in_read`); ``attrs`` go into the trace event beside it.
    """
    if not _on:
        return _OFF
    return _Span(name, read, attrs)


def timed(name: str) -> _Timer:
    """:func:`span` whose block is timed on or off: the context's
    ``.seconds`` is the span's own duration, for a counter that must agree
    with it."""
    if not _on:
        return _Timer()
    return _Span(name, None, {})


class _ReadScope:
    __slots__ = ("read", "_token")

    def __init__(self, read: int):
        self.read = read

    def __enter__(self) -> int:
        self._token = _read.set(self.read)
        return self.read

    def __exit__(self, *exc: Any) -> None:
        _read.reset(self._token)


def new_read() -> Any:
    """A fresh read id for this thread's spans inside the block; the shared
    no-op when off."""
    if not _on:
        return _OFF
    return _ReadScope(next(_read_ids))


def in_read(read: Optional[int]) -> Any:
    """Adopt ``read``, an id captured on another thread, for this thread's
    spans inside the block; the shared no-op when off or outside a read."""
    if not _on or read is None:
        return _OFF
    return _ReadScope(read)
