"""Device idle time put down to the store's own spans.

The store writes a span at each layer of its read path (``store.plan``,
``store.fetch``, ``store.decode``, ``store.parse``, ``store.stage``,
``store.h2d``, ``store.dispatch``; ``repro.lake.spans``) into the profiler's
trace. For each such name this takes the union of its intervals over every
host thread and intersects it with the device's idle intervals inside the
traced window (``chipbench.window``, the same idle intervals as
``trace.reduce``'s gaps); ``any`` is the union of all of them. Longest
first, in seconds: which layers the host was in while the chip waited.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

from . import trace as tracing

PREFIX = "store."
ANY = "any"


def covered(a: np.ndarray, b: np.ndarray) -> int:
    """Length of the intersection of two sorted, disjoint ``(n, 2)`` sets."""
    total, i, j = 0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i, 0], b[j, 0]), min(a[i, 1], b[j, 1])
        if hi > lo:
            total += int(hi - lo)
        if a[i, 1] < b[j, 1]:
            i += 1
        else:
            j += 1
    return total


def attribute(idle: np.ndarray,
              spans: Dict[str, Tuple[np.ndarray, np.ndarray]]
              ) -> List[Tuple[str, float]]:
    """``[(name, seconds)]`` of ``idle`` (ns intervals, sorted, disjoint)
    that each name's ``(starts, ends)`` cover, and :data:`ANY` for their
    union; longest first."""
    out = [(name, covered(tracing.union(s, e), idle) * 1e-9)
           for name, (s, e) in spans.items()]
    if spans:
        s = np.concatenate([s for s, _ in spans.values()])
        e = np.concatenate([e for _, e in spans.values()])
        out.append((ANY, covered(tracing.union(s, e), idle) * 1e-9))
    return sorted(out, key=lambda kv: -kv[1])


def idle_by_span(path: str) -> List[Tuple[str, float]]:
    """:func:`attribute` for the ``store.`` spans of the trace at ``path``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    window = None
    device_lines = []
    found: Dict[str, Tuple[List[int], List[int]]] = {}
    for plane in data.planes:
        if tracing.DEVICE_PLANE.match(plane.name):
            device_lines.extend(ln for ln in plane.lines
                                if ln.name == tracing.OPS_LINE)
        elif tracing.HOST_PLANE.match(plane.name):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == tracing.WINDOW:
                        window = (int(ev.start_ns),
                                  int(ev.start_ns + ev.duration_ns))
                    elif ev.name.startswith(PREFIX):
                        s, e = found.setdefault(ev.name, ([], []))
                        s.append(int(ev.start_ns))
                        e.append(int(ev.start_ns + ev.duration_ns))
    if window is None:
        raise ValueError(f"{path}: no {tracing.WINDOW!r} span on the host")
    lo, hi = window
    # the first device that ran anything in the window, as trace.reduce
    busy = np.zeros((0, 2), np.int64)
    for line in device_lines:
        s, e, _ = tracing._events(line)
        s, e = np.clip(s, lo, hi), np.clip(e, lo, hi)
        inside = e > s
        if inside.any():
            busy = tracing.union(s[inside], e[inside])
            break
    return attribute(tracing.gaps(busy, lo, hi),
                     {name: (np.asarray(s, np.int64), np.asarray(e, np.int64))
                      for name, (s, e) in found.items()})
