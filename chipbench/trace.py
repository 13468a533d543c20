"""Reduce a profiler trace to device busy time, idle share and breakdowns.

The window is the host span named :data:`WINDOW`, which the harness opens
around the measured window. Busy time is the union of the intervals of the
operations on each TPU's ``XLA Ops`` line inside it, whatever their names,
averaged over the TPUs that ran any. The idle gaps are what is left of the
window; each of the longest is labelled with the host event that overlaps
it most, which says what the host was doing while the device waited.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

WINDOW = "chipbench.window"
READ = "chipbench.read"
OPS_LINE = "XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = re.compile(r"^/host:")
# spans that cover whole reads or the window: they say nothing of a gap
NOT_A_LABEL = {WINDOW, READ}


@dataclass
class Summary:
    """One traced window, reduced."""

    window_s: float
    busy_s: float
    devices: int
    device_ops: List[Tuple[str, float]] = field(default_factory=list)
    idle_gaps: List[Tuple[str, float]] = field(default_factory=list)

    @property
    def idle_share(self) -> float:
        """Share of the window with no device operation running."""
        return 1.0 - self.busy_s / self.window_s if self.window_s else 0.0


def union(starts: np.ndarray, ends: np.ndarray) -> np.ndarray:
    """Merge intervals; returns ``(m, 2)`` disjoint, sorted intervals."""
    if len(starts) == 0:
        return np.zeros((0, 2), np.int64)
    order = np.argsort(starts, kind="stable")
    s, e = np.asarray(starts)[order], np.asarray(ends)[order]
    reach = np.maximum.accumulate(e)
    new = np.ones(len(s), bool)
    new[1:] = s[1:] > reach[:-1]
    heads = np.flatnonzero(new)
    return np.stack([s[heads], np.maximum.reduceat(e, heads)], axis=1)


def gaps(busy: np.ndarray, lo: int, hi: int) -> np.ndarray:
    """The parts of ``[lo, hi)`` that no interval of ``busy`` covers."""
    edges = np.concatenate([[lo], busy.ravel(), [hi]]).reshape(-1, 2)
    edges[:, 0] = np.clip(edges[:, 0], lo, hi)
    edges[:, 1] = np.clip(edges[:, 1], lo, hi)
    return edges[edges[:, 1] > edges[:, 0]]


def find(trace_dir: str) -> Optional[str]:
    """The newest ``.xplane.pb`` under ``trace_dir``, or None."""
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def _events(line) -> Tuple[np.ndarray, np.ndarray, List[str]]:
    s, e, n = [], [], []
    for ev in line.events:
        s.append(int(ev.start_ns))
        e.append(int(ev.start_ns + ev.duration_ns))
        n.append(ev.name)
    return np.asarray(s, np.int64), np.asarray(e, np.int64), n


def reduce(path: str, top: int = 10) -> Summary:
    """Read the trace at ``path`` and reduce its window."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    host_s, host_e, host_n = [], [], []
    window: Optional[Tuple[int, int]] = None
    device_lines = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            device_lines.extend(ln for ln in plane.lines
                                if ln.name == OPS_LINE)
        elif HOST_PLANE.match(plane.name):
            for line in plane.lines:
                s, e, n = _events(line)
                for i, name in enumerate(n):
                    if name == WINDOW:
                        window = (int(s[i]), int(e[i]))
                host_s.append(s)
                host_e.append(e)
                host_n.extend(n)
    if window is None:
        raise ValueError(f"{path}: no {WINDOW!r} span on the host")
    lo, hi = window
    busy_each, op_time = [], {}
    first_busy = np.zeros((0, 2), np.int64)
    for line in device_lines:
        s, e, n = _events(line)
        s, e = np.clip(s, lo, hi), np.clip(e, lo, hi)
        inside = e > s
        if not inside.any():
            continue
        merged = union(s[inside], e[inside])
        if not len(busy_each):
            first_busy = merged
        busy_each.append(int((merged[:, 1] - merged[:, 0]).sum()))
        for name, d in zip((x for x, k in zip(n, inside) if k),
                           (e - s)[inside]):
            op_time[name] = op_time.get(name, 0) + int(d)
    devices = len(busy_each)
    busy_ns = sum(busy_each) / devices if devices else 0.0
    ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:top]
    scale = 1e-9 / max(devices, 1)
    hs = np.concatenate(host_s) if host_s else np.zeros(0, np.int64)
    he = np.concatenate(host_e) if host_e else np.zeros(0, np.int64)
    idle = gaps(first_busy, lo, hi) if devices else np.array([[lo, hi]])
    longest = idle[np.argsort(-(idle[:, 1] - idle[:, 0]), kind="stable")][:top]
    return Summary(
        window_s=(hi - lo) * 1e-9, busy_s=busy_ns * 1e-9, devices=devices,
        device_ops=[(name, t * scale) for name, t in ops],
        idle_gaps=[(_label(hs, he, host_n, int(g0), int(g1)),
                    (int(g1) - int(g0)) * 1e-9) for g0, g1 in longest])


def _label(hs: np.ndarray, he: np.ndarray, names: List[str],
           g0: int, g1: int) -> str:
    """The host event that overlaps ``[g0, g1)`` most, by name."""
    over = np.minimum(he, g1) - np.maximum(hs, g0)
    hit = np.flatnonzero(over > 0)
    by_name: Dict[str, int] = {}
    for i in hit:
        if names[i] not in NOT_A_LABEL:
            by_name[names[i]] = by_name.get(names[i], 0) + int(over[i])
    if not by_name:
        return "no host event"
    return max(by_name.items(), key=lambda kv: kv[1])[0]
